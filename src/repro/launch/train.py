"""Training launcher — the two faces of the framework behind one CLI.

Simulation mode (the paper's experiment):
    PYTHONPATH=src python -m repro.launch.train sim \
        --strategy fedasync --alpha 0.4 --sigma 1.0 --rounds 40 \
        --ckpt-dir results/ckpt_sim

Distributed SPMD mode (fl_train_step on a host-device mesh; the same
program the dry-run lowers for the production meshes):
    PYTHONPATH=src python -m repro.launch.train spmd \
        --arch smollm-360m --devices 8 --data-axis 4 --steps 100 \
        --reduce d_model=256,n_layers=4,vocab=2048
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def run_sim(args):
    from repro.checkpoint import checkpoint as ckpt
    from repro.core.testbed import TestbedConfig, run_experiment
    from repro.data.synthetic_ser import SERDataConfig

    cfg = TestbedConfig(
        use_dp=args.sigma > 0, sigma=args.sigma, batch_size=args.batch,
        data=SERDataConfig(n_total=args.n_total), seed=args.seed,
    )
    kw = {}
    if args.strategy != "fedavg":
        kw.update(alpha=args.alpha, max_updates=args.max_updates)
    params, log = run_experiment(
        args.strategy, cfg, rounds=args.rounds, eval_every=args.eval_every,
        target_acc=args.target_acc, **kw)
    print(f"[train:sim] {args.strategy}: acc={log.global_acc[-1]:.3f} "
          f"virtual_time={log.times[-1]:.0f}s "
          f"updates={log.update_counts}")
    fr = log.fairness()
    eps = {k: round(v[-1], 2) for k, v in log.eps_trajectory.items() if v}
    print(f"[train:sim] eps={eps} disparity={fr['privacy_disparity']:.1f}x "
          f"jain={fr['jain_participation']:.2f}")
    if args.ckpt_dir:
        ckpt.save(args.ckpt_dir, len(log.times), params,
                  meta={"strategy": args.strategy, "sigma": args.sigma,
                        "acc": log.global_acc[-1]})
        print(f"[train:sim] checkpoint -> {args.ckpt_dir}")
    if args.log_json:
        with open(args.log_json, "w") as f:
            json.dump({"acc": log.global_acc, "times": log.times,
                       "eps": {k: v for k, v in log.eps_trajectory.items()},
                       "updates": log.update_counts}, f, default=float)
    return 0


def run_spmd(args):
    os.environ.setdefault(
        "XLA_FLAGS",
        f"--xla_force_host_platform_device_count={args.devices}")
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.checkpoint import checkpoint as ckpt
    from repro.configs import get_config
    from repro.core.dp import DPConfig
    from repro.core.fl_step import (
        FLStepConfig, make_fl_train_step, make_server_optimizer)
    from repro.data.tokens import TokenDataConfig, make_batches
    from repro.models import layers as Lyr
    from repro.models.base import get_family
    from repro.launch.mesh import auto_mesh
    from repro.launch.shardings import batch_spec, leaf_spec, tree_shardings

    cfg = get_config(args.arch)
    if args.reduce:
        overrides = {}
        for kv in args.reduce.split(","):
            k, v = kv.split("=")
            overrides[k] = int(v) if v.isdigit() else v
        cfg = cfg.replace(param_dtype="float32", **overrides)
    fam = get_family(cfg.family)

    G = args.data_axis
    mesh = auto_mesh((G, args.devices // G), ("data", "model"))
    Lyr.set_mesh_context(mesh, None, "model")  # no batch constraints (§Perf)

    fl = FLStepConfig(
        num_clients=G, n_local=args.n_local, n_micro=args.n_micro,
        local_lr=args.local_lr, server_lr=args.server_lr,
        dp=DPConfig(clip_norm=args.clip, noise_multiplier=args.sigma,
                    granularity="per_microbatch"),
        compute_dtype=cfg.param_dtype,
    )
    key = jax.random.PRNGKey(args.seed)
    params = fam.init_params(key, cfg)
    stacked_sds = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct((G,) + l.shape, l.dtype), params)
    client_sh = tree_shardings(stacked_sds, cfg, mesh, role="client")
    master_sh = tree_shardings(params, cfg, mesh, role="master")
    step = make_fl_train_step(
        lambda p, b: fam.loss(p, b, cfg), fl,
        client_shardings=client_sh, master_shardings=master_sh)
    sopt = make_server_optimizer(fl)
    opt_state = sopt.init(params)
    osh = jax.tree_util.tree_map(
        lambda l: NamedSharding(mesh, P() if l.ndim == 0
                                else leaf_spec(l.shape, cfg, mesh, "master")),
        opt_state)
    repl = NamedSharding(mesh, P())
    B = G * args.n_local * args.n_micro * args.per_micro
    bsp = {k: NamedSharding(mesh, batch_spec(mesh, 1))
           for k in ("tokens", "labels")}
    data = make_batches(
        TokenDataConfig(vocab=cfg.vocab, seq_len=args.seq, seed=args.seed),
        num_batches=args.steps, batch_size=B)
    weights = jnp.ones((G,)) / G
    eval_loss = jax.jit(lambda p, b: fam.loss(p, b, cfg))

    with jax.sharding.set_mesh(mesh):
        params = jax.device_put(params, master_sh)
        opt_state = jax.device_put(opt_state, osh)
        jitted = jax.jit(step, in_shardings=(master_sh, osh, bsp, repl, repl),
                         donate_argnums=(0, 1))
        for i, batch in enumerate(data):
            jb = jax.device_put(
                {k: jnp.asarray(v) for k, v in batch.items()}, bsp)
            if i % args.log_every == 0:
                print(f"[train:spmd] round {i:5d} "
                      f"loss {float(eval_loss(params, jb)):.4f}", flush=True)
            params, opt_state, _ = jitted(
                params, opt_state, jb, weights, jax.random.PRNGKey(i))
        final = float(eval_loss(params, jb))
    print(f"[train:spmd] final loss {final:.4f}")
    if args.ckpt_dir:
        ckpt.save(args.ckpt_dir, args.steps, params,
                  meta={"arch": args.arch, "loss": final})
        print(f"[train:spmd] checkpoint -> {args.ckpt_dir}")
    return 0


def main():
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="mode", required=True)

    sim = sub.add_parser("sim", help="paper testbed simulation")
    sim.add_argument("--strategy", default="fedasync",
                     choices=("fedavg", "fedasync", "fedasync_nostale",
                              "fedbuff", "adaptive_async"))
    sim.add_argument("--alpha", type=float, default=0.4)
    sim.add_argument("--sigma", type=float, default=1.0)
    sim.add_argument("--rounds", type=int, default=40)
    sim.add_argument("--max-updates", type=int, default=300)
    sim.add_argument("--batch", type=int, default=64)
    sim.add_argument("--n-total", type=int, default=2940)
    sim.add_argument("--eval-every", type=int, default=5)
    sim.add_argument("--target-acc", type=float, default=None)
    sim.add_argument("--seed", type=int, default=0)
    sim.add_argument("--ckpt-dir", default="")
    sim.add_argument("--log-json", default="")

    spmd = sub.add_parser("spmd", help="distributed fl_train_step")
    spmd.add_argument("--arch", default="smollm-360m")
    spmd.add_argument("--devices", type=int, default=8)
    spmd.add_argument("--data-axis", type=int, default=4)
    spmd.add_argument("--steps", type=int, default=100)
    spmd.add_argument("--seq", type=int, default=128)
    spmd.add_argument("--n-local", type=int, default=1)
    spmd.add_argument("--n-micro", type=int, default=4)
    spmd.add_argument("--per-micro", type=int, default=2)
    spmd.add_argument("--local-lr", type=float, default=0.5)
    spmd.add_argument("--server-lr", type=float, default=5e-3)
    spmd.add_argument("--clip", type=float, default=10.0)
    spmd.add_argument("--sigma", type=float, default=0.02)
    spmd.add_argument("--seed", type=int, default=0)
    spmd.add_argument("--log-every", type=int, default=25)
    spmd.add_argument("--ckpt-dir", default="")
    spmd.add_argument("--reduce", default="",
                      help="comma list of cfg overrides, e.g. d_model=256")

    args = ap.parse_args()
    sys.exit(run_sim(args) if args.mode == "sim" else run_spmd(args))


if __name__ == "__main__":
    main()
