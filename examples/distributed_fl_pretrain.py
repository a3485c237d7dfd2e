"""Distributed FL pretraining of a small language model with DP — the
datacenter-scale face of the paper's technique (core/fl_step.py).

    PYTHONPATH=src python examples/distributed_fl_pretrain.py \
        --steps 200 --devices 8

Spawns N virtual host devices, builds a ('data','model') mesh, and runs
``fl_train_step`` (per-client DP-SGD + staleness-weighted aggregation as
ONE pjit program) on a reduced smollm-family LM over the synthetic token
pipeline.  Loss decreasing over a few hundred federated rounds shows the
whole stack — model zoo, sharding rules, DP clipping, server Adam,
checkpointing — working end to end.
"""
import argparse
import os
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--data-axis", type=int, default=4)
    ap.add_argument("--sigma", type=float, default=0.02)
    ap.add_argument("--clip", type=float, default=10.0)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="results/fl_pretrain_ckpt")
    args = ap.parse_args()

    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={args.devices}")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
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

    G = args.data_axis
    mesh = auto_mesh((G, args.devices // G), ("data", "model"))
    cfg = get_config("smollm-360m").replace(
        n_layers=args.layers, d_model=args.d_model, n_heads=4, n_kv_heads=2,
        d_head=args.d_model // 4, d_ff=2 * args.d_model, vocab=2048,
        param_dtype="float32")
    fam = get_family(cfg.family)
    Lyr.set_mesh_context(mesh, "data", "model")

    # DP granularity note: per-microbatch clipping with few microbatches
    # needs a looser clip than the paper's per-example C=1 (the clipped
    # unit is a whole-model mean gradient, not one sample's), and the
    # noise norm scales with sqrt(n_params): per step it EXCEEDS the
    # clipped signal, and training still works only because the signal
    # accumulates coherently across rounds while the noise averages out —
    # the same reason the paper needs ~60 rounds to 75%.  sigma here is
    # deliberately small for a 200-round demo; production DP-FL buys SNR
    # with client count and per-example clipping.
    fl = FLStepConfig(
        num_clients=G, n_local=1, n_micro=4, local_lr=0.5, server_lr=5e-3,
        dp=DPConfig(clip_norm=args.clip, noise_multiplier=args.sigma,
                    granularity="per_microbatch"),
        compute_dtype="float32",
    )
    key = jax.random.PRNGKey(0)
    params = fam.init_params(key, cfg)
    stacked_sds = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct((G,) + l.shape, l.dtype), params)
    client_sh = tree_shardings(stacked_sds, cfg, mesh, role="client")
    step = make_fl_train_step(lambda p, b: fam.loss(p, b, cfg), fl,
                              client_shardings=client_sh)
    sopt = make_server_optimizer(fl)
    opt_state = sopt.init(params)

    msh = tree_shardings(params, cfg, mesh, role="master")
    osh = jax.tree_util.tree_map(
        lambda l: NamedSharding(mesh, P() if l.ndim == 0
                                else leaf_spec(l.shape, cfg, mesh, "master")),
        opt_state)
    repl = NamedSharding(mesh, P())
    B = G * 8  # 8 sequences per client round (4 microbatches of 2)
    bsp = {k: NamedSharding(mesh, batch_spec(mesh, 1))
           for k in ("tokens", "labels")}

    data = make_batches(
        TokenDataConfig(vocab=cfg.vocab, seq_len=args.seq, seed=0),
        num_batches=args.steps, batch_size=B)
    weights = jnp.ones((G,)) / G

    eval_loss = jax.jit(lambda p, b: fam.loss(p, b, cfg))
    with jax.sharding.set_mesh(mesh):
        params = jax.device_put(params, msh)
        opt_state = jax.device_put(opt_state, osh)
        jitted = jax.jit(step, in_shardings=(msh, osh, bsp, repl, repl),
                         donate_argnums=(0, 1))
        first_loss = None
        for i, batch in enumerate(data):
            jb = jax.device_put(
                {k: jnp.asarray(v) for k, v in batch.items()}, bsp)
            if i % 25 == 0 or i == args.steps - 1:
                loss = float(eval_loss(params, jb))
                first_loss = first_loss if first_loss is not None else loss
                print(f"[fl-pretrain] round {i:4d} loss {loss:.4f}")
            params, opt_state, _ = jitted(
                params, opt_state, jb, weights, jax.random.PRNGKey(i))
        final_loss = float(eval_loss(params, jb))

    ckpt.save(args.ckpt_dir, args.steps, params,
              meta={"sigma": args.sigma, "final_loss": final_loss})
    print(f"[fl-pretrain] loss {first_loss:.4f} -> {final_loss:.4f} "
          f"({args.steps} federated rounds, G={G} clients, DP sigma="
          f"{args.sigma}); checkpoint in {args.ckpt_dir}")
    assert final_loss < first_loss, "training did not reduce loss"


if __name__ == "__main__":
    main()
