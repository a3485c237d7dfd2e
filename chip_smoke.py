"""Run the cohort engine's main path once on a TPU, at the paper's widths.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the mesh phase, on four chips

The paper cell is ``TestbedConfig()`` as it stands: the SER CNN (64/128
conv filters, FC-128, 64x40 input, 317,124 params), 5,882 synthetic clips
over the five device tiers, B=128, per-example DP with C=1 and sigma=1,
Adam 1e-3.  Every run goes through the entry points a user calls:
``ExperimentSpec`` -> ``Session.run`` -> ``CohortRunner``.

One chip, in order:

1. FedAsync (alpha 0.4) with a 45 s staleness window, ``dp_path="jnp"``:
   params finite, the update budget met exactly, every epsilon finite and
   positive, ``audit_engine_stats`` clean.  Prints the compile seconds and
   merged updates/s of this one run: a smoke reading, not a benchmark.
2. FedAvg, two barrier rounds, the same checks.
3. The FedAsync spec with ``dp_path="pallas"``: the kernel compiled (not
   interpreted), bookkeeping equal to run 1, params within ``PARAMS_ATOL``.
4. The plain reference: window 0 on the cohort engine against the legacy
   per-client loop, bookkeeping identical, params within
   ``PARAMS_ATOL``.

``--four-chips`` runs only the mesh phase: FedAsync over 8 clients with
the vmap executor and cohorts of 4 partitioned over a 4-device mesh,
against the same spec with no mesh, plus the Pallas path on the mesh
(the kernel partitioned per member).  It prints where the client-state
arena and one cohort's stacked params live.

Every check raises and nothing is caught, so a failed phase exits
nonzero.  The last line of stdout, printed only when every phase passed,
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.  Without a
TPU the script exits nonzero before any phase runs.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.checks import CompileClock  # noqa: E402

UPDATES = 40            # FedAsync budget: merged updates per run
EVAL_EVERY = 10
FEDAVG_ROUNDS = 2
# max |param difference| admitted between two executions of one spec:
# 2 * lr.  On the TPU the default f32 matmul precision rounds per-example
# gradients differently in each compiled program, and Adam turns those
# into parameter moves of up to ~lr per step.  Measured on one v5e:
# pallas vs jnp 5.49e-4, window-0 cohort vs legacy 4.38e-4.
PARAMS_ATOL = 2e-3


def require(cond, msg):
    if not cond:
        raise RuntimeError(msg)


def _version(pkg):
    from importlib import metadata
    try:
        return metadata.version(pkg)
    except metadata.PackageNotFoundError:
        return "not installed"


def device_check(chips):
    """The platform and device count; exits nonzero unless it is a TPU
    with at least ``chips`` devices."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    print(f"jax {jax.__version__}, jaxlib {_version('jaxlib')}, "
          f"libtpu {_version('libtpu')}")
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)}")
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found {d0.platform!r}")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: needs {chips} TPU chips, found {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# specs and checks
# ---------------------------------------------------------------------------

def fedasync_spec(tb, **engine):
    from repro.api import ExperimentSpec, RunBudget, StrategySpec
    from repro.engine import EngineConfig
    engine.setdefault("staleness_window", 45.0)
    return ExperimentSpec(
        testbed=tb, strategy=StrategySpec("fedasync", alpha=0.4),
        run=RunBudget(max_updates=UPDATES, eval_every=EVAL_EVERY),
        engine=EngineConfig(**engine))


def check_run(label, params, log, updates, audit=True, overshoot=0):
    """Finite params, the budget met (a last cohort of K may pass it by
    up to K - 1 = ``overshoot``), epsilons finite and positive, engine
    stats audited."""
    import jax
    import numpy as np
    from repro.analysis.audits import audit_engine_stats
    for leaf in jax.tree_util.tree_leaves(params):
        require(np.isfinite(np.asarray(leaf)).all(),
                f"{label}: non-finite params")
    total = sum(log.update_counts.values())
    require(updates <= total <= updates + overshoot,
            f"{label}: {total} merged updates, budget {updates}")
    eps = [e for traj in log.eps_trajectory.values() for e in traj]
    require(eps and all(math.isfinite(e) and e > 0 for e in eps),
            f"{label}: epsilon not finite and positive: {eps}")
    if audit:
        audit_engine_stats(log.engine_stats, label=label)
    print(f"{label}: {total} updates, final acc {log.global_acc[-1]:.4f}, "
          f"max eps {max(eps):.4f}")


def check_books(label, a, b):
    for field in ("update_counts", "eps_trajectory", "staleness", "times"):
        require(getattr(a, field) == getattr(b, field),
                f"{label}: {field} differ")


def check_params(label, a, b, atol):
    """Max |a - b| over all leaves, and the relative L2 distance."""
    import jax
    import numpy as np
    la = [np.asarray(x, np.float64) for x in jax.tree_util.tree_leaves(a)]
    lb = [np.asarray(x, np.float64) for x in jax.tree_util.tree_leaves(b)]
    max_abs = max(float(np.max(np.abs(x - y))) for x, y in zip(la, lb))
    rel = math.sqrt(sum(float(np.sum((x - y) ** 2)) for x, y in zip(la, lb))
                    / sum(float(np.sum(x ** 2)) for x in la))
    print(f"{label}: params max |diff| {max_abs!r}, relative L2 {rel!r}")
    require(max_abs <= atol, f"{label}: max |diff| {max_abs} > {atol}")


def check_compiled_kernel(info):
    require(info["interpret"] is False and info["source"] == "auto",
            f"the Pallas kernel did not run compiled: {info}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def one_chip(tb):
    """Phases 1-4 of the module docstring on ``tb`` (the paper cell)."""
    from dataclasses import replace

    import jax
    from repro.api import ExperimentSpec, RunBudget, Session, StrategySpec
    from repro.engine import EngineConfig

    clock = CompileClock()
    session = Session()
    spec = fedasync_spec(tb)

    t0 = time.perf_counter()
    p_j, log_j = session.run(spec)
    jax.block_until_ready(p_j)
    cold = time.perf_counter() - t0
    check_run("fedasync/jnp", p_j, log_j, UPDATES)
    t0 = time.perf_counter()
    p_w, log_w = session.run(spec)
    jax.block_until_ready(p_w)
    warm = time.perf_counter() - t0
    check_books("fedasync/jnp warm rerun", log_j, log_w)
    print(f"fedasync/jnp, one smoke run (not a benchmark): cold run "
          f"{cold!r} s, XLA compile {clock.seconds!r} s "
          f"({clock.cache_hits} programs from the persistent cache); warm rerun "
          f"{warm!r} s = {UPDATES / warm!r} merged updates/s")

    fedavg = ExperimentSpec(
        testbed=tb, strategy=StrategySpec("fedavg"),
        run=RunBudget(rounds=FEDAVG_ROUNDS, eval_every=1),
        engine=EngineConfig(staleness_window=45.0))
    p_a, log_a = session.run(fedavg)
    check_run("fedavg", p_a, log_a, FEDAVG_ROUNDS * tb.num_clients)

    p_p, log_p = session.run(fedasync_spec(replace(tb, dp_path="pallas")))
    check_run("fedasync/pallas", p_p, log_p, UPDATES)
    check_compiled_kernel(log_p.engine_stats["pallas_interpret"])
    check_books("pallas vs jnp", log_j, log_p)
    require(log_j.cohort_sizes == log_p.cohort_sizes,
            "pallas vs jnp: cohort sizes differ")
    check_params("pallas vs jnp", p_j, p_p, PARAMS_ATOL)

    w0 = fedasync_spec(tb, staleness_window=0.0)
    p_c, log_c = session.run(w0)
    check_run("window 0/cohort", p_c, log_c, UPDATES)
    p_l, log_l = session.run(replace(w0, backend="legacy"))
    check_run("window 0/legacy", p_l, log_l, UPDATES, audit=False)
    check_books("cohort vs legacy", log_c, log_l)
    check_params("cohort vs legacy", p_c, p_l, PARAMS_ATOL)


def shard_devices(label, tree, mesh):
    """Devices holding ``tree``'s shards, after asserting that every leaf
    is partitioned along its leading (client) axis."""
    import jax
    from repro.engine import assert_cohort_partitioned
    per_shard = assert_cohort_partitioned(tree, mesh)
    devs = {s.device.id for leaf in jax.tree_util.tree_leaves(tree)
            for s in leaf.addressable_shards}
    print(f"{label}: {len(per_shard)} leaves, "
          f"{sorted(set(per_shard.values()))} rows per shard, "
          f"on devices {sorted(devs)}")
    return devs


def four_chips(tb):
    """The mesh phase: sharded cohorts of 4 on a 4-device mesh against the
    same spec with no mesh, and the Pallas path on the mesh."""
    from dataclasses import replace

    import jax
    from repro.api import Session
    from repro.core.testbed import build_testbed
    from repro.engine import CohortRunner, cohort_mesh

    mesh = cohort_mesh()
    require(dict(mesh.shape) == {"data": 4, "model": 1},
            f"expected a 4x1 mesh, got {dict(mesh.shape)}")
    tb = replace(tb, num_clients=8)
    engine = dict(client_axis="vmap", max_cohort=4)
    over = dict(overshoot=engine["max_cohort"] - 1)
    session = Session()
    p_m, log_m = session.run(fedasync_spec(tb, mesh=mesh, **engine))
    check_run("mesh/jnp", p_m, log_m, UPDATES, **over)
    p_n, log_n = session.run(fedasync_spec(tb, **engine))
    check_run("no mesh/jnp", p_n, log_n, UPDATES, **over)
    check_books("mesh vs no mesh", log_m, log_n)
    check_params("mesh vs no mesh", p_m, p_n, PARAMS_ATOL)

    p_k, log_k = session.run(
        fedasync_spec(replace(tb, dp_path="pallas"), mesh=mesh, **engine))
    check_run("mesh/pallas", p_k, log_k, UPDATES, **over)
    check_compiled_kernel(log_k.engine_stats["pallas_interpret"])
    check_books("mesh pallas vs mesh jnp", log_m, log_k)
    check_params("mesh pallas vs mesh jnp", p_m, p_k, PARAMS_ATOL)

    clients, params, _, _ = build_testbed(tb)
    runner = CohortRunner(clients, fedasync_spec(tb, mesh=mesh, **engine).engine)
    key = jax.random.PRNGKey(0)
    plans = []
    for c in clients[:4]:
        key, sub = jax.random.split(key)
        plans.append(runner.dispatch(c, params, sub, 0))
    stacked = runner.run_cohort(plans)
    every = {d.id for d in mesh.devices.flat}
    require(shard_devices("client-state arena", runner._arena_params,
                          mesh) == every,
            "the client-state arena does not span all 4 devices")
    require(shard_devices("one cohort's stacked params", stacked,
                          mesh) == every,
            "the cohort does not span all 4 devices")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh phase, on four chips")
    args = ap.parse_args(argv)
    device = device_check(4 if args.four_chips else 1)

    from repro.core.testbed import TestbedConfig
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    (four_chips if args.four_chips else one_chip)(TestbedConfig())
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
