"""Benchmark harness entry point — one benchmark per paper table/figure
plus kernel microbenchmarks and the roofline digest.

    PYTHONPATH=src python -m benchmarks.run            # full pass
    PYTHONPATH=src python -m benchmarks.run --quick    # CI-sized

Prints ``name,us_per_call,derived`` CSV lines; full artifacts land in
results/bench/.
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np


def _line(name, us, derived=""):
    print(f"{name},{us if us is not None else ''},{derived}", flush=True)


def kernel_microbench():
    """us_per_call for the three Pallas kernels (interpret mode on CPU —
    correctness-path timing, not TPU perf; the roofline table carries the
    TPU projection)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.dp_clip.ops import dp_clip_mean_flat
    from repro.kernels.flash_attn.ops import flash_decode
    from repro.kernels.ssd_scan.ops import ssd_intra_chunk

    key = jax.random.PRNGKey(0)

    def timeit(f, *args, reps=3):
        jax.block_until_ready(f(*args))  # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(f(*args))
        return (time.perf_counter() - t0) / reps * 1e6

    flat = jax.random.normal(key, (128, 4096), jnp.float32)
    us = timeit(lambda x: dp_clip_mean_flat(x, 1.0), flat)
    _line("kernel.dp_clip.128x4096", round(us), "interpret")

    q = jax.random.normal(key, (2, 8, 64), jnp.float32)
    k = jax.random.normal(key, (2, 1024, 2, 64), jnp.float32)
    v = jax.random.normal(key, (2, 1024, 2, 64), jnp.float32)
    pos = jnp.array([900, 1000])
    us = timeit(lambda a, b, c, d: flash_decode(a, b, c, d, window=512),
                q, k, v, pos)
    _line("kernel.flash_decode.S1024", round(us), "interpret")

    xr = jax.random.normal(key, (1, 4, 64, 4, 32), jnp.float32)
    ar = -jnp.abs(jax.random.normal(key, (1, 4, 4, 64))) * 0.1
    Br = jax.random.normal(key, (1, 4, 64, 32), jnp.float32)
    Cr = jax.random.normal(key, (1, 4, 64, 32), jnp.float32)
    us = timeit(ssd_intra_chunk, xr, ar, Br, Cr)
    _line("kernel.ssd_intra.c4q64", round(us), "interpret")


def sweep_smoke() -> None:
    """A tiny 2x2 ``Session.sweep`` — {fedavg, fedasync} x two sigmas —
    through the declarative API, sharded over the mesh when more than one
    device exists (CI's engine-mesh job forces 8 host devices).  Prints
    one CSV line per scenario plus the session's cache telemetry; any
    scenario failing to train is a hard error."""
    import jax

    from repro.api import ExperimentSpec, RunBudget, Session, StrategySpec
    from repro.core.testbed import TestbedConfig
    from repro.data.synthetic_ser import SERDataConfig
    from repro.engine import EngineConfig, cohort_mesh
    from repro.models.ser_cnn import SERConfig

    n_clients = 8
    dims = dict(time_frames=12, n_mels=12)
    multi = len(jax.devices()) > 1
    if multi:
        mesh = cohort_mesh(max_cohort=n_clients)
        ec = EngineConfig(staleness_window=45.0,
                          max_cohort=mesh.shape["data"],
                          client_axis="vmap", mesh=mesh)
    else:
        ec = EngineConfig(staleness_window=45.0)
    spec = ExperimentSpec(
        testbed=TestbedConfig(
            use_dp=True, sigma=0.5, batch_size=16, num_clients=n_clients,
            data=SERDataConfig(n_total=36 * n_clients, **dims),
            model=SERConfig(channels1=8, channels2=16, fc_dim=32, **dims)),
        strategy=StrategySpec("fedasync", alpha=0.4),
        run=RunBudget(rounds=2, max_updates=8, eval_every=4),
        engine=ec)
    t0 = time.time()
    result = Session().sweep(spec, axes={
        "strategy": [StrategySpec("fedavg"),
                     StrategySpec("fedasync", alpha=0.4)],
        "testbed.sigma": [0.5, 2.0],
    })
    for row in result.table():
        if row["final_acc"] is None:
            raise SystemExit(f"sweep-smoke scenario produced no eval: {row}")
        _line(f"sweep.smoke.{row['strategy']}.s{row['sigma']:g}",
              round(row["wall_s"] * 1e6),
              f"acc={row['final_acc']};eps={row['max_eps']}"
              + (";mesh" if multi else ""))
    _line("sweep.smoke", round((time.time() - t0) * 1e6),
          f"points={len(result)};mesh={multi}")


def fault_smoke() -> None:
    """Kill/resume drill for the resilience layer (RESILIENCE.md): a tiny
    chaotic run (failures, upload loss + retries, duplicates, late
    deliveries, churn), then the same run killed in-process by
    ``SimulatedCrash`` at its second published checkpoint and resumed from
    disk — the resumed RunLog must equal the uninterrupted one BIT FOR
    BIT (params, times, epsilon trajectories, fault events, engine
    stats).  Runs sharded when more than one device exists (CI's
    engine-mesh job forces 8 host devices)."""
    import shutil
    import tempfile

    import jax

    from repro.api import ExperimentSpec, RunBudget, Session, StrategySpec
    from repro.core.faults import FaultModel
    from repro.core.testbed import TestbedConfig
    from repro.data.synthetic_ser import SERDataConfig
    from repro.engine import EngineConfig, SimulatedCrash, cohort_mesh
    from repro.models.ser_cnn import SERConfig

    n_clients = 8
    dims = dict(time_frames=12, n_mels=12)
    multi = len(jax.devices()) > 1
    if multi:
        mesh = cohort_mesh(max_cohort=n_clients)
        ec = EngineConfig(staleness_window=45.0,
                          max_cohort=mesh.shape["data"],
                          client_axis="vmap", mesh=mesh)
    else:
        ec = EngineConfig(staleness_window=45.0)
    spec = ExperimentSpec(
        testbed=TestbedConfig(
            use_dp=True, sigma=0.5, batch_size=16, num_clients=n_clients,
            data=SERDataConfig(n_total=36 * n_clients, **dims),
            model=SERConfig(channels1=8, channels2=16, fc_dim=32, **dims),
            faults=FaultModel(
                seed=7, failure_prob=0.1, upload_loss_prob=0.15,
                max_retries=1, retry_backoff_s=4.0, duplicate_prob=0.15,
                late_prob=0.1, leave_prob=0.1, rejoin_delay_s=40.0)),
        strategy=StrategySpec("fedasync", alpha=0.4),
        run=RunBudget(max_updates=24, eval_every=6),
        engine=ec)

    def logdict(log):
        return dict(times=log.times, acc=log.global_acc,
                    sv=log.server_version, uc=dict(log.update_counts),
                    st=log.staleness, fe=list(log.fault_events),
                    es=dict(log.engine_stats),
                    eps={k: list(v) for k, v in log.eps_trajectory.items()})

    t0 = time.time()
    p_plain, log_plain = Session().run(spec)
    if not log_plain.fault_events:
        raise SystemExit("fault-smoke chaos model produced no faults")
    ckdir = tempfile.mkdtemp(prefix="fault_smoke_ck_")
    try:
        try:
            Session().run(spec, checkpoint_every=7, checkpoint_dir=ckdir,
                          crash_after_saves=2)
            raise SystemExit("fault-smoke run survived crash_after_saves=2")
        except SimulatedCrash:
            pass
        p_res, log_res = Session().run(spec, checkpoint_every=7,
                                       checkpoint_dir=ckdir,
                                       resume_from=ckdir)
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)
    a, b = logdict(log_plain), logdict(log_res)
    bad = [k for k in a if a[k] != b[k]]
    bad += ["params"] if any(
        not np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree_util.tree_leaves(p_plain),
                        jax.tree_util.tree_leaves(p_res))) else []
    if bad:
        raise SystemExit(
            f"fault-smoke resume is NOT bit-identical; diverged: {bad}")
    s = log_res.engine_stats
    _line("fault.smoke", round((time.time() - t0) * 1e6),
          f"events={len(log_res.fault_events)}"
          f";lost={s['fault_failures'] + s['fault_lost_updates']}"
          f";retries={s['fault_retries']}"
          f";degraded={s['degraded_cohorts']}"
          f";mesh={multi};resume=bit-identical")


def screen_smoke() -> None:
    """Corrupt-update defense drill (RESILIENCE.md): the SAME tiny async
    workload run clean, corrupted-and-undefended, and corrupted-defended
    (in-step screening + quarantine + norm-bounded merge) through one
    warm Session.  The defended run must fire in-step rejections with a
    consistent counter ledger and beat the undefended run's final
    accuracy — corruption defense as an acceptance check, sharded when
    more than one device exists (CI's engine-mesh job forces 8 host
    devices)."""
    import math
    from dataclasses import replace

    import jax

    from repro.api import ExperimentSpec, RunBudget, Session, StrategySpec
    from repro.core.faults import FaultModel
    from repro.core.screening import ScreeningConfig
    from repro.core.testbed import TestbedConfig
    from repro.data.synthetic_ser import SERDataConfig
    from repro.engine import EngineConfig, cohort_mesh
    from repro.models.ser_cnn import SERConfig

    n_clients = 8
    dims = dict(time_frames=12, n_mels=12)
    multi = len(jax.devices()) > 1
    if multi:
        mesh = cohort_mesh(max_cohort=n_clients)
        ec = EngineConfig(staleness_window=45.0,
                          max_cohort=mesh.shape["data"],
                          client_axis="vmap", mesh=mesh)
    else:
        ec = EngineConfig(staleness_window=45.0)
    tb = TestbedConfig(
        use_dp=True, sigma=0.5, batch_size=16, num_clients=n_clients,
        data=SERDataConfig(n_total=36 * n_clients, **dims),
        model=SERConfig(channels1=8, channels2=16, fc_dim=32, **dims))
    faults = FaultModel(seed=7, corrupt_prob=0.5)
    screen = ScreeningConfig(max_update_norm=1e3, quarantine_after=2,
                             readmit_delay_s=100.0)

    def spec(tb_, strat):
        return ExperimentSpec(testbed=tb_, strategy=strat,
                              run=RunBudget(max_updates=24, eval_every=8),
                              engine=ec)

    plain = StrategySpec("fedasync", alpha=0.4)
    robust = StrategySpec("fedasync_normbound", alpha=0.4, norm_bound=10.0)
    sess = Session()
    t0 = time.time()
    _, log_clean = sess.run(spec(tb, plain))
    _, log_open = sess.run(spec(replace(tb, faults=faults), plain))
    _, log_def = sess.run(
        spec(replace(tb, faults=faults, screening=screen), robust))

    s = log_def.engine_stats
    if not s["screen_rejections"]:
        raise SystemExit("screen-smoke defended run rejected nothing — "
                         "the corruption drill is not exercising screening")
    if s["screen_rejections"] != s["screen_nonfinite"] + s["screen_norm_rejects"]:
        raise SystemExit(f"screen-smoke rejection ledger broken: {s}")
    if not any(e[0].startswith("corrupt_") for e in log_open.fault_events):
        raise SystemExit("screen-smoke fault model produced no corruption")
    a_clean, a_open, a_def = (log.global_acc[-1] for log in
                              (log_clean, log_open, log_def))
    if not math.isfinite(a_def):
        raise SystemExit(f"screen-smoke defended accuracy is {a_def}")
    # the acceptance comparison: defense must beat the undefended run,
    # whose merges ingest the NaN/blown-up payloads unchecked
    a_open_eff = a_open if math.isfinite(a_open) else -1.0
    if a_def <= a_open_eff:
        raise SystemExit(
            f"screen-smoke defense did not help: defended acc {a_def} "
            f"<= undefended {a_open} (clean {a_clean})")
    _line("screen.smoke", round((time.time() - t0) * 1e6),
          f"rej={s['screen_rejections']}"
          f";nonfinite={s['screen_nonfinite']}"
          f";norm={s['screen_norm_rejects']}"
          f";quar={s['screen_quarantined']}"
          f";acc_clean={a_clean};acc_open={a_open};acc_def={a_def}"
          f";mesh={multi}")


def scale_smoke() -> None:
    """Tiered client-state store drill (STORE.md): the SAME tiny async
    workload run all-resident and through a hot-slot-bounded
    TieredStateStore with lookahead prefetch — the two runs must produce
    BIT-IDENTICAL params and trajectories, the tiered run must actually
    churn (prefetch hits AND evictions both nonzero), its fetch ledger
    must balance, and the pipelined scheduler must stay sync-free
    between eval boundaries.  Runs sharded when more than one device
    exists (CI's engine-mesh job forces 8 host devices)."""
    import jax
    import jax.random as jr

    from repro.api.workloads import get_workload
    from repro.core.aggregation import FedAsync
    from repro.core.runlog import STORE_STATS_KEYS
    from repro.core.testbed import TestbedConfig, build_clients, \
        build_partitions
    from repro.data.synthetic_ser import SERDataConfig
    from repro.engine import (CohortRunner, EngineConfig, StoreConfig,
                              cohort_mesh, run_async_engine)
    from repro.models.ser_cnn import SERConfig

    n_clients = 16
    dims = dict(time_frames=12, n_mels=12)
    tb = TestbedConfig(
        use_dp=True, sigma=0.5, batch_size=16, num_clients=n_clients,
        data=SERDataConfig(n_total=36 * n_clients, **dims),
        model=SERConfig(channels1=8, channels2=16, fc_dim=32, **dims))
    splits, pooled = build_partitions(tb)
    wl = get_workload(tb.workload)
    params0 = wl.init(jr.PRNGKey(0), tb.model)
    acc_fn = wl.shared_accuracy(tb.model)
    multi = len(jax.devices()) > 1
    if multi:
        mesh, max_cohort, updates = cohort_mesh(max_cohort=8), 8, 24
        store = StoreConfig(hot_slots=8, lookahead=6)
    else:
        mesh, max_cohort, updates = None, 4, 40
        store = StoreConfig(hot_slots=6, lookahead=4)

    def go(store_cfg):
        clients = build_clients(tb, splits)
        ec = EngineConfig(staleness_window=30.0, max_cohort=max_cohort,
                          pipeline_depth=2, mesh=mesh, store=store_cfg)
        return run_async_engine(
            clients, params0, acc_fn, pooled, FedAsync(alpha=0.5),
            max_updates=updates, seed=0, eval_every=10,
            runner=CohortRunner(clients, ec))

    t0 = time.time()
    p_res, log_res = go(StoreConfig())
    p_tier, log_tier = go(store)
    bad = [k for k in ("times", "global_acc", "staleness", "update_counts",
                       "cohort_sizes")
           if getattr(log_res, k) != getattr(log_tier, k)]
    bad += ["params"] if any(
        not np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree_util.tree_leaves(p_res),
                        jax.tree_util.tree_leaves(p_tier))) else []
    if bad:
        raise SystemExit(
            f"scale-smoke tiered run is NOT bit-identical; diverged: {bad}")
    s = {k: log_tier.engine_stats[k] for k in STORE_STATS_KEYS}
    if s["store_fetches"] != (s["store_hot_hits"] + s["store_prefetch_hits"]
                              + s["store_stall_waits"]):
        raise SystemExit(f"scale-smoke store ledger broken: {s}")
    if not s["store_prefetch_hits"]:
        raise SystemExit(f"scale-smoke lookahead prefetcher never hit: {s}")
    if not s["store_evictions"]:
        raise SystemExit(f"scale-smoke store never evicted "
                         f"(hot_slots={store.hot_slots} of {n_clients}): {s}")
    if log_tier.engine_stats["host_syncs_between_evals"]:
        raise SystemExit(
            "scale-smoke tiered run blocked between eval boundaries: "
            f"{log_tier.engine_stats['host_syncs_between_evals']} syncs")
    _line("scale.smoke", round((time.time() - t0) * 1e6),
          f"hot={store.hot_slots}/{n_clients}"
          f";prefetch={s['store_prefetch_hits']}"
          f";evictions={s['store_evictions']}"
          f";stalls={s['store_stall_waits']}"
          f";spill_kb={s['store_spill_bytes'] // 1024}"
          f";mesh={multi};parity=bit-identical")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--skip-fl", action="store_true")
    ap.add_argument("--fresh", action="store_true",
                    help="recompute even when a cached artifact exists")
    ap.add_argument("--engine-smoke", action="store_true",
                    help="tiny bench_engine_throughput pass only: emits "
                         "BENCH_engine.json for summarize.py --check-engine "
                         "(CI's engine-mesh bench-smoke step)")
    ap.add_argument("--dp-smoke", action="store_true",
                    help="tiny bench_dp_path pass only: jnp vs the fused "
                         "Pallas clip+noise kernel on the cohort hot path "
                         "(CI's engine-mesh dp-smoke step; does NOT rewrite "
                         "BENCH_engine.json)")
    ap.add_argument("--sweep-smoke", action="store_true",
                    help="tiny 2x2 Session.sweep (strategy x sigma) — "
                         "exercises the declarative API end to end on "
                         "whatever devices exist (CI's engine-mesh "
                         "sweep-smoke step runs it on the forced-8-device "
                         "mesh)")
    ap.add_argument("--fault-smoke", action="store_true",
                    help="tiny chaotic run + in-process SimulatedCrash at "
                         "a published checkpoint + resume; the resumed "
                         "RunLog must be bit-identical (CI's engine-mesh "
                         "fault-smoke step runs it on the forced-8-device "
                         "mesh)")
    ap.add_argument("--scale-smoke", action="store_true",
                    help="tiny resident-vs-tiered client-state-store pair: "
                         "bit-identical params/trajectories with live "
                         "prefetch hits, evictions and a balanced fetch "
                         "ledger (CI's engine-mesh scale-smoke step runs "
                         "it on the forced-8-device mesh)")
    ap.add_argument("--screen-smoke", action="store_true",
                    help="tiny corrupted run with in-step screening + "
                         "robust aggregation: rejections must fire and "
                         "the defended accuracy must beat the undefended "
                         "run (CI's engine-mesh screen-smoke step runs it "
                         "on the forced-8-device mesh)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import fl_benchmarks as flb

    if args.scale_smoke:
        scale_smoke()
        return

    if args.screen_smoke:
        screen_smoke()
        return

    if args.fault_smoke:
        fault_smoke()
        return

    if args.sweep_smoke:
        sweep_smoke()
        return

    if args.dp_smoke:
        t0 = time.time()
        rows = flb.bench_dp_path(tiny=True)
        pallas = next(r for r in rows if r["dp_path"] == "pallas")
        _line("engine.dp.smoke", round((time.time() - t0) * 1e6),
              ";".join(f"{r['dp_path']}:{r['speedup_vs_jnp']}x"
                       for r in rows)
              + f";interpret={pallas['interpret']}"
              + f"({pallas['interpret_source']})")
        return

    if args.engine_smoke:
        t0 = time.time()
        rows = flb.bench_engine_throughput(tiny=True)
        _line("engine.smoke", round((time.time() - t0) * 1e6),
              ";".join(f"{r['engine']}:{r['speedup_vs_legacy']}x"
                       for r in rows))
        # the pipelined-scheduler pair (multi-device only): tiny
        # pipeline_depth=2 run vs the serial driver, validated by
        # summarize.py --check-engine against the BENCH pipeline section.
        # Read THIS run's BENCH_engine.json (bench_engine_throughput just
        # rewrote it) — the results/bench cache may hold a stale
        # multi-device artifact from an earlier invocation.
        import json
        import os
        bench_fn = os.path.join(os.path.dirname(flb.__file__), "..",
                                "BENCH_engine.json")
        with open(bench_fn) as f:
            bench = json.load(f)
        pipe = bench.get("pipeline", {}).get("rows", [])
        if pipe:
            _line("engine.pipeline.smoke", None,
                  ";".join(f"{r['engine']}:{r['speedup_vs_serial']}x"
                           for r in pipe))
        sw = bench.get("sweep")
        if sw:
            _line("engine.sweep.smoke", None,
                  f"warm:{sw['speedup']}x;builds:{sw['warm_step_builds']}"
                  f"/{sw['cold_step_builds']}")
        dp = bench.get("dp_path", {}).get("rows", [])
        if dp:
            _line("engine.dp.smoke", None,
                  ";".join(f"{r['dp_path']}:{r['speedup_vs_jnp']}x"
                           for r in dp))
        return

    def run_or_cache(name, fn):
        if not args.fresh:
            rows = flb.cached(name)
            if rows is not None:
                return rows, True
        return fn(), False

    t0 = time.time()
    kernel_microbench()

    if not args.skip_fl:
        rounds = 4 if args.quick else 8
        rows, hit = run_or_cache(
            "table2_resources", lambda: flb.bench_table2_resources(rounds=rounds))
        _line("table2.resources", round((time.time() - t0) * 1e6),
              f"tiers={len(rows)}{';cached' if hit else ''}")

        rows, hit = run_or_cache("fig3_per_device", flb.bench_fig3_per_device)
        rel = {r["hw_type"]: r["rel_vs_T5"] for r in rows}
        _line("fig3.per_device", None,
              f"T1_rel={rel.get('HW_T1')}x{';cached' if hit else ''}")

        t = time.time()
        rows, hit = run_or_cache(
            "fig4_convergence",
            lambda: flb.bench_fig4_convergence(seeds=(0,) if args.quick else (0, 1)))
        sp = [r["speedup"] for r in rows if r["speedup"]]
        _line("fig4.convergence", round((time.time() - t) * 1e6),
              (f"speedup={np.mean(sp):.1f}x" if sp else "no-target")
              + (";cached" if hit else ""))

        t = time.time()
        rows, hit = run_or_cache(
            "fig5_fairness",
            lambda: flb.bench_fig5_fairness(
                alphas=(0.2, 0.6) if args.quick else (0.2, 0.4, 0.6),
                max_updates=150 if args.quick else 300))
        _line("fig5.fairness", round((time.time() - t) * 1e6),
              ";".join(f"a{r['alpha']}:high={r['high_end_pp']}%"
                       for r in rows) + (";cached" if hit else ""))

        t = time.time()
        rows, hit = run_or_cache(
            "table3_privacy",
            lambda: flb.bench_table3_privacy(
                sigmas=(0.5, 2.0) if args.quick else (0.5, 1.0, 2.0),
                alphas=(0.2,) if args.quick else (0.2, 0.6),
                max_updates=120 if args.quick else 240,
                rounds=12 if args.quick else 25))
        hi = [r for r in rows if r["device"] == "HW_T5"
              and "async" in r["method"]]
        lo = [r for r in rows if r["device"] == "HW_T1"
              and "async" in r["method"]]
        if hi and lo:
            disp = np.mean([h["epsilon"] / max(l["epsilon"], 1e-9)
                            for h, l in zip(hi, lo)])
            _line("table3.privacy", round((time.time() - t) * 1e6),
                  f"eps_disparity={disp:.1f}x" + (";cached" if hit else ""))

        t = time.time()
        rows, hit = run_or_cache(
            "noniid_ablation",
            lambda: flb.bench_noniid_ablation(
                max_updates=120 if args.quick else 240))
        _line("beyond.noniid", round((time.time() - t) * 1e6),
              ";".join(f"{r['partition']}:gap={r['accuracy_gap']}"
                       for r in rows) + (";cached" if hit else ""))

        t = time.time()
        rows, hit = run_or_cache(
            "engine_throughput",
            lambda: flb.bench_engine_throughput(
                num_clients=8, updates=24 if args.quick else 48))
        sp = {r["engine"]: r["speedup_vs_legacy"] for r in rows}
        _line("engine.throughput", round((time.time() - t) * 1e6),
              ";".join(f"{k}:{v}x" for k, v in sp.items())
              + (";cached" if hit else ""))

        t = time.time()
        rows, hit = run_or_cache(
            "beyond_paper_tradeoffs",
            lambda: flb.bench_beyond_paper(
                max_updates=100 if args.quick else 240))
        _line("beyond.tradeoffs", round((time.time() - t) * 1e6),
              ";".join(f"{r['strategy']}:eps={r['max_eps']}"
                       for r in rows) + (";cached" if hit else ""))

    # roofline digest from whatever dry-run artifacts exist
    try:
        from benchmarks.roofline import analyze_all, write_table
        rows = analyze_all()
        ok = [r for r in rows if r.get("status") == "ok"]
        if ok:
            write_table(rows)
            doms = {}
            for r in ok:
                doms[r["dominant"]] = doms.get(r["dominant"], 0) + 1
            _line("roofline.single_pod", None,
                  f"pairs={len(ok)};dominant={doms}")
    except Exception as e:  # noqa: BLE001
        _line("roofline.single_pod", None, f"unavailable:{e}")

    _line("total", round((time.time() - t0) * 1e6), "bench pass complete")


if __name__ == "__main__":
    main()
