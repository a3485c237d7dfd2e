#!/usr/bin/env python3
"""One run of one benchmark cell of the cohort engine, on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` -> ``workloads``) names a configuration,
``bench/configs/<config>.json``, and a traffic mix,
``bench/traffic/<traffic>.json``; the configuration names its model
family, ``bench/families/<family>.py``, which maps it onto the
program's ``TestbedConfig`` and supplies the reference's corpus and
model and the readers' operation counts.  The run:

1. builds the cell's ``ExperimentSpec``s (one per noise multiplier of
   the mix) and one ``Session``;
2. warms up: a short experiment at every smaller cohort-size bucket
   (max_cohort 1, 2, 4, ...), then the window's first experiment whole,
   so every program the window runs is compiled or read from the
   compilation cache in ``<checkout>/.jax_cache``;
3. runs the window: ``Session.run`` of one whole experiment after
   another, cycling the noise multipliers, until the first experiment
   that ends after ``--seconds``; each experiment is attempted, and
   failed when it raises or fails ``checks.check_run``;
4. with ``--trace 1``, traces the window's first experiments, up to
   the first that ends after ``TRACE_SECONDS``, with the JAX profiler,
   and reduces the trace (``trace_reduce``) for the per-layer readers
   in ``bench/metrics/<metric>.py``, which read that traced part;
5. reads the peak device memory, frees the program's state, and compares
   one experiment of the window, drawn from the seed, with the plain
   reference (``correct``, ``reference``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
with ``--trace 1``), with ``checks`` last: each compared number beside
its limit.  The same numbers close standard error.  Without a TPU, or
with fewer chips than the cell asks for, it exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field, replace  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import checks, correct  # noqa: E402

EXIT_NO_CHIP = 3
TRACE_DIR = ROOT / ".bench" / "trace"
# a traced run traces the window's experiments up to the first that ends
# after this many seconds: on one v5e a 34 s trace of testbed5-fedasync
# kept about half of its device operations, a 13.5 s one all of them
TRACE_SECONDS = 10.0
SUMMED = ("cohorts", "h2d_bytes_total", "host_syncs_at_eval",
          "host_syncs_between_evals", "blocking_submits", "drain_waits",
          "store_fetches", "store_hot_hits", "store_prefetch_hits",
          "store_stall_waits", "store_evictions", "store_spill_bytes",
          "store_sync_reads")


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_family(config_path: Path, config: dict, root: Path = ROOT):
    """The model family module ``<root>/bench/families/<family>.py`` that
    the configuration's ``family`` key names: ``testbed_fields(cfg)``,
    ``Corpus(cfg)``, ``init_params(key, model)``, ``loss(params, x, y,
    model)``, ``train_flops_per_example(model)``, ``param_count(model)``."""
    families = root / "bench" / "families"
    known = sorted(p.stem for p in families.glob("*.py"))
    if "family" not in config:
        raise SystemExit(f"{config_path}: no 'family' key; families: {known}")
    name = config["family"]
    if name not in known:
        raise SystemExit(f"{config_path}: unknown family {name!r}; "
                         f"families: {known}")
    spec = importlib.util.spec_from_file_location(
        f"bench_family_{name}", families / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    family: object      # the module named by the config: see ``load_family``
    traffic: dict
    end_to_end: list
    per_layer: list
    limits: dict        # the limits file: see ``correct.limits_for``

    @classmethod
    def load(cls, name: str, root: Path = ROOT) -> "Cell":
        bench = load_json(root / "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}: {sorted(cells)}")
        w = cells[name]
        mine = [m for m in bench["per_layer"]
                if name in m.get("workloads", [name])]
        e2e = [m for m in bench["end_to_end"]
               if name in m.get("workloads", [name])]
        files = root / "bench"
        config_path = files / "configs" / f"{w['config']}.json"
        config = load_json(config_path)
        return cls(name=name, chips=int(w["chips"]), config=config,
                   family=load_family(config_path, config, root),
                   traffic=load_json(files / "traffic" / f"{w['traffic']}.json"),
                   end_to_end=e2e, per_layer=mine,
                   limits=load_json(files / "limits" / f"{name}.json"))


@dataclass
class Window:
    """What the window did, for the readers in ``bench/metrics``."""
    seconds: float = 0.0
    updates: int = 0
    attempted: int = 0
    failed: int = 0
    errors: list = field(default_factory=list)
    stats: dict = field(default_factory=dict)
    cohort_sizes: list = field(default_factory=list)
    tier_updates: dict = field(default_factory=dict)
    experiments: list = field(default_factory=list)
    traced: "Window | None" = None   # the traced first part, --trace 1

    def part(self, logs, seconds: float) -> "Window":
        """The window so far, for the readers: counts, not params."""
        return Window(seconds=seconds, updates=self.updates,
                      attempted=self.attempted, failed=self.failed,
                      stats=summed_stats(logs),
                      cohort_sizes=list(self.cohort_sizes),
                      tier_updates=dict(self.tier_updates))


@dataclass
class RunRecord:
    """Everything a per-layer reader may read: the cell, the traced part
    of the window (``Window.traced``), the compile clock, the reduced trace (``None`` untraced), the peaks of
    this device, and the client step counts per tier."""
    cell: Cell
    window: Window
    setup_s: float
    compile_setup: tuple
    compile_window: tuple
    trace: object
    peaks: dict
    chips: int
    steps_by_tier: dict
    s_max: int


def device_check(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            print(f"bench: needs a TPU, found {devs[0].platform!r}",
                  file=sys.stderr)
            sys.exit(EXIT_NO_CHIP)
        if len(devs) < chips:
            print(f"bench: needs {chips} TPU chips, found {len(devs)}",
                  file=sys.stderr)
            sys.exit(EXIT_NO_CHIP)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def testbed_seed(seed: int) -> int:
    """The program's seed: the run's seed modulo 2**32, since JAX keys
    take 32 bits of it."""
    return seed % (2 ** 32)


def build_specs(cell: Cell, seed: int):
    """``(window_specs, warmup_specs)`` of the program's API."""
    from repro.api import ExperimentSpec, RunBudget, StrategySpec
    from repro.core.testbed import TestbedConfig
    from repro.engine import EngineConfig, StoreConfig

    c, t = cell.config, cell.traffic
    tb = TestbedConfig(
        **c["testbed"], seed=testbed_seed(seed), dp_path=t["dp_path"],
        **cell.family.testbed_fields(c))
    mesh = None
    if t["mesh"]:
        from repro.engine import cohort_mesh
        mesh = cohort_mesh()
    engine = EngineConfig(
        staleness_window=t["staleness_window"], max_cohort=t["max_cohort"],
        client_axis=t["client_axis"], pipeline_depth=t["pipeline_depth"],
        store=StoreConfig(**t["store"]) if t["store"] else StoreConfig(),
        mesh=mesh)
    strategy = StrategySpec(t["strategy"], alpha=t["alpha"])
    window = [ExperimentSpec(
        testbed=replace(tb, sigma=s), strategy=strategy, engine=engine,
        run=RunBudget(max_updates=t["max_updates"],
                      eval_every=t["eval_every"]))
        for s in t["sigmas"]]
    # a short experiment (no eval) per smaller cohort bucket (1, 2, 4,
    # ...), then the window's first experiment itself: the window's
    # experiments differ only in the noise multiplier, a runtime value,
    # so that one reaches every program the window runs (store
    # evictions and their row counts, evals) and compiles or loads it
    buckets = [1 << i for i in range(t["max_cohort"].bit_length())]
    n = t["warmup_updates"]
    warm = [replace(window[0], engine=replace(engine, max_cohort=k),
                    run=RunBudget(max_updates=n, eval_every=n + 1))
            for k in buckets[:-1]]
    return window, warm + [window[0]]


def summed_stats(logs) -> dict:
    out = {k: 0 for k in SUMMED}
    for log in logs:
        for k in SUMMED:
            out[k] += log.engine_stats[k]
    return out


def run_window(session, specs, seconds: float, cell: Cell, compiled: bool,
               annotate, stop_trace=None) -> Window:
    """Whole experiments until the first that ends after ``seconds``.
    With ``stop_trace``, the trace stops after the first experiment that
    ends after ``TRACE_SECONDS`` (or with the window), and
    ``Window.traced`` holds the counts up to there."""
    import jax
    w = Window()
    overshoot = cell.traffic["max_cohort"] - 1
    budget = cell.traffic["max_updates"]
    logs = []
    t0 = time.perf_counter()
    i = 0
    while True:
        spec = specs[i % len(specs)]
        i += 1
        w.attempted += 1
        try:
            with annotate("bench.session_run"):
                params, log = session.run(spec)
                params = jax.device_get(params)
            with annotate("bench.check_run"):
                n = checks.check_run(f"experiment {i}", params, log, budget,
                                     overshoot, compiled)
        except Exception as e:  # a raise is a failed experiment
            w.failed += 1
            w.errors.append(f"experiment {i}: {type(e).__name__}: {e}")
            n = None
        else:
            w.updates += n
            logs.append(log)
            w.cohort_sizes += list(log.cohort_sizes)
            for tier, v in log.update_counts.items():
                w.tier_updates[tier] = w.tier_updates.get(tier, 0) + v
            w.experiments.append(
                {"sigma": spec.testbed.sigma, "params": params,
                 "books": correct.program_books(log)})
        elapsed = time.perf_counter() - t0
        if stop_trace is not None and (elapsed >= TRACE_SECONDS
                                       or elapsed >= seconds):
            stop_trace()
            stop_trace = None
            w.traced = w.part(logs, elapsed)
        if elapsed >= seconds:
            break
    w.seconds = time.perf_counter() - t0
    w.stats = summed_stats(logs)
    return w


def memory_peak(chips: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks)


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def check_against_reference(cell: Cell, seed: int, window: Window) -> tuple:
    """``(ok, checks)`` for one experiment of the window drawn from the
    seed, against the plain reference."""
    from bench import reference
    exp = random.Random(seed).choice(window.experiments)
    tb_seed = testbed_seed(seed)
    corpus = cell.family.Corpus(cell.config)
    init, ref, books = reference.simulate(
        cell.family, cell.config, cell.traffic, corpus, tb_seed, exp["sigma"])
    numbers = correct.compare(init, exp["params"], exp["books"], ref, books)
    return correct.judge(numbers,
                         correct.limits_for(cell.limits, exp["sigma"]))


def steps_by_tier(session) -> tuple:
    """Mean local DP steps per update of each tier's clients, and the
    padded step count of the compiled step (shapes of the program's
    clients)."""
    from repro.engine.cohort import steps_per_round
    by_tier = {}
    for c in session._clients:
        by_tier.setdefault(c.tier, []).append(
            steps_per_round(c.n_train, c.batch_size, c.local_epochs))
    s_max = max(max(v) for v in by_tier.values())
    return {t: sum(v) / len(v) for t, v in by_tier.items()}, s_max


def run(argv=None, require_chip: bool = True, root: Path = ROOT) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = Cell.load(args.workload, root)
    device = device_check(cell.chips, require_chip)

    import jax
    from repro.api import Session
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    clock = checks.CompileClock()

    def annotate(name):
        return jax.profiler.TraceAnnotation(name)

    with annotate("bench.setup"):
        window_specs, warm_specs = build_specs(cell, args.seed)
        session = Session()
        for spec in warm_specs:
            with annotate("bench.warmup"):
                params, _ = session.run(spec)
                jax.block_until_ready(params)
        del params
    compile_setup = clock.snapshot()
    setup_s = time.perf_counter() - T_START

    stop_trace = None
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        stop_trace = jax.profiler.stop_trace
    window = run_window(session, window_specs, args.seconds, cell,
                        require_chip, annotate, stop_trace)
    c_after = clock.snapshot()
    compile_window = tuple(b - a for a, b in zip(compile_setup, c_after))
    device["memory_peak_bytes"] = memory_peak(cell.chips)
    tiers, s_max = steps_by_tier(session)
    del session
    gc.collect()

    metrics, breakdown = {}, None
    if args.trace:
        from bench import trace_reduce
        red = trace_reduce.reduce_dir(TRACE_DIR, cell.chips)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = red.breakdown()
        peaks_all = load_json(BENCH / "peaks.json")["devices"]
        if device["kind"] not in peaks_all:
            raise SystemExit(f"no peaks for device {device['kind']!r} in "
                             "bench/peaks.json")
        rec = RunRecord(cell=cell, window=window.traced, setup_s=setup_s,
                        compile_setup=compile_setup,
                        compile_window=compile_window, trace=red,
                        peaks=peaks_all[device["kind"]], chips=cell.chips,
                        steps_by_tier=tiers, s_max=s_max)
        for m in cell.per_layer:
            value = load_reader(m["name"])(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {"updates_per_s": window.updates / window.seconds,
                  "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    if window.experiments:
        ok, checked = check_against_reference(cell, args.seed, window)
    else:
        ok, checked = False, {}
    for e in window.errors:
        print(e, file=sys.stderr)
    import resource
    print(f"bench: set-up {setup_s!r} s, window {window.seconds!r} s, "
          f"{len(window.experiments)} experiments, {window.updates} updates, "
          + (f"traced the first {window.traced.seconds!r} s "
             f"({window.traced.updates} updates), " if window.traced else "")
          + f"compiles in window {compile_window[1]}, host peak RSS "
          f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20!r} GiB",
          file=sys.stderr)
    result = {"correct": bool(ok and window.failed == 0),
              "attempted": window.attempted, "failed": window.failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checked
    for k, c in checked.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return result


def main():
    result = run()
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
