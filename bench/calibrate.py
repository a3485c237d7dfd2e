#!/usr/bin/env python3
"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --controls 4 \
        --fault-seeds 4 --faults unchanged,half_batch,answer

In one process, on the chip the cell asks for:

* the program, at the cell's own size: for each of ``--seeds`` seeds,
  one experiment through ``Session.run`` with the cell's spec (the noise
  multipliers taken in turn), compared with the plain reference exactly
  as a benchmark run compares it: the lower readings;
* the control: the reference computed in bfloat16, put in the program's
  place, on the first ``--controls`` seeds: the upper readings;
* the ``--faults``, planted in the reference put in the program's place,
  on the first ``--fault-seeds`` seeds: a local round that returns its
  params unchanged, half of each batch left out, an epsilon altered
  where it is produced.

Prints one JSON line per reading and writes them all to ``--out``.  It
reads no limit: it is what the limits are set from.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import correct, reference, run  # noqa: E402

FAULTS = ("unchanged", "half_batch", "answer")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=None,
                    help="seeds that get the faults (default: --controls)")
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 101)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    cell = run.Cell.load(args.workload)
    run.device_check(cell.chips, True)
    import jax
    import jax.numpy as jnp
    from repro.api import Session
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    faults = [f for f in args.faults.split(",") if f]
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise SystemExit(f"unknown faults {sorted(unknown)}: {FAULTS}")
    fault_seeds = (args.controls if args.fault_seeds is None
                   else args.fault_seeds)
    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    corpus = reference.Corpus(cell.config)

    sigmas = cell.traffic["sigmas"]
    for i, seed in enumerate(seeds):
        sigma = sigmas[i % len(sigmas)]
        tb_seed = run.testbed_seed(seed)
        t0 = time.perf_counter()
        specs, _ = run.build_specs(cell, seed)
        spec = [s for s in specs if s.testbed.sigma == sigma][0]
        session = Session()
        params, log = session.run(spec)
        params = jax.device_get(params)
        books = correct.program_books(log)
        del session
        t1 = time.perf_counter()
        init, ref, ref_books = reference.simulate(
            cell.config, cell.traffic, corpus, tb_seed, sigma)
        t2 = time.perf_counter()
        emit({"kind": "program", "seed": seed, "sigma": sigma,
              "program_s": t1 - t0, "reference_s": t2 - t1,
              **correct.compare(init, params, books, ref, ref_books),
              "leaves": correct.leaf_readings(init, params, ref)})
        if i < args.controls:
            _, ctl, ctl_books = reference.simulate(
                cell.config, cell.traffic, corpus, tb_seed, sigma,
                dtype=jnp.bfloat16)
            emit({"kind": "control", "seed": seed, "sigma": sigma,
                  **correct.compare(init, ctl, ctl_books, ref, ref_books)})
        if i < fault_seeds:
            for fault in faults:
                _, bad, bad_books = reference.simulate(
                    cell.config, cell.traffic, corpus, tb_seed, sigma,
                    fault=fault)
                emit({"kind": f"fault:{fault}", "seed": seed, "sigma": sigma,
                      **correct.compare(init, bad, bad_books, ref, ref_books),
                      "leaves": correct.leaf_readings(init, bad, ref)})
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text("\n".join(json.dumps(r) for r in rows) + "\n")


if __name__ == "__main__":
    main()
