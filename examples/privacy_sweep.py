"""Privacy sweep over the paper's noise grid, two ways:

1. **Analytic** (default, instant): the Moments Accountant table behind
   the paper's Table 3 — per-tier epsilon as a function of sigma and the
   emergent update frequencies, no training.
2. **Measured** (``--train``): a TRAINED sigma sweep through ONE
   ``repro.api.Session`` — reduced-scale FedAsync runs whose per-tier
   epsilons come out of the actual RunLogs.  The session keeps the
   dataset partitions and the compiled cohort step warm across the grid
   (the step takes the noise scale as a runtime argument), so the four
   sigma points cost ONE testbed generation and ONE XLA compile — this
   script used to be exactly the kind of per-point ``run_experiment``
   loop the Session API deletes.

    PYTHONPATH=src python examples/privacy_sweep.py            # analytic
    PYTHONPATH=src python examples/privacy_sweep.py --train    # + measured
"""
import argparse

from repro.core.accountant import compute_epsilon

Q = 0.136          # paper: q = B/|D_k|
DELTA = 1e-5
SIGMAS = (0.5, 1.0, 1.5, 2.0)
# update counts emergent from the tier clocks at alpha=0.2 (c.f. Fig. 5:
# high-end 62%, mid 16%, low-end <14%) over a 300-update async run,
# x ~7 DP steps per round
TIER_UPDATES = {"HW_T1": 9, "HW_T2": 11, "HW_T3": 26, "HW_T4": 120,
                "HW_T5": 134}
STEPS_PER_UPDATE = 7


def analytic():
    print(f"q={Q} delta={DELTA}  (paper Sec. 4.1.4)")
    header = "tier     updates | " + " | ".join(f"sig={s:<4}" for s in SIGMAS)
    print(header)
    print("-" * len(header))
    eps_by_sigma = {}
    for tier, ups in TIER_UPDATES.items():
        row = []
        for s in SIGMAS:
            eps = compute_epsilon(Q, s, ups * STEPS_PER_UPDATE, DELTA)
            row.append(eps)
            eps_by_sigma.setdefault(s, []).append(eps)
        print(f"{tier}  {ups:7d} | " + " | ".join(f"{e:7.2f}" for e in row))
    print("\nper-sigma disparity (max eps / min eps):")
    for s, es in eps_by_sigma.items():
        print(f"  sigma={s}: {max(es)/min(es):.1f}x "
              f"(paper reports ~5-6x at alpha>=0.4)")
    # FedAvg reference: uniform participation, ~60 rounds
    print("\nFedAvg uniform reference (60 rounds x 7 steps):")
    for s in SIGMAS:
        print(f"  sigma={s}: eps={compute_epsilon(Q, s, 420, DELTA):.2f} "
              f"on every tier")


def trained(max_updates: int):
    from repro.api import ExperimentSpec, RunBudget, Session, StrategySpec
    from repro.core.testbed import TestbedConfig
    from repro.data.synthetic_ser import SERDataConfig

    spec = ExperimentSpec(
        testbed=TestbedConfig(use_dp=True, sigma=SIGMAS[0], batch_size=64,
                              data=SERDataConfig(n_total=2940), seed=0),
        strategy=StrategySpec("fedasync", alpha=0.2),
        run=RunBudget(max_updates=max_updates, eval_every=20))
    session = Session()
    print(f"\nmeasured sigma sweep (FedAsync alpha=0.2, "
          f"{max_updates} updates, one warm session) ...")
    result = session.sweep(spec, axes={"testbed.sigma": list(SIGMAS)})
    for point, log, wall in zip(result.points, result.logs, result.wall_s):
        eps = {t: (v[-1] if v else 0.0)
               for t, v in log.eps_trajectory.items()}
        disp = (max(eps.values()) / max(min(eps.values()), 1e-9)
                if eps else 0.0)
        by_tier = " ".join(f"{t.split('_')[1]}={e:.1f}"
                           for t, e in sorted(eps.items()))
        print(f"  sigma={point['testbed.sigma']}: eps {by_tier} "
              f"(disparity {disp:.1f}x, acc {log.global_acc[-1]:.3f}, "
              f"{wall:.1f}s)")
    print(f"  session cache telemetry: {session.stats()}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--train", action="store_true",
                    help="also run the measured (trained) sigma sweep "
                         "through one Session")
    ap.add_argument("--max-updates", type=int, default=120)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    analytic()
    if args.train:
        trained(args.max_updates)


if __name__ == "__main__":
    main()
