"""Sharded cohort execution demo: the async engine's cohort axis
partitioned over a host-device mesh.

    PYTHONPATH=src python examples/mesh_cohort_demo.py --devices 8

Spawns N virtual host devices, builds a ('data','model') mesh with every
device on the data axis, and drives one federated SER workload through
the cohort engine with ``client_axis="vmap"`` (or ``"fl_step"`` for the
production per-microbatch-DP round): a full-population cohort is stacked
on a leading client axis, constrained onto the data axis, and every
member's local DP-SGD round runs on its own device.  Prints the per-leaf
shard occupancy (the proof the axis is partitioned, not replicated) and
the usual accuracy/participation summary.
"""
import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--executor", default="vmap",
                    choices=("vmap", "fl_step"))
    ap.add_argument("--updates", type=int, default=16)
    ap.add_argument("--sigma", type=float, default=1.0)
    args = ap.parse_args()

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        # append rather than setdefault: a pre-existing XLA_FLAGS value
        # must not silently discard --devices (the partition proof would
        # then pass trivially on 1 device)
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{args.devices}").strip()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax

    from repro.core.testbed import TestbedConfig, build_testbed, run_experiment
    from repro.data.synthetic_ser import SERDataConfig
    from repro.engine import (
        CohortRunner, EngineConfig, assert_cohort_partitioned, cohort_mesh)

    n = len(jax.devices())
    mesh = cohort_mesh()
    print(f"[mesh-cohorts] {n} host devices, mesh {dict(mesh.shape)}")

    cfg = TestbedConfig(num_clients=n, batch_size=32, sigma=args.sigma,
                        data=SERDataConfig(n_total=150 * n), seed=0)
    ec = EngineConfig(client_axis=args.executor, mesh=mesh, max_cohort=n,
                      staleness_window=1e9)
    if args.executor == "fl_step":
        from repro.core.dp import DPConfig
        from repro.core.fl_step import FLStepConfig
        ec = EngineConfig(client_axis="fl_step", mesh=mesh, max_cohort=n,
                          staleness_window=1e9,
                          fl_cfg=FLStepConfig(
                              num_clients=n, n_micro=2, local_lr=0.02,
                              dp=DPConfig(clip_norm=1.0,
                                          noise_multiplier=args.sigma,
                                          granularity="per_microbatch")))

    # 1) shard-shape proof: one full-population cohort through the runner
    clients, params, _, _ = build_testbed(cfg)
    runner = CohortRunner(clients, ec)
    key = jax.random.PRNGKey(0)
    plans = []
    for c in clients:
        key, sub = jax.random.split(key)
        plans.append(runner.dispatch(c, params, sub, 0))
    stacked = runner.run_cohort(plans)
    report = assert_cohort_partitioned(stacked, mesh)
    print(f"[mesh-cohorts] cohort of {n} partitioned: "
          f"{len(report)} leaves x {set(report.values())} member(s)/shard")

    # 2) the same config end-to-end through the run_experiment frontend
    _, log = run_experiment("fedasync", cfg, max_updates=args.updates,
                            alpha=0.4, eval_every=args.updates,
                            engine="cohort", engine_cfg=ec)
    eps = {t: round(v[-1], 2) for t, v in log.eps_trajectory.items() if v}
    print(f"[mesh-cohorts] {sum(log.update_counts.values())} updates in "
          f"cohorts of {sorted(set(log.cohort_sizes))}, "
          f"final acc {log.global_acc[-1]:.3f}, eps per tier {eps}")
    st = log.engine_stats
    print(f"[mesh-cohorts] data path: {st['data_path']} — "
          f"{st['h2d_bytes_per_cohort']:.0f} B/cohort over H2D "
          f"({st['cohorts']} cohorts; index plans only on the arena path)")


if __name__ == "__main__":
    main()
