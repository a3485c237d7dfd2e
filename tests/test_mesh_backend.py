"""Mesh backend tests: cohort sharding rules, host-mesh clamping, and —
when multiple devices exist — genuinely partitioned cohort execution.

Sharding-spec construction is device-free (AbstractMesh).  The
partitioned-execution and acceptance tests need multiple host devices, so
they skip on a single device and run in CI's multi-device job
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``).  The
``make_host_mesh`` regression runs in a subprocess with its own forced
device count (the flag only takes effect before the first jax import).
"""
import os
import subprocess
import sys
from dataclasses import replace

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import largest_divisor

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs multiple devices (CI: XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)")


@pytest.fixture(scope="module")
def amesh():
    # AbstractMesh: shape metadata without devices
    return jax.sharding.AbstractMesh((8, 1), ("data", "model"))


# ---------------------------------------------------------------------------
# sharding rule (device-free)
# ---------------------------------------------------------------------------

def test_cohort_spec_partitions_divisible_cohorts(amesh):
    from repro.engine.mesh_backend import cohort_spec
    assert cohort_spec(amesh, (8, 5, 40, 64)) == P("data", None, None, None)
    assert cohort_spec(amesh, (16,)) == P("data")


def test_cohort_spec_replicates_uneven_cohorts(amesh):
    """GSPMD silently replicates uneven leading-dim partitions, so the
    rule must fall back to explicit replication (not emit a spec that
    looks partitioned but isn't)."""
    from repro.engine.mesh_backend import cohort_spec
    assert cohort_spec(amesh, (4, 5)) == P()
    assert cohort_spec(amesh, (2, 3, 3)) == P()
    assert cohort_spec(amesh, ()) == P()


def test_cohort_sharding_hashable_per_mesh(amesh):
    """cached_cohort_step keys compiled programs on the sharding object:
    two CohortShardings over the same mesh must collide."""
    from repro.engine.mesh_backend import CohortSharding
    a, b = CohortSharding(amesh), CohortSharding(amesh)
    assert a == b and hash(a) == hash(b) and len({a, b}) == 1
    assert a.spec((8, 3)) == P("data", None)


def test_step_cache_keyed_per_mesh_with_invalidation(amesh):
    """Supplying shardings must NOT bypass the compiled-step cache (every
    sweep run used to re-trace); entries are dropped per mesh."""
    from repro.core.dp import DPConfig
    from repro.engine.cohort_step import cached_cohort_step, invalidate_step_cache
    from repro.engine.mesh_backend import CohortSharding
    from repro.optim.optimizers import Adam

    def loss(p, ex):
        return ((p["w"] - ex["x"]) ** 2).sum()

    args = (loss, DPConfig(clip_norm=1.0, noise_multiplier=0.0), Adam(lr=0.1))
    sh = CohortSharding(amesh)
    invalidate_step_cache(amesh)
    s1 = cached_cohort_step(*args, client_axis="vmap", client_shardings=sh)
    s2 = cached_cohort_step(*args, client_axis="vmap",
                            client_shardings=CohortSharding(amesh))
    assert s1 is s2                       # same mesh -> same compiled step
    s3 = cached_cohort_step(*args, client_axis="vmap")
    assert s3 is not s1                   # unsharded is a different entry
    assert invalidate_step_cache(amesh) == 1
    s4 = cached_cohort_step(*args, client_axis="vmap", client_shardings=sh)
    assert s4 is not s1                   # invalidation dropped the entry
    assert cached_cohort_step(*args, client_axis="vmap") is s3  # untouched
    invalidate_step_cache(amesh)


# ---------------------------------------------------------------------------
# make_host_mesh clamping (satellite regression)
# ---------------------------------------------------------------------------

def test_largest_divisor():
    assert largest_divisor(6, 4) == 3
    assert largest_divisor(8, 6) == 4
    assert largest_divisor(8, 8) == 8
    assert largest_divisor(7, 3) == 1
    assert largest_divisor(6, 0) == 1      # used to divide by zero downstream
    assert largest_divisor(6, 100) == 6


def test_make_host_mesh_clamps_on_forced_six_devices():
    """Regression (ISSUE 2): ``data=4`` on 6 devices built a ``(4, 1)``
    mesh — invalid where jax requires the product to cover the devices,
    silently stranding two of them where it truncates.  Axis sizes now
    clamp to divisors of the device count."""
    code = """
import jax
from repro.launch.mesh import make_host_mesh
assert len(jax.devices()) == 6, len(jax.devices())
m = make_host_mesh(data=4)
assert dict(m.shape) == {"data": 3, "model": 1}, dict(m.shape)
assert m.devices.size == 3
m = make_host_mesh(data=6, model=4)
assert dict(m.shape) == {"data": 6, "model": 1}, dict(m.shape)
m = make_host_mesh(data=2, model=3)
assert dict(m.shape) == {"data": 2, "model": 3}, dict(m.shape)
m = make_host_mesh(data=2, model=2)      # 2 does not divide 6 // 2 = 3
assert dict(m.shape) == {"data": 2, "model": 1}, dict(m.shape)
m = make_host_mesh(data=0)          # used to ZeroDivisionError
assert dict(m.shape) == {"data": 1, "model": 1}, dict(m.shape)
print("host-mesh-clamp-ok")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=6"
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert "host-mesh-clamp-ok" in out.stdout


# ---------------------------------------------------------------------------
# partitioned execution (multi-device job)
# ---------------------------------------------------------------------------

def _mesh_cfg():
    from repro.core.testbed import TestbedConfig
    from repro.data.synthetic_ser import SERDataConfig
    n = len(jax.devices())
    return TestbedConfig(num_clients=n, batch_size=32,
                         data=SERDataConfig(n_total=120 * n), seed=0)


def _assert_close(a, b, rtol=1e-4, atol=1e-5):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


@multi_device
def test_cohort_step_partitions_cohort_axis():
    """Smoke: one full-population cohort through the vmap executor on a
    mesh — every stacked leaf must hold K / n_data members per shard."""
    from repro.core.testbed import build_testbed
    from repro.engine import (CohortRunner, EngineConfig,
                              assert_cohort_partitioned, cohort_mesh)
    mesh = cohort_mesh()
    n = len(jax.devices())
    clients, params, _, _ = build_testbed(_mesh_cfg())
    runner = CohortRunner(clients, EngineConfig(
        client_axis="vmap", mesh=mesh, max_cohort=n))
    key = jax.random.PRNGKey(0)
    plans = []
    for c in clients:
        key, sub = jax.random.split(key)
        plans.append(runner.dispatch(c, params, sub, 0))
    stacked = runner.run_cohort(plans)
    report = assert_cohort_partitioned(stacked, mesh)
    assert report and set(report.values()) == {n // mesh.shape["data"]}


@multi_device
def test_padded_uneven_cohort_partitions_and_matches():
    """Tentpole acceptance: an UNEVEN cohort — the case PR 2's GSPMD rule
    could only run replicated — pads to its bucket size, GENUINELY
    partitions (addressable-shard shapes), and its first K rows equal the
    unpadded host-path result (pad members are zero-step masked)."""
    from repro.core.testbed import build_testbed
    from repro.engine import (CohortRunner, EngineConfig,
                              assert_cohort_partitioned, cohort_mesh,
                              padded_cohort_size)
    mesh = cohort_mesh()
    n = len(jax.devices())
    n_data = mesh.shape["data"]
    k = max(2, (3 * n_data) // 4)
    assert k % n_data, "need a cohort size that does not divide the axis"
    cfg = replace(_mesh_cfg(), use_dp=False)

    def one_cohort(ec):
        clients, params, _, _ = build_testbed(cfg)
        runner = CohortRunner(clients, ec)
        key = jax.random.PRNGKey(0)
        plans = []
        for c in clients[:k]:
            key, sub = jax.random.split(key)
            plans.append(runner.dispatch(c, params, sub, 0))
        return runner.run_cohort(plans)

    stacked = one_cohort(
        EngineConfig(client_axis="vmap", mesh=mesh, max_cohort=n))
    k_pad = padded_cohort_size(k, n_data)
    assert jax.tree_util.tree_leaves(stacked)[0].shape[0] == k_pad
    report = assert_cohort_partitioned(stacked, mesh)
    assert report and set(report.values()) == {k_pad // n_data}
    ref = one_cohort(EngineConfig(device_arena=False))  # host path, no mesh
    for a, b in zip(jax.tree_util.tree_leaves(stacked),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(np.asarray(a)[:k], np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_invalidate_step_cache_frees_arena_buffers(micro_cfg):
    """The compiled-step cache holds step FUNCTIONS only — arenas are
    per-runner call arguments, never closed over — so dropping a mesh's
    cache entries plus the runner must free every device-resident arena
    buffer (params, opt state and dataset)."""
    import gc
    import weakref

    from repro.core.testbed import build_testbed
    from repro.engine import CohortRunner, EngineConfig, cohort_mesh
    from repro.engine.cohort_step import invalidate_step_cache

    mesh = cohort_mesh()
    clients, params, _, _ = build_testbed(micro_cfg)
    runner = CohortRunner(clients, EngineConfig(mesh=mesh, max_cohort=2))
    key = jax.random.PRNGKey(0)
    plans = []
    for c in clients[:2]:
        key, sub = jax.random.split(key)
        plans.append(runner.dispatch(c, params, sub, 0))
    stacked = runner.run_cohort(plans)
    jax.block_until_ready(jax.tree_util.tree_leaves(stacked)[0])
    refs = [weakref.ref(leaf) for leaf in (
        jax.tree_util.tree_leaves(runner._arena_data)
        + jax.tree_util.tree_leaves(runner._arena_params)
        + jax.tree_util.tree_leaves(runner._arena_opt))]
    # at least the runner's compiled step AND its arena helpers entry
    # (cached_arena_helpers shares the step cache) must drop
    assert invalidate_step_cache(mesh) >= 2
    del runner, plans, stacked
    gc.collect()
    alive = [r for r in refs if r() is not None]
    assert not alive, f"{len(alive)}/{len(refs)} arena buffers leaked"


@multi_device
def test_run_experiment_vmap_sharded_matches_unroll():
    """The acceptance criterion: run_experiment(..., engine="cohort",
    engine_cfg=EngineConfig(client_axis="vmap"), mesh=...) end-to-end on a
    multi-host-device mesh, params allclose vs the unroll executor with
    identical RunLog bookkeeping.

    DP off for the tight comparison: with DP on, noise-dominated
    gradients near zero get sign-flipped by ~1e-7 lowering differences
    between the batched and unbatched conv programs, and Adam's
    normalized first step turns each flip into a ±lr difference (the DP
    case is covered at that documented looser tolerance below)."""
    from repro.core.testbed import run_experiment
    from repro.engine import EngineConfig, cohort_mesh
    mesh = cohort_mesh()
    n = len(jax.devices())
    cfg = replace(_mesh_cfg(), use_dp=False)
    kw = dict(rounds=2, eval_every=2, engine="cohort")
    p_u, log_u = run_experiment("fedavg", cfg,
                                engine_cfg=EngineConfig(max_cohort=n), **kw)
    p_v, log_v = run_experiment("fedavg", cfg, mesh=mesh,
                                engine_cfg=EngineConfig(client_axis="vmap",
                                                        max_cohort=n), **kw)
    _assert_close(p_u, p_v)
    assert log_u.update_counts == log_v.update_counts
    assert log_u.staleness == log_v.staleness
    assert log_u.eps_trajectory == log_v.eps_trajectory
    assert log_u.times == log_v.times
    np.testing.assert_allclose(log_u.global_acc, log_v.global_acc, atol=1e-5)
    assert log_v.cohort_sizes == [n, n]    # full-population compiled cohorts


@multi_device
def test_sharded_async_dp_run_trains():
    """FedAsync with DP over sharded cohorts: bookkeeping exact vs the
    unroll executor, params allclose at the Adam-sign-amplified tolerance
    (see test_run_experiment_vmap_sharded_matches_unroll)."""
    from repro.core.testbed import run_experiment
    from repro.engine import EngineConfig, cohort_mesh
    mesh = cohort_mesh()
    n = len(jax.devices())
    kw = dict(max_updates=2 * n, eval_every=n, alpha=0.4, engine="cohort")
    ec = EngineConfig(staleness_window=1e9, max_cohort=n)
    _, log_u = run_experiment("fedasync", _mesh_cfg(), engine_cfg=ec, **kw)
    p_v, log_v = run_experiment(
        "fedasync", _mesh_cfg(), mesh=mesh,
        engine_cfg=replace(ec, client_axis="vmap"), **kw)
    assert log_u.update_counts == log_v.update_counts
    assert log_u.eps_trajectory == log_v.eps_trajectory
    assert sum(log_v.cohort_sizes) == 2 * n
    assert max(log_v.cohort_sizes) == n    # the window batched full cohorts
    for leaf in jax.tree_util.tree_leaves(p_v):
        assert bool(np.isfinite(np.asarray(leaf)).all())
