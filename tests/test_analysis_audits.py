"""Compiled-program audits + the compile guard.

Device-free logic runs everywhere; the real-cohort-step audits need
multiple host devices and activate in CI's engine-mesh job
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``), where they
check the ACTUAL compiled step — and seeded regressions (replicated
client axis, dropped donation, forbidden collective) must each fail."""
import functools
import warnings
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import (
    AuditFailure, CompileBudgetExceeded, audit_collectives, audit_donation,
    audit_engine_stats, audit_sharding, compile_guard, donation_aliases,
    step_signature, sweep_max_builds)
from repro.core.runlog import ENGINE_STATS_KEYS

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs multiple devices (CI: XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)")


# ---------------------------------------------------------------------------
# donation audit (single device suffices: CPU materializes aliases)
# ---------------------------------------------------------------------------

def _compiled_text(fn, *avals, donate=()):
    f = jax.jit(fn, donate_argnums=donate) if donate else jax.jit(fn)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")      # "donated buffers not usable"
        return f.lower(*avals).compile().as_text()


def test_donation_alias_table_parses():
    txt = _compiled_text(lambda a, b: (a + b, b * 2),
                         jnp.zeros((8, 4)), jnp.ones((8, 4)), donate=(0,))
    aliases = donation_aliases(txt)
    assert aliases and all(isinstance(p, int) for _, p in aliases)
    assert audit_donation(txt, expect=True) == len(aliases)


def test_donation_audit_catches_dropped_alias():
    # donate requested, but no output matches the input buffer — XLA
    # silently copies; the audit is what makes that loud
    txt = _compiled_text(lambda a: a[0:1], jnp.zeros((8, 4)), donate=(0,))
    assert donation_aliases(txt) == []
    with pytest.raises(AuditFailure, match="aliases materialized"):
        audit_donation(txt, expect=True)


def test_donation_audit_catches_unexpected_alias():
    # the pipelined path REQUIRES donation-free programs
    txt = _compiled_text(lambda a, b: (a + b, b * 2),
                         jnp.zeros((8, 4)), jnp.ones((8, 4)), donate=(0,))
    with pytest.raises(AuditFailure, match="expected OFF"):
        audit_donation(txt, expect=False)
    clean = _compiled_text(lambda a, b: a + b,
                           jnp.zeros((8, 4)), jnp.ones((8, 4)))
    assert audit_donation(clean, expect=False) == 0


# ---------------------------------------------------------------------------
# sharding audit (device-free via stand-in shardings)
# ---------------------------------------------------------------------------

class _FakeSharding:
    def __init__(self, shard_shape):
        self._shard = tuple(shard_shape)

    def shard_shape(self, shape):
        return self._shard


def _fake_compiled(*shardings):
    return SimpleNamespace(output_shardings=list(shardings))


def test_sharding_audit_passes_partitioned():
    compiled = _fake_compiled(_FakeSharding((1, 4)), _FakeSharding(()))
    assert audit_sharding(compiled, [(8, 4), ()], client_dim=8,
                          min_partition=2) == 1


def test_sharding_audit_fails_replicated_client_axis():
    compiled = _fake_compiled(_FakeSharding((8, 4)))
    with pytest.raises(AuditFailure, match="replicated"):
        audit_sharding(compiled, [(8, 4)], client_dim=8)


def test_sharding_audit_fails_when_nothing_matches():
    compiled = _fake_compiled(_FakeSharding((2, 4)))
    with pytest.raises(AuditFailure, match="checked nothing"):
        audit_sharding(compiled, [(16, 4)], client_dim=8)


# ---------------------------------------------------------------------------
# collective audit (synthetic HLO)
# ---------------------------------------------------------------------------

_AG_HLO = """\
HloModule m, entry_computation_layout={(f32[4,8]{1,0})->f32[32,8]{1,0}}

ENTRY %main (p0: f32[4,8]) -> f32[32,8] {
  %p0 = f32[4,8] parameter(0)
  ROOT %ag = f32[32,8] all-gather(%p0), replica_groups=[1,8]<=[8], dimensions={0}
}
"""


def test_collective_audit_forbid_fires():
    with pytest.raises(AuditFailure, match="forbidden collective"):
        audit_collectives(_AG_HLO, forbid=("all-gather",))


def test_collective_audit_budget():
    counts = audit_collectives(_AG_HLO, max_counts={"all-gather": 1})
    assert counts["all-gather"] == 1
    with pytest.raises(AuditFailure, match="exceeds budget"):
        audit_collectives(_AG_HLO, max_counts={"all-gather": 0})


# ---------------------------------------------------------------------------
# engine-stats audit
# ---------------------------------------------------------------------------

def _stats(**over):
    base = {k: 0 for k in ENGINE_STATS_KEYS}
    base.update(data_path="arena", dp_path="jnp", pallas_interpret=None,
                h2d_bytes_per_cohort=0.0, pipeline_depth=1)
    base.update(over)
    return base


def test_engine_stats_audit_roundtrip():
    assert audit_engine_stats(_stats()) == _stats()


def test_engine_stats_audit_catches_drift():
    missing = _stats()
    missing.pop("drain_waits")
    with pytest.raises(AuditFailure, match="drain_waits"):
        audit_engine_stats(missing)
    extra = _stats(new_counter=3)
    with pytest.raises(AuditFailure, match="new_counter"):
        audit_engine_stats(extra)


def test_engine_stats_audit_cross_field_invariants():
    with pytest.raises(AuditFailure, match="submit/drain overlap"):
        audit_engine_stats(_stats(pipeline_depth=2,
                                  host_syncs_between_evals=1))
    with pytest.raises(AuditFailure, match="interpret provenance"):
        audit_engine_stats(_stats(dp_path="pallas"))
    ok = _stats(dp_path="pallas", pallas_interpret={
        "backend": "cpu", "interpret": True, "source": "auto"})
    audit_engine_stats(ok)


# ---------------------------------------------------------------------------
# compile guard
# ---------------------------------------------------------------------------

def test_compile_guard_budget_and_delta():
    from repro.engine import cohort_step
    base = cohort_step._STEP_BUILDS
    try:
        with compile_guard(2, label="test") as g:
            cohort_step._STEP_BUILDS += 1
            assert g.delta == 1
        assert g.delta == 1

        with pytest.raises(CompileBudgetExceeded, match="budgeted for 0"):
            with compile_guard(0, label="test"):
                cohort_step._STEP_BUILDS += 1
    finally:
        cohort_step._STEP_BUILDS = base


def test_compile_guard_never_masks_exceptions():
    from repro.engine import cohort_step
    base = cohort_step._STEP_BUILDS
    try:
        with pytest.raises(RuntimeError, match="boom"):
            with compile_guard(0):
                cohort_step._STEP_BUILDS += 5
                raise RuntimeError("boom")
    finally:
        cohort_step._STEP_BUILDS = base


def test_compile_guard_rejects_negative_budget():
    with pytest.raises(ValueError):
        with compile_guard(-1):
            pass


# ---------------------------------------------------------------------------
# sweep budgets from spec signatures
# ---------------------------------------------------------------------------

def test_sigma_grid_is_one_signature():
    from repro.api.spec import ExperimentSpec, replace_path
    import dataclasses
    spec = ExperimentSpec()
    spec = replace_path(spec, "testbed.use_dp", True)
    grid = [replace_path(spec, "testbed.sigma", s) for s in (0.5, 1.0, 2.0)]
    assert sweep_max_builds(grid) == 1
    assert len({step_signature(s) for s in grid}) == 1
    # noise OFF is a different program (add_noise is static)
    grid.append(replace_path(spec, "testbed.sigma", 0.0))
    assert sweep_max_builds(grid) == 2
    # so is a different DP implementation
    grid.append(replace_path(grid[0], "testbed.dp_path", "pallas"))
    assert sweep_max_builds(grid) == 3
    # legacy backend never touches the step cache
    assert step_signature(
        dataclasses.replace(spec, backend="legacy")) is None
    assert sweep_max_builds([dataclasses.replace(spec, backend="legacy")]) == 0


# ---------------------------------------------------------------------------
# the REAL compiled cohort step (multi-device; CI engine-mesh job)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def compiled_step():
    if len(jax.devices()) < 2:
        pytest.skip("needs multiple devices")
    import jax.random as jr
    from repro.api.workloads import get_workload
    from repro.core.testbed import TestbedConfig, build_clients, \
        build_partitions
    from repro.data.synthetic_ser import SERDataConfig
    from repro.engine import CohortRunner, EngineConfig, cohort_mesh
    from repro.models.ser_cnn import SERConfig

    n_clients = 8
    dims = dict(time_frames=12, n_mels=12)
    mesh = cohort_mesh(max_cohort=n_clients)
    ec = EngineConfig(staleness_window=45.0, max_cohort=8,
                      client_axis="vmap", mesh=mesh)
    tb = TestbedConfig(
        use_dp=True, sigma=0.5, batch_size=16, num_clients=n_clients,
        data=SERDataConfig(n_total=36 * n_clients, **dims),
        model=SERConfig(channels1=8, channels2=16, fc_dim=32, **dims))
    splits, _pooled = build_partitions(tb)
    clients = build_clients(tb, splits)
    runner = CohortRunner(clients, ec)
    wl = get_workload(tb.workload)
    params0 = wl.init(jr.PRNGKey(0), tb.model)
    key = jr.PRNGKey(1)
    plans = []
    for c in clients:
        key, sub = jr.split(key)
        plans.append(runner.dispatch(c, params0, sub, 0))
    staged = runner.stage_cohort(plans)
    runner._ensure_state_arenas(params0)
    args = (runner._arena_params, runner._arena_opt, runner._arena_data,
            staged.slots, staged.data_slots, staged.batch_idx, staged.keys,
            staged.n_steps, runner._noise_std, staged.corrupt)
    compiled = runner.cohort_step.lower(*args).compile()
    shapes = [tuple(s.shape) for s in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda *a: runner.cohort_step(*a), *args))]
    return SimpleNamespace(compiled=compiled, text=compiled.as_text(),
                           shapes=shapes, n_clients=n_clients,
                           n_devices=len(mesh.devices.flatten()))


@multi_device
def test_real_step_client_axis_partitions(compiled_step):
    # every output leaf stacked over the cohort axis must partition —
    # GSPMD replicating it is the PR-2 silent regression
    audited = audit_sharding(
        compiled_step.compiled, compiled_step.shapes,
        client_dim=compiled_step.n_clients,
        min_partition=compiled_step.n_devices)
    assert audited > 0


@multi_device
def test_real_step_is_donation_free(compiled_step):
    # since the PR-9 screen/corrupt epilogue the cohort step never
    # donates its inputs on ANY path (XLA:CPU's thunk runtime recycled
    # the donated opt arena while the epilogue still read pre-scatter
    # state); the alias table must stay empty — the same invariant the
    # pipelined scheduler always required
    assert audit_donation(compiled_step.text, expect=False) == 0


@multi_device
def test_real_step_collective_budget(compiled_step):
    # the sharded-arena gather legitimately all-gathers; pin the budget
    # to its measured footprint (rederive deliberately if the data path
    # changes) rather than pretending the step is collective-free
    counts = audit_collectives(
        compiled_step.text,
        max_counts={"all-gather": 120, "all-reduce": 60, "all-to-all": 8,
                    "reduce-scatter": 8, "collective-permute": 8})
    assert counts.get("all-gather", 0) > 0       # the gather IS there


@multi_device
def test_seeded_replicated_client_axis_fails(compiled_step):
    # regression seed: an unconstrained program leaves the client axis
    # replicated -> the audit must fire
    x = jnp.zeros((compiled_step.n_clients, 4))
    compiled = jax.jit(lambda v: v * 2).lower(x).compile()
    with pytest.raises(AuditFailure, match="replicated"):
        audit_sharding(compiled, [(compiled_step.n_clients, 4)],
                       client_dim=compiled_step.n_clients)


@multi_device
def test_seeded_forced_all_gather_fails():
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = len(jax.devices())
    from repro.launch.mesh import auto_mesh
    mesh = auto_mesh((n,), ("data",))
    sharded = NamedSharding(mesh, P("data"))
    repl = NamedSharding(mesh, P())
    f = jax.jit(lambda v: v + 1.0, in_shardings=(sharded,),
                out_shardings=repl)
    txt = f.lower(
        jax.ShapeDtypeStruct((8 * n, 4), jnp.float32)).compile().as_text()
    with pytest.raises(AuditFailure, match="all-gather"):
        audit_collectives(txt, forbid=("all-gather",))
