"""dp_path engine parity: the fused Pallas clip+noise hot path must be a
pure implementation swap — params close to the jnp path and the legacy
per-client loop with IDENTICAL privacy/update bookkeeping, on both the
single-device unroll executor and the forced-8-device sharded mesh, and
one compiled program across the paper's whole sigma grid (the runtime
noise-stddev argument).

Two tolerances, for two reasons:

* With the ``ser_linear`` workload every executor computes the same
  per-example gradients (dense products lower alike in every program),
  so pallas, jnp and legacy agree at rtol 1e-4 — the tight check that
  the kernel path changes nothing but the launch granularity.  The
  per-cohort clipped-and-noised mean over identical per-example grads is
  held to the same tolerance on the CNN's own parameter tree.
* With the SER CNN, XLA:CPU's per-example conv-weight gradients depend
  on the program they are compiled into: at these tiny widths single
  elements disagree with the unbatched gradient (and with finite
  differences) by O(1) relative error, in a different way per executor.
  Engine/jnp against legacy/jnp differs as much as pallas against jnp
  does.  Clipping bounds each example's share and Adam moves a parameter
  by about lr per step, so end params are held to
  ``_EXECUTOR_ATOL = 2 * lr`` (measured gaps: at most 1.0e-3), every
  pair — the jnp-only pair included — and bookkeeping stays exact.
"""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.testbed import TestbedConfig, run_experiment
from repro.data.synthetic_ser import SERDataConfig
from repro.engine import EngineConfig, cohort_step
from repro.models.ser_cnn import SERConfig

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs multiple devices (CI: XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)")

# Tiny model on purpose: interpret-mode pallas unrolls the whole kernel
# grid into the traced program, so compile time scales with param count —
# the small CNN keeps the grid a handful of tiles while exercising the
# identical multi-leaf conv/dense tree structure.
_DIMS = dict(time_frames=12, n_mels=12)
_LR = TestbedConfig().lr
_EXECUTOR_ATOL = 2 * _LR


def _dp_cfg(dp_path, num_clients=5, seed=3, workload="ser_cnn"):
    return TestbedConfig(
        use_dp=True, sigma=1.0, batch_size=16, num_clients=num_clients,
        data=SERDataConfig(n_total=72 * num_clients, **_DIMS),
        model=SERConfig(channels1=8, channels2=16, fc_dim=32, **_DIMS),
        seed=seed, dp_path=dp_path, workload=workload)


def _assert_params_close(a, b, rtol=1e-4, atol=1e-5):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


def _assert_within_executor_bound(a, b):
    _assert_params_close(a, b, rtol=0.0, atol=_EXECUTOR_ATOL)


def _assert_books_match(log_a, log_b):
    assert log_a.update_counts == log_b.update_counts
    assert log_a.eps_trajectory == log_b.eps_trajectory
    assert log_a.staleness == log_b.staleness
    assert log_a.times == log_b.times


# ---------------------------------------------------------------------------
# unroll executor: pallas vs jnp vs legacy (the tentpole acceptance)
# ---------------------------------------------------------------------------

def _three_way(workload):
    """engine/jnp, engine/pallas (ONE fused kernel launch per cohort
    step), legacy/pallas and legacy/jnp runs of one DP FedAsync spec."""
    kw = dict(max_updates=10, eval_every=5, alpha=0.4)
    return {(path, eng): run_experiment(
                "fedasync", _dp_cfg(path, workload=workload), engine=eng,
                **kw)
            for path in ("jnp", "pallas") for eng in ("cohort", "legacy")}


def test_engine_pallas_matches_jnp_and_legacy():
    """The SER CNN through the four executions: every pair within the
    executor bound — engine/jnp against legacy/jnp included, which is
    what shows the gap is the executor's and not the kernel's — and
    bookkeeping identical (the noise epilogue replays ``noise_tree``'s
    exact Gaussian draws, so the runs are comparable draw for draw)."""
    runs = _three_way("ser_cnn")
    keys = list(runs)
    for i, a in enumerate(keys):
        for b in keys[i + 1:]:
            _assert_within_executor_bound(runs[a][0], runs[b][0])
            _assert_books_match(runs[a][1], runs[b][1])
    log_j, log_p = runs["jnp", "cohort"][1], runs["pallas", "cohort"][1]
    # provenance: the run must record which DP path executed and, for the
    # kernel path, the resolved interpret decision + its source
    assert log_j.engine_stats["dp_path"] == "jnp"
    assert log_j.engine_stats["pallas_interpret"] is None
    assert log_p.engine_stats["dp_path"] == "pallas"
    info = log_p.engine_stats["pallas_interpret"]
    assert info["backend"] == jax.default_backend()
    assert info["source"] in ("override", "env", "auto")


def test_engine_pallas_matches_jnp_and_legacy_tightly_on_linear():
    """Where every executor computes the same per-example gradients, the
    four executions agree at rtol 1e-4."""
    runs = _three_way("ser_linear")
    ref = runs["pallas", "cohort"]
    for key, (params, log) in runs.items():
        _assert_params_close(params, ref[0])
        _assert_books_match(log, ref[1])


def test_cohort_clip_mean_noise_matches_jnp_per_member():
    """The engine's per-cohort DP step on identical per-example grads: the
    fused kernel over the stacked (K, B, D) matrix, its draws from
    ``tree_gaussian_vector_like`` per member key, unflattened onto the
    CNN's parameter tree, must equal each member's jnp
    ``dp_mean_gradient`` (per-leaf clip, mean and ``noise_tree``) at
    rtol 1e-4.  A linear loss makes the per-example grads exactly the
    given array in both paths."""
    from repro.core.dp import DPConfig, dp_mean_gradient
    from repro.kernels.dp_clip.ops import dp_clip_mean_noise_cohort
    from repro.models import ser_cnn
    from repro.pytree import tree_gaussian_vector_like

    template = ser_cnn.init(jax.random.PRNGKey(0), _dp_cfg("jnp").model)
    leaves, treedef = jax.tree_util.tree_flatten(template)
    K, B, stddev = 3, 16, 1.0 / 16
    rng = np.random.default_rng(0)
    per_ex = [jax.tree_util.tree_unflatten(treedef, [
        jnp.asarray(rng.normal(size=(B,) + l.shape) * 0.05, jnp.float32)
        for l in leaves]) for _ in range(K)]
    keys = jax.random.split(jax.random.PRNGKey(1), K)

    flat = jnp.stack([jnp.concatenate(
        [l.reshape(B, -1) for l in jax.tree_util.tree_leaves(g)], axis=1)
        for g in per_ex])
    z = jax.vmap(lambda k: tree_gaussian_vector_like(k, template))(keys)
    means, _, fracs = dp_clip_mean_noise_cohort(
        flat, 1.0, jnp.float32(stddev), z)
    fused = cohort_step._unflatten_members(means, template)
    assert float(jnp.min(fracs)) > 0          # the clip actually binds

    def linear_loss(params, example):
        return sum(jnp.vdot(p, e) for p, e in zip(
            jax.tree_util.tree_leaves(params),
            jax.tree_util.tree_leaves(example)))

    for m in range(K):
        ref, _ = dp_mean_gradient(
            linear_loss, template, per_ex[m], keys[m],
            DPConfig(clip_norm=1.0), noise_stddev=stddev)
        _assert_params_close(
            jax.tree_util.tree_map(lambda l: l[m], fused), ref)


def _windowed(workload):
    kw = dict(max_updates=8, eval_every=4, alpha=0.4, engine="cohort")
    ec = EngineConfig(staleness_window=1e9, max_cohort=4)
    return [run_experiment("fedasync", _dp_cfg(path, workload=workload),
                           engine_cfg=ec, **kw) for path in ("jnp", "pallas")]


def _assert_windowed_books(log_j, log_p):
    _assert_books_match(log_j, log_p)
    assert log_j.cohort_sizes == log_p.cohort_sizes
    assert max(log_p.cohort_sizes) > 1     # the window actually batched


def test_engine_pallas_windowed_cohorts_match_jnp():
    """Multi-member cohorts through the step-major fused executor: one
    kernel launch per local step over the stacked (K*B, D) matrix."""
    (p_j, log_j), (p_p, log_p) = _windowed("ser_cnn")
    _assert_within_executor_bound(p_j, p_p)
    _assert_windowed_books(log_j, log_p)


def test_engine_pallas_windowed_cohorts_match_jnp_tightly_on_linear():
    (p_j, log_j), (p_p, log_p) = _windowed("ser_linear")
    _assert_params_close(p_j, p_p)
    _assert_windowed_books(log_j, log_p)


def test_engine_rejects_pallas_with_fl_step_axis():
    """client_axis='fl_step' runs the production per-microbatch DP
    mechanism — the per-example kernel cannot substitute for it."""
    with pytest.raises(ValueError, match="fl_step"):
        run_experiment(
            "fedasync", _dp_cfg("pallas"),
            max_updates=2, eval_every=2, alpha=0.4, engine="cohort",
            engine_cfg=EngineConfig(client_axis="fl_step"))


def test_engine_rejects_unknown_dp_path():
    with pytest.raises(ValueError, match="dp_path"):
        run_experiment("fedasync", _dp_cfg("triton"),
                       max_updates=2, eval_every=2, alpha=0.4,
                       engine="cohort")


# ---------------------------------------------------------------------------
# sigma grid: one compiled program (the PR-5 runtime-noise invariant)
# ---------------------------------------------------------------------------

def test_pallas_sigma_sweep_shares_one_compiled_step():
    """The fused kernel takes noise_stddev as a RUNTIME scalar: after the
    first sigma compiles, the rest of the paper's grid must replay the
    same program (step_builds delta == 0), each agreeing with the jnp
    path at its own sigma."""
    sigmas = (0.5, 1.0, 1.5, 2.0)
    kw = dict(max_updates=6, eval_every=6, alpha=0.4, engine="cohort")

    def run(path, sigma):
        return run_experiment(
            "fedasync", replace(_dp_cfg(path), sigma=sigma), **kw)

    run("pallas", sigmas[0])               # compile both paths once
    run("jnp", sigmas[0])
    b0 = cohort_step.step_builds()
    for sg in sigmas:
        p_p, log_p = run("pallas", sg)
        p_j, log_j = run("jnp", sg)
        _assert_within_executor_bound(p_j, p_p)
        _assert_books_match(log_j, log_p)
    assert cohort_step.step_builds() == b0


# ---------------------------------------------------------------------------
# sharded mesh: padded uneven cohorts through the fused kernel
# ---------------------------------------------------------------------------

def _mesh_dp_cfg(dp_path, workload):
    return _dp_cfg(dp_path, num_clients=len(jax.devices()), seed=0,
                   workload=workload)


@multi_device
@pytest.mark.parametrize("workload", ["ser_cnn", "ser_linear"])
def test_sharded_padded_cohorts_pallas_matches_jnp(workload):
    """UNEVEN cohorts (max_cohort not dividing the data axis) on the
    forced-8-device mesh: the arena path pads them to the bucket size —
    padded members must contribute nothing through the kernel (their
    zero gradients clip to zero and their updates are masked out), and
    the kernel runs partitioned per member under ``shard_map``."""
    from repro.engine import cohort_mesh
    mesh = cohort_mesh()
    n = mesh.shape["data"]
    k = max(2, (3 * n) // 4)
    if k % n == 0:
        pytest.skip(f"{n} devices admit no uneven max_cohort")
    ec = EngineConfig(staleness_window=1e9, max_cohort=k,
                      client_axis="vmap", mesh=mesh, pow2_cohorts=False)
    kw = dict(max_updates=2 * k, eval_every=k, alpha=0.4, engine="cohort")
    p_j, log_j = run_experiment("fedasync", _mesh_dp_cfg("jnp", workload),
                                engine_cfg=ec, **kw)
    p_p, log_p = run_experiment("fedasync", _mesh_dp_cfg("pallas", workload),
                                engine_cfg=ec, **kw)
    if workload == "ser_cnn":
        _assert_within_executor_bound(p_j, p_p)
    else:
        _assert_params_close(p_j, p_p)
    _assert_books_match(log_j, log_p)
    assert log_j.cohort_sizes == log_p.cohort_sizes
    assert log_p.engine_stats["dp_path"] == "pallas"
    for leaf in jax.tree_util.tree_leaves(p_p):
        assert bool(np.isfinite(np.asarray(leaf)).all())


@multi_device
def test_pallas_rejects_cohorts_that_would_run_replicated():
    """The host data path keeps an uneven cohort replicated on the mesh;
    the kernel refuses that rather than run every member on every
    device."""
    from repro.engine import cohort_mesh
    mesh = cohort_mesh()
    n = mesh.shape["data"]
    ec = EngineConfig(staleness_window=1e9, max_cohort=n + 1,
                      client_axis="vmap", mesh=mesh, device_arena=False,
                      pow2_cohorts=False)
    cfg = _dp_cfg("pallas", num_clients=n + 1, seed=0,
                  workload="ser_linear")
    with pytest.raises(ValueError, match="does not partition"):
        run_experiment("fedasync", cfg, max_updates=n + 1,
                       eval_every=n + 1, alpha=0.4, engine="cohort",
                       engine_cfg=ec)
