"""The compiled cohort step: one jitted program that runs every cohort
member's whole local round (all DP-SGD minibatch steps) plus the fused
weighted aggregation — the simulation-side sibling of
``repro.core.fl_step``'s ``fl_train_step`` (same structure: stacked client
axis -> mapped local phase -> weights-vector reduction over the client
axis).

Two data paths feed it (``make_cohort_step(..., arena=...)``):

* **arena** (the engine's default) — the cohort assembles ON DEVICE from
  persistent arenas: all clients' params/opt state live in one stacked
  (A, ...) pytree (slot per client) and every client's dataset is
  uploaded once; the step gathers the cohort with ``jnp.take`` over a
  (K,) slot vector, gathers minibatches from the resident data via the
  (K, S_max, B) int32 ``batch_idx`` plan, and scatters the new optimizer
  state back into the opt arena.  Per-cohort H2D is a few KB of
  indices.
* **host** (PR-2 baseline, ``arena=False``) — params/opt state stack in
  Python per cohort and fully materialized batch tensors cross H2D every
  step; kept for the benchmark comparison and for raw-pytree shardings.

Numerical parity with the legacy per-client loop is load-bearing (the
tier-1 parity tests assert it): the per-step math is literally the same
``dp_mean_gradient`` / ``opt.update`` composition as ``Client.local_train``
uses, including the per-step ``key, sub = split(key)`` chain, executed
inside one compiled program instead of one jit call per minibatch.
Members whose local round is shorter than the cohort's padded step count
are masked with ``jnp.where`` (a masked step leaves params/opt state/key
untouched).

Four client-axis executors (``client_axis``), chosen from CPU
measurements on the SER testbed (B=32, 5 local steps, 317k params; legacy
per-step dispatch = 377 ms per local round):

* ``"unroll"`` (default) — flat program: Python loop over the K members
  AND the local steps inside one jit.  ~250 ms per client warm (the
  whole-round fusion is where the engine's measured speedup comes from),
  but XLA compile time scales with K * S — keep ``max_cohort`` small and
  let the cross-run step cache amortize it.  The right choice on a single
  CPU device.
* ``"map"``  — ``lax.map`` over the stacked axis: compile cost is
  K-independent (body compiled once) but XLA CPU optimizes while-loop
  bodies poorly (~2x slower warm than the flat program).  Use for large
  cohorts / one-off runs.
* ``"vmap"`` — ``jax.vmap`` over the stacked axis, composing with
  ``client_shardings`` exactly like ``fl_train_step``'s broadcast/stack
  layout: on a mesh the cohort partitions over the data axes and members
  genuinely run in parallel (build the shardings with
  ``engine.mesh_backend.CohortSharding``).  On a single CPU device it
  turns every convolution into a batched-filter conv that XLA lowers off
  the fast path — do not use it there.
* ``"fl_step"`` — the PRODUCTION local round: each member runs
  ``core/fl_step.make_local_phase`` (per-microbatch DP clipping, one
  noise draw per local step, plain ``local_lr`` SGD — the client
  optimizer state passes through untouched), vmapped over the stacked
  axis and composing with ``client_shardings`` the same way.  Requires an
  ``FLStepConfig`` (``fl_cfg``); with DP off and ``n_micro=1`` it
  computes exactly the simulation math (the tier-1 parity test asserts
  it), with DP on it is the per-microbatch granularity the large
  architectures train under rather than the paper's per-example Eq. 4.

DP implementation (``dp_path``): with ``dp_path="pallas"`` (and DP on)
the member-major executors above are replaced by a STEP-MAJOR fused
executor — all K members advance one local step together, so each DP-SGD
step launches the fused ``repro.kernels.dp_clip`` clip+mean+noise kernel
ONCE over the whole cohort's stacked (K*B, D) per-example grad matrix
(not vmap-of-pallas_call per member), with the Gaussian noise added in
the kernel's final-tile epilogue.  The noise stddev stays the runtime
scalar argument and the noise draws replay ``noise_tree``'s per-leaf
split order, so the pallas path keeps both the one-program-per-sigma-
sweep invariant and float-tolerance parity with ``dp_path="jnp"`` and
the legacy loop (asserted by tests/test_dp_path_engine.py).  Padded
mask members ride along exactly like every other masked step: their
kernel row is computed and discarded (``n_steps=0`` masks the update,
the merge gives them coefficient 0).  On a mesh the kernel runs under
``shard_map`` over the cohort axis, each device launching it on its own
members: GSPMD cannot partition a Mosaic kernel, and a cohort that would
stay replicated is refused.
"""
from __future__ import annotations

import functools
from dataclasses import replace
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.dp import (
    DPConfig, dp_mean_gradient, per_example_grads, validate_dp_path)

# flat-unroll the local-step loop up to this length; beyond it, fall back
# to a rolled scan to keep compile times bounded
_MAX_FULL_UNROLL = 16

# programs built since process start (make_cohort_step invocations): the
# observable cache-miss counter behind the Session sweep-amortization
# acceptance test/bench — a warm sigma sweep must NOT grow it per point
_STEP_BUILDS = 0


def step_builds() -> int:
    """How many cohort-step programs have been BUILT (cache misses at the
    make_cohort_step level; each build implies a fresh XLA trace+compile
    on first call).  ``benchmarks.fl_benchmarks.bench_sweep_amortization``
    reports the cold-vs-warm delta of this counter."""
    return _STEP_BUILDS

# the one place the executor set is defined: make_cohort_step and
# EngineConfig both validate against it (they used to disagree on the
# default too — "map" vs "unroll" — which handed direct callers the
# executor the docstring calls ~2x slower on CPU)
CLIENT_AXES = ("unroll", "map", "vmap", "fl_step")


def validate_client_axis(client_axis: str) -> str:
    if client_axis not in CLIENT_AXES:
        raise ValueError(
            f"client_axis must be one of {CLIENT_AXES}: {client_axis!r}")
    return client_axis


def _tree_where(mask, new, old):
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(mask, n, o), new, old)


def _corrupt_members(ref, new, scale):
    """In-step transit corruption of the stacked upload payloads:
    member k's delivered params become ``p0 + scale[k] * (p - p0)``
    (float32, elementwise).  ``scale[k] == 1.0`` is the CLEAN sentinel
    and selects ``p`` verbatim through ``jnp.where`` — clean members'
    payloads stay bitwise identical to an uncorrupted run, which is
    what makes screening-off a true no-op (and keeps the one-program
    invariant: the scale vector is a runtime argument, corruption rate
    sweeps never retrace).  A NaN scale poisons the whole payload (the
    all-NaN corruption mode)."""
    def leaf(p0, p):
        shape = (-1,) + (1,) * (p.ndim - 1)
        s = scale.reshape(shape).astype(jnp.float32)
        clean = (scale == 1.0).reshape(shape)
        p0f, pf = p0.astype(jnp.float32), p.astype(jnp.float32)
        return jnp.where(clean, pf, p0f + s * (pf - p0f)).astype(p.dtype)

    return jax.tree_util.tree_map(leaf, ref, new)


def _screen_members(ref, new):
    """Per-member screen pass over the stacked (K, D) update matrix:
    ``(finite, norm)`` with ``norm[k]`` the float32 L2 norm of member
    k's update delta and ``finite[k]`` its finiteness verdict
    (``isfinite(norm)`` — NaN/Inf anywhere in the delta, or a square
    sum overflowing float32, both trip it).  Threshold comparison is
    deliberately NOT here: it happens on the host against runtime
    config scalars, so one compiled program serves screening on/off and
    every threshold.  ``repro.core.screening.screen_update`` is the
    legacy loops' host-side mirror (same leaf-order accumulation)."""
    with jax.named_scope("screen"):
        sq = None
        for p0, p in zip(jax.tree_util.tree_leaves(ref),
                         jax.tree_util.tree_leaves(new)):
            d = p.astype(jnp.float32) - p0.astype(jnp.float32)
            part = jnp.sum(d * d, axis=tuple(range(1, d.ndim)))    # (K,)
            sq = part if sq is None else sq + part
        norm = jnp.sqrt(sq)
        return jnp.isfinite(norm), norm


def _tree_where_members(live, new, old):
    """Per-member select over stacked (K, ...) trees: ``live`` is (K,)."""
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(live.reshape((-1,) + (1,) * (n.ndim - 1)),
                               n, o), new, old)


def _unflatten_members(mat, template):
    """(K, D) flat member vectors -> stacked tree with leaves (K, ...)
    shaped/typed like ``template`` (a single member's tree)."""
    leaves, treedef = jax.tree_util.tree_flatten(template)
    out, off = [], 0
    for l in leaves:
        out.append(mat[:, off:off + l.size]
                   .reshape((mat.shape[0],) + l.shape).astype(l.dtype))
        off += l.size
    return jax.tree_util.tree_unflatten(treedef, out)


def constrain_tree(tree, client_shardings):
    """Apply the shardings to every leaf: a callable rule (CohortSharding)
    maps each leaf's shape to its sharding; a raw pytree of shardings is
    zipped leaf-wise; None is a no-op.  The ONE place constraint
    application lives — the cohort step and the arena helpers both call
    it."""
    if client_shardings is None:
        return tree
    if callable(client_shardings):
        return jax.tree_util.tree_map(
            lambda l: jax.lax.with_sharding_constraint(
                l, client_shardings(l)), tree)
    return jax.tree_util.tree_map(
        jax.lax.with_sharding_constraint, tree, client_shardings)


def make_cohort_step(loss_fn: Callable, dp_cfg: DPConfig, opt,
                     use_dp: bool = True, dp_path: str = "jnp",
                     client_axis: str = "unroll", client_shardings=None,
                     fl_cfg=None, arena: bool = False,
                     donate_globals: bool = False, donate: bool = True,
                     add_noise: bool = True):
    """Build the jitted cohort program.

    Returns ``(cohort_step, merge_cohort)``.  With ``arena=False`` (the
    host-fed data path, kept as the PR-2 comparison baseline):

    ``cohort_step(stacked_params, stacked_opt, batches, keys, n_steps)``
    where every input has a leading cohort axis K:

      stacked_params: pytree, leaves (K, ...)
      stacked_opt:    pytree of optimizer state, leaves (K, ...)
      batches:        pytree, leaves (K, S_max, B, ...)
      keys:           (K, 2) uint32 dispatch keys
      n_steps:        (K,) int32 — member i executes its first n_steps[i]
                      loop iterations; the rest are masked no-ops

    With ``arena=True`` (the device-resident data path, the engine's
    default) the per-cohort inputs are a few KB of int32 indices instead
    of stacked state and batch tensors:

    ``cohort_step(arena_params, arena_opt, arena_data, slots, data_slots,
    batch_idx, keys, n_steps)`` where the arenas hold the resident
    clients' state/data on a leading slot axis (a slot per RESIDENT
    client plus a spare pad slot — all N clients on the all-resident
    layout, the ``StoreConfig.hot_slots`` hot set under the tiered
    store):

      arena_params: pytree, leaves (A, ...) — per-slot dispatch params
      arena_opt:    pytree, leaves (A, ...) — per-slot optimizer state
                    (DONATED: scatter-updated in place each cohort)
      arena_data:   pytree, leaves (A_d, n_max, ...) — every DISTINCT
                    dataset, uploaded once at runner construction and
                    keyed separately from client state (A_d never
                    shrinks to the hot set; see ``statestore.DataArena``)
      slots:        (K,) int32 — STATE arena slot of each cohort member
                    (padded mask members point at the spare slot)
      data_slots:   (K,) int32 — DATA arena row of each member (equal to
                    ``slots`` values on the legacy all-resident layout)
      batch_idx:    (K, S_max, B) int32 minibatch plan, gathered from
                    ``arena_data`` INSIDE the compiled program

    Cohort assembly is then one fused ``jnp.take`` over the slot axis and
    write-back one scatter — no per-member Python stacking, no batch
    tensors over H2D.

    ``merge_cohort(global_params, stacked_uploads, coeffs, g_coeff)``
    computes ``g_coeff * g + sum_i coeffs[i] * upload_i`` as one weighted
    reduction over the client axis (the ``weights``-vector aggregation of
    ``fl_train_step``, here carrying alpha/(1+tau) staleness weights or
    FedAvg's n_k / sum n).  With ``donate_globals`` its ``global_params``
    argument is donated — the async inner loop re-merges every cohort and
    never reuses the old globals.  Only safe when nothing else aliases the
    globals buffer: the CohortRunner enables it on the arena path (plans
    carry slot ids, not params0 snapshots) for populations without
    personalized clients (whose ``_personal`` / ``personal_snapshot``
    subtrees alias received globals across merges).

    ``client_shardings`` may be a pytree of NamedShardings congruent with
    the stacked params (legacy form, host path only) or a callable
    ``leaf -> sharding`` applied to EVERY stacked input — params,
    optimizer state and batches — at trace time
    (``engine.mesh_backend.CohortSharding``; being shape-aware it can
    partition the divisible leading dims and replicate the rest).
    ``fl_cfg`` (an ``FLStepConfig``) is required by the ``"fl_step"``
    executor and ignored by the others.

    ``dp_path`` selects the per-example DP implementation: ``"jnp"``
    (reference) or ``"pallas"`` — the fused clip+mean+noise kernel run
    STEP-MAJOR, one launch over the whole cohort's stacked (K*B, D)
    per-example grad matrix per local step (see the module docstring).
    Incompatible with ``client_axis="fl_step"`` (per-microbatch
    mechanism); ignored when ``use_dp=False``.

    Both data-path variants additionally take a trailing ``corrupt_scale``
    argument — a (K_pad,) float32 runtime vector of per-member transit-
    corruption scales (1.0 = clean sentinel, NaN = all-NaN payload, any
    other value = update-delta blowup; see ``repro.core.faults``) — and
    return a third output ``screen = (finite, norm)``: the per-member
    finite verdict and float32 update-delta L2 norm of the (possibly
    corrupted) upload payload, computed INSIDE the compiled program over
    the stacked (K, D) update matrix.  Threshold comparison against
    ``ScreeningConfig`` happens on the host, so screening on/off, every
    threshold and every corruption rate replay ONE compiled program
    (``step_builds`` delta 0 — the same invariant the runtime sigma
    preserves).

    Both data-path variants take a trailing ``noise_stddev`` argument — a
    runtime float32 scalar carrying the DP noise scale ``sigma * C / B``
    (computed ON THE HOST by the runner so it rounds to the same float32
    the statically-folded legacy constant does).  ``dp_cfg``'s own
    ``noise_multiplier`` is therefore IGNORED by the built program, and
    :func:`cached_cohort_step` strips it from the cache key: every point
    of a sigma sweep replays ONE compiled program instead of re-tracing
    per sigma (the Session sweep-amortization win).  The ``"fl_step"``
    executor is the exception — its noise is baked into ``fl_cfg.dp``
    (the production mechanism), so ``fl_cfg`` stays in the key unstripped.
    ``add_noise=False`` builds the STATICALLY noise-free variant for
    sigma == 0 runs (clipping still applies): a traced zero scale would
    defeat ``noise_tree``'s short-circuit and sample a full Gaussian tree
    per step just to multiply it away — the runner picks the variant from
    the clients' sigma, so only the noisy points of a sweep share the
    runtime-scale program.

    The cohort step itself is ALWAYS donation-free (see the arena-path
    comment: on jax 0.4.37 the XLA:CPU thunk runtime recycles donated
    step inputs while the PR-9 screen/corrupt epilogue still reads
    pre-scatter state, corrupting the returned uploads).  ``donate``
    now gates only the remaining donations outside the step —
    ``donate_globals`` on the fused merge and the arena write helpers;
    ``donate=False`` disables those too.  Donation is a throughput win
    on the strictly serial driver, but a donated-input dispatch BLOCKS
    the host until the computation finishes (measured on jax 0.4 CPU: a
    donation-chained loop runs fully synchronously while the identical
    non-donated chain dispatches asynchronously) — the engine's
    pipelined scheduler (``EngineConfig.pipeline_depth >= 2``)
    therefore runs every donation off so host planning can overlap
    device compute.
    """
    global _STEP_BUILDS
    _STEP_BUILDS += 1
    # the built program NEVER reads the static sigma (noise is the
    # runtime argument, or statically off with add_noise=False) — strip
    # it here too so direct callers get the same program the cache hands
    # out for every sigma
    dp_cfg = replace(dp_cfg, noise_multiplier=0.0)
    validate_client_axis(client_axis)
    validate_dp_path(dp_path)
    if client_axis == "fl_step" and fl_cfg is None:
        raise ValueError(
            "client_axis='fl_step' drives the production local round and "
            "needs an FLStepConfig (EngineConfig.fl_cfg / fl_cfg=)")
    if client_axis == "fl_step" and dp_path == "pallas":
        raise ValueError(
            "dp_path='pallas' fuses the PER-EXAMPLE clip+noise mechanism "
            "(paper Eq. 4-6); client_axis='fl_step' runs the per-microbatch "
            "production mechanism from fl_cfg.dp — use dp_path='jnp' there")
    fused_dp = bool(use_dp and dp_path == "pallas")
    if arena and client_shardings is not None and not callable(client_shardings):
        raise ValueError(
            "the arena data path needs a shape-aware callable shardings "
            "rule (engine.mesh_backend.CohortSharding) or None — a raw "
            "pytree of NamedShardings is congruent with one cohort stack, "
            "not with the (A, ...) arenas")

    def constrain(tree):
        return constrain_tree(tree, client_shardings)

    def one_step(params, opt_state, batch, key, noise_stddev):
        """Identical math to the legacy ``_dp_sgd_step`` / ``_sgd_step``
        (``noise_stddev`` carries the host-rounded sigma*C/B scalar)."""
        if use_dp:
            # add_noise=False: fall back to the (sigma-stripped) static
            # config — a concrete 0.0 stddev short-circuits noise_tree
            grad, _aux = dp_mean_gradient(
                loss_fn, params, batch, key, dp_cfg,
                noise_stddev=noise_stddev if add_noise else None)
        else:
            grad = jax.grad(
                lambda p: jnp.mean(
                    jax.vmap(lambda ex: loss_fn(p, ex))(batch)))(params)
        with jax.named_scope("optimizer"):
            return opt.update(grad, opt_state, params)

    def local_phase(params, opt_state, key, batches, n_steps, noise_stddev):
        """One member's whole local round, fused across minibatch steps."""
        s_max = jax.tree_util.tree_leaves(batches)[0].shape[0]

        def apply_masked(p, o, k, step_i, batch):
            live = step_i < n_steps
            k_next, sub = jax.random.split(k)
            p_new, o_new = one_step(p, o, batch, sub, noise_stddev)
            return (_tree_where(live, p_new, p),
                    _tree_where(live, o_new, o),
                    jnp.where(live, k_next, k))

        if s_max <= _MAX_FULL_UNROLL:
            # flat step loop: measured ~1.5x faster than the same body
            # under a lax.scan/lax.map while loop on XLA CPU
            p, o, k = params, opt_state, key
            for s in range(s_max):
                batch = jax.tree_util.tree_map(lambda l: l[s], batches)
                p, o, k = apply_masked(p, o, k, s, batch)
            return p, o

        def body(carry, inp):
            step_i, batch = inp
            return apply_masked(*carry, step_i, batch), None

        (p, o, _), _ = jax.lax.scan(
            body, (params, opt_state, key), (jnp.arange(s_max), batches))
        return p, o

    if client_axis == "fl_step":
        from repro.core.fl_step import make_local_phase

        def batch_mean_loss(p, mb):
            # the engine's loss is per-example; fl_step's local phase
            # consumes a batch-mean loss (production loss signature)
            return jnp.mean(jax.vmap(lambda ex: loss_fn(p, ex))(mb))

        fl_local = make_local_phase(batch_mean_loss, fl_cfg)

        def fl_member_phase(params, opt_state, key, member_batches, steps):
            def to_micro(l):
                s, b = l.shape[0], l.shape[1]
                if b % fl_cfg.n_micro:
                    raise ValueError(
                        f"cohort batch size {b} is not divisible by "
                        f"fl_cfg.n_micro={fl_cfg.n_micro}")
                return l.reshape((s, fl_cfg.n_micro, b // fl_cfg.n_micro)
                                 + l.shape[2:])

            micro = jax.tree_util.tree_map(to_micro, member_batches)
            # production semantics: plain local_lr SGD inside the round —
            # the client optimizer state passes through untouched (the
            # server-side merge is the engine's weights-vector reduction)
            return fl_local(params, micro, key, n_steps=steps), opt_state

    if fused_dp:
        from repro.kernels.dp_clip.ops import dp_clip_mean_noise_cohort
        from repro.pytree import tree_gaussian_vector_like

        if client_shardings is not None and not callable(client_shardings):
            raise ValueError(
                "dp_path='pallas' on a mesh needs a shape-aware callable "
                "shardings rule (engine.mesh_backend.CohortSharding): the "
                "kernel is partitioned per member along the cohort axis")

        def clip_mean_noise(flat, *noise):
            """(K, B, D) per-example grads [+ the runtime stddev and (K, D)
            draws] -> (K, D) clipped (noised) member means."""
            return dp_clip_mean_noise_cohort(flat, dp_cfg.clip_norm, *noise)[0]

        def kernel_members(flat, *noise):
            """The kernel over the cohort, partitioned per member on a
            mesh: a Mosaic kernel cannot be partitioned by GSPMD, so it
            runs under ``shard_map`` with each device taking its own
            members (the kernel grid is already per member).  A cohort
            whose rule replicates it is refused rather than run
            replicated on every device."""
            sh = None if client_shardings is None else client_shardings(flat)
            if sh is None or sh.mesh.size == 1:
                return clip_mean_noise(flat, *noise)
            lead = sh.spec[0] if len(sh.spec) else None
            if lead is None:
                raise ValueError(
                    f"dp_path='pallas': a cohort of {flat.shape[0]} members "
                    f"does not partition over the mesh {dict(sh.mesh.shape)};"
                    " use the device-arena data path, which pads cohorts "
                    "to a multiple of the data axes")
            specs = (P(lead), P(), P(lead))[:1 + len(noise)]
            return jax.shard_map(
                clip_mean_noise, mesh=sh.mesh, in_specs=specs,
                out_specs=P(lead), check_vma=False)(flat, *noise)

        def fused_one_step(stacked_params, stacked_opt, ks, batch_s,
                           step_i, n_steps, noise_stddev):
            """All K members' DP-SGD step s, ONE fused kernel launch over
            the stacked (K*B, D) per-example grad matrix.  Per-member math
            (clip scales, mean, noise draws keyed off the same
            ``split(key)`` chain) is identical to ``one_step``'s
            ``dp_mean_gradient`` — only the launch granularity changes."""
            live = step_i < n_steps                       # (K,)
            splits = jax.vmap(jax.random.split)(ks)       # (K, 2, key)
            k_next, subs = splits[:, 0], splits[:, 1]
            with jax.named_scope("dp_grads"):
                g_per = jax.vmap(
                    lambda p, b: per_example_grads(loss_fn, p, b))(
                        stacked_params, batch_s)          # leaves (K, B, ...)
            with jax.named_scope("dp_clip_noise"):
                leaves = jax.tree_util.tree_leaves(g_per)
                K, bsz = leaves[0].shape[0], leaves[0].shape[1]
                flat = jnp.concatenate(
                    [l.reshape(K, bsz, -1).astype(jnp.float32)
                     for l in leaves], axis=2)            # (K, B, D)
                template = jax.tree_util.tree_map(lambda l: l[0, 0], g_per)
                noise = ()
                if add_noise:
                    noise = (noise_stddev, jax.vmap(
                        lambda k: tree_gaussian_vector_like(k, template))(
                            subs))
                means = kernel_members(flat, *noise)
                grads = _unflatten_members(means, template)
            with jax.named_scope("optimizer"):
                p_new, o_new = jax.vmap(opt.update)(
                    grads, stacked_opt, stacked_params)
            return (_tree_where_members(live, p_new, stacked_params),
                    _tree_where_members(live, o_new, stacked_opt),
                    jnp.where(live[:, None], k_next, ks))

        def run_members_fused(stacked_params, stacked_opt, keys, batches,
                              n_steps, noise_stddev):
            """Step-major executor for the pallas DP path: the local-step
            loop is OUTSIDE the member axis so every iteration is one
            cohort-wide kernel launch (batches leaves are (K, S_max, B,
            ...))."""
            s_max = jax.tree_util.tree_leaves(batches)[0].shape[1]
            if s_max <= _MAX_FULL_UNROLL:
                p, o, k = stacked_params, stacked_opt, keys
                for s in range(s_max):
                    batch_s = jax.tree_util.tree_map(
                        lambda l: l[:, s], batches)
                    p, o, k = fused_one_step(
                        p, o, k, batch_s, s, n_steps, noise_stddev)
                return p, o

            step_major = jax.tree_util.tree_map(
                lambda l: jnp.moveaxis(l, 1, 0), batches)  # (S_max, K, B, ..)

            def body(carry, inp):
                step_i, batch_s = inp
                p, o, k = carry
                return fused_one_step(p, o, k, batch_s, step_i, n_steps,
                                      noise_stddev), None

            (p, o, _), _ = jax.lax.scan(
                body, (stacked_params, stacked_opt, keys),
                (jnp.arange(s_max), step_major))
            return p, o

    def run_members(stacked_params, stacked_opt, keys, batches, n_steps,
                    noise_stddev):
        """The client-axis executor switch over one stacked cohort
        (``noise_stddev`` is shared across members — broadcast, never
        stacked; the fl_step executor ignores it, its noise lives in
        ``fl_cfg.dp``).  ``dp_path="pallas"`` overrides the member-major
        executors with the step-major fused-kernel executor above."""
        if fused_dp:
            return run_members_fused(stacked_params, stacked_opt, keys,
                                     batches, n_steps, noise_stddev)
        if client_axis == "vmap":
            return jax.vmap(local_phase,
                            in_axes=(0, 0, 0, 0, 0, None))(
                stacked_params, stacked_opt, keys, batches, n_steps,
                noise_stddev)
        if client_axis == "fl_step":
            return jax.vmap(fl_member_phase)(
                stacked_params, stacked_opt, keys, batches, n_steps)
        if client_axis == "map":
            return jax.lax.map(
                lambda t: local_phase(*t, noise_stddev),
                (stacked_params, stacked_opt, keys, batches, n_steps))
        # unroll: flat program over the K members
        K = keys.shape[0]
        outs = [
            local_phase(unstack_tree(stacked_params, i),
                        unstack_tree(stacked_opt, i),
                        keys[i],
                        unstack_tree(batches, i),
                        n_steps[i],
                        noise_stddev)
            for i in range(K)
        ]
        return (stack_trees([p for p, _ in outs]),
                stack_trees([o for _, o in outs]))

    if arena:
        # the opt arena used to be donated and scatter-updated in place
        # (input/output leaves share shape/dtype and sharding rule, so
        # the alias materialized even on a mesh) — but with the PR-9
        # screen/corrupt epilogue adding reduction outputs to the step,
        # XLA:CPU's thunk runtime recycles the donated buffers while the
        # epilogue still reads pre-scatter state, and the step returns
        # garbage (observed on jax 0.4.37: NaN uploads in a fault-free
        # run; data-dependency fences don't help because the bug is in
        # buffer liveness, not op ordering).  The step is therefore
        # donation-free on every path — the same program shape the
        # pipelined scheduler always required (see audit_donation) — at
        # the cost of one opt-arena copy per serial-path cohort.
        @jax.jit
        def cohort_step(arena_params, arena_opt, arena_data, slots,
                        data_slots, batch_idx, keys, n_steps, noise_stddev,
                        corrupt_scale):
            def take(tree):
                return jax.tree_util.tree_map(
                    lambda l: jnp.take(l, slots, axis=0), tree)

            stacked_params = constrain(take(arena_params))
            stacked_opt = constrain(take(arena_opt))
            # in-step batch gather: (A_d, n_max, ...)[dslot, idx] -> the
            # (K, S_max, B, ...) batch stack, computed on device from the
            # resident datasets (only the index plan crossed H2D).  The
            # dataset arena has its OWN slot map: state slots are hot-set
            # rows under the tiered store while data rows stay resident
            # per distinct dataset (deduped), so the two index spaces
            # only coincide on the legacy all-resident layout.
            batches = constrain(jax.tree_util.tree_map(
                lambda l: l[data_slots[:, None, None], batch_idx],
                arena_data))
            new_params, new_opt = run_members(
                stacked_params, stacked_opt, keys, batches, n_steps,
                noise_stddev)
            # write-back scatter: pad members target the spare slot with
            # their (masked, unchanged) gathered state, so duplicate
            # indices only ever carry identical values.  The scatter
            # takes the HONEST post-training state: transit corruption
            # below touches only the returned upload payload, never the
            # client's own arena slot.
            new_arena_opt = constrain(jax.tree_util.tree_map(
                lambda a, n: a.at[slots].set(n), arena_opt, new_opt))
            upload = constrain(_corrupt_members(
                stacked_params, new_params, corrupt_scale))
            screen = constrain(_screen_members(stacked_params, upload))
            return upload, new_arena_opt, screen
    else:
        # donation-free like the arena path (the cohort-stack inputs
        # never aliased under mesh shardings anyway — replicated in,
        # partitioned out — and the single-device alias trips the same
        # XLA:CPU thunk-runtime buffer recycling described above)
        @jax.jit
        def cohort_step(stacked_params, stacked_opt, batches, keys, n_steps,
                        noise_stddev, corrupt_scale):
            stacked_params = constrain(stacked_params)
            if callable(client_shardings):
                stacked_opt = constrain(stacked_opt)
                batches = constrain(batches)
            new_params, new_opt = run_members(
                stacked_params, stacked_opt, keys, batches, n_steps,
                noise_stddev)
            upload = constrain(_corrupt_members(
                stacked_params, new_params, corrupt_scale))
            screen = constrain(_screen_members(stacked_params, upload))
            return upload, new_opt, screen

    # every merge replaces the globals, so donating kills the one
    # full-model re-allocation in the async inner loop — but only when the
    # runner proved nothing aliases the buffer (see docstring)
    merge_kw = {"donate_argnums": (0,)} if donate_globals and donate else {}

    @functools.partial(jax.jit, **merge_kw)
    def merge_cohort(global_params, stacked_uploads, coeffs, g_coeff):
        coeffs = coeffs.astype(jnp.float32)

        def leaf(g, s):
            sf = s.astype(jnp.float32)
            # a zero-coefficient member must contribute EXACTLY nothing
            # even when its payload is nonfinite (a screened-out corrupt
            # upload, PR 9): 0 * NaN = NaN would poison the reduction
            mask = (coeffs != 0.0).reshape((-1,) + (1,) * (sf.ndim - 1))
            sf = jnp.where(mask, sf, 0.0)
            return (g_coeff * g.astype(jnp.float32)
                    + jnp.tensordot(coeffs, sf, axes=(0, 0))).astype(g.dtype)

        return jax.tree_util.tree_map(leaf, global_params, stacked_uploads)

    return cohort_step, merge_cohort


# ---------------------------------------------------------------------------
# cross-run compile cache: repeated runs over the same testbed (benchmark
# sweeps, parity tests) reuse the compiled programs instead of re-tracing
# ---------------------------------------------------------------------------

_STEP_CACHE: dict = {}


def _hashable_loss(loss_fn):
    """Normalize functools.partial losses so two testbeds built from the
    same model config share one compiled step."""
    if isinstance(loss_fn, functools.partial):
        try:
            key = (loss_fn.func, loss_fn.args,
                   tuple(sorted(loss_fn.keywords.items())))
            hash(key)
            return key
        except TypeError:
            pass
    return loss_fn


_UNCACHEABLE = object()  # sentinel: shardings we cannot turn into a key


def _shardings_key(client_shardings):
    """Hashable cache key for the shardings argument.  ``CohortSharding``
    hashes by (mesh, arch_cfg); a raw pytree of NamedShardings flattens to
    (treedef, leaves); anything unhashable disables caching for that call
    only (returns the _UNCACHEABLE sentinel, never None — None means "no
    shardings" and is a perfectly cacheable key)."""
    if client_shardings is None:
        return None
    try:
        hash(client_shardings)
        return client_shardings
    except TypeError:
        pass
    try:
        leaves, treedef = jax.tree_util.tree_flatten(client_shardings)
        key = (treedef, tuple(leaves))
        hash(key)
        return key
    except TypeError:
        return _UNCACHEABLE


def cached_cohort_step(loss_fn, dp_cfg, opt, use_dp=True, dp_path="jnp",
                       client_axis="unroll", client_shardings=None,
                       fl_cfg=None, arena=False, donate_globals=False,
                       donate=True, add_noise=True):
    """Memoized :func:`make_cohort_step`, keyed per (training config,
    executor, data path, shardings/mesh): scenario sweeps over the same
    testbed AND mesh reuse the compiled programs instead of re-tracing
    every run.  Supplying shardings no longer bypasses the cache —
    mesh-lifetime entries are dropped explicitly with
    :func:`invalidate_step_cache`.  The cache only ever holds the compiled
    step FUNCTIONS; arenas are per-runner arguments, never closed over, so
    dropping a runner frees its device buffers regardless of the cache.

    ``dp_cfg.noise_multiplier`` is STRIPPED from both the key and the
    built program: the noise scale is a runtime argument of the compiled
    step, so every sigma of a noise sweep shares one entry (the
    ``"fl_step"`` executor's noise lives in ``fl_cfg``, which stays in
    the key)."""
    dp_cfg = replace(dp_cfg, noise_multiplier=0.0)

    def build():
        return make_cohort_step(
            loss_fn, dp_cfg, opt, use_dp=use_dp, dp_path=dp_path,
            client_axis=client_axis, client_shardings=client_shardings,
            fl_cfg=fl_cfg, arena=arena, donate_globals=donate_globals,
            donate=donate, add_noise=add_noise)

    sh_key = _shardings_key(client_shardings)
    if sh_key is _UNCACHEABLE:
        return build()
    key = (_hashable_loss(loss_fn), dp_cfg, opt, use_dp, dp_path,
           client_axis, fl_cfg, sh_key, arena, donate_globals, donate,
           add_noise)
    try:
        hash(key)
    except TypeError:
        return build()
    if key not in _STEP_CACHE:
        _STEP_CACHE[key] = build()
    return _STEP_CACHE[key]


def cached_arena_helpers(arena_slots: int, opt, client_shardings,
                         donate: bool = True):
    """Compiled arena plumbing — ``(init, write, gather, write_rows,
    init_opt)`` over the (A, ...) client-state arenas — shared across
    CohortRunners and stored in the SAME cache as the compiled steps, so
    :func:`invalidate_step_cache` drops a mesh's helper entries alongside
    its step entries (the documented mesh-lifetime cleanup covers both).
    The arenas themselves are call arguments, never closed over: the
    cache holds compiled functions only, no device buffers.
    ``donate=False`` keeps the writers out-of-place (the pipelined
    scheduler needs async dispatch; donated inputs block it — see
    :func:`make_cohort_step`).

    ``write_rows``/``init_opt`` serve the tiered store's hot-set churn:
    ``write_rows(arena, rows, slots)`` scatters pre-stacked per-slot
    rows (cold-store reloads — leaves (k, ...)); ``init_opt(arena_opt,
    p, slots)`` re-initializes slots' optimizer rows in place from a
    params tree (``opt.init`` is value-independent, so a re-initialized
    slot is bitwise the row a fresh all-resident arena would hold)."""

    def build():
        def constrain(tree):
            return constrain_tree(tree, client_shardings)

        @jax.jit
        def init(p):
            stacked = jax.tree_util.tree_map(
                lambda l: jnp.broadcast_to(
                    l[None], (arena_slots,) + l.shape), p)
            return constrain(stacked), constrain(jax.vmap(opt.init)(stacked))

        @functools.partial(
            jax.jit, **({"donate_argnums": (0,)} if donate else {}))
        def write(arena, p, slots):
            return constrain(jax.tree_util.tree_map(
                lambda a, l: a.at[slots].set(
                    jnp.broadcast_to(l[None].astype(a.dtype),
                                     (slots.shape[0],) + l.shape)),
                arena, p))

        @jax.jit
        def gather(arena, slots):
            return jax.tree_util.tree_map(
                lambda l: jnp.take(l, slots, axis=0), arena)

        @functools.partial(
            jax.jit, **({"donate_argnums": (0,)} if donate else {}))
        def write_rows(arena, rows, slots):
            return constrain(jax.tree_util.tree_map(
                lambda a, r: a.at[slots].set(r.astype(a.dtype)),
                arena, rows))

        @functools.partial(
            jax.jit, **({"donate_argnums": (0,)} if donate else {}))
        def init_opt(arena_opt, p, slots):
            fresh = opt.init(p)
            return constrain(jax.tree_util.tree_map(
                lambda a, l: a.at[slots].set(
                    jnp.broadcast_to(l[None].astype(a.dtype),
                                     (slots.shape[0],) + l.shape)),
                arena_opt, fresh))

        return init, write, gather, write_rows, init_opt

    sh_key = _shardings_key(client_shardings)
    if sh_key is _UNCACHEABLE:
        return build()
    key = ("arena_helpers", arena_slots, opt, sh_key, donate)
    try:
        hash(key)
    except TypeError:
        return build()
    if key not in _STEP_CACHE:
        _STEP_CACHE[key] = build()
    return _STEP_CACHE[key]


def _mentions_mesh(obj, mesh) -> bool:
    if isinstance(obj, tuple):
        return any(_mentions_mesh(o, mesh) for o in obj)
    return getattr(obj, "mesh", None) == mesh


def invalidate_step_cache(mesh=None) -> int:
    """Explicitly drop cached compiled cohort steps.

    With ``mesh``, drop only entries whose shardings were built for that
    mesh (call it when a mesh's devices go away, or between sweeps that
    rebuild meshes); with no argument, clear everything.  Returns the
    number of entries dropped.
    """
    if mesh is None:
        n = len(_STEP_CACHE)
        _STEP_CACHE.clear()
        return n
    drop = [k for k in _STEP_CACHE if _mentions_mesh(k, mesh)]
    for k in drop:
        del _STEP_CACHE[k]
    return len(drop)


def stack_trees(trees):
    """Stack a list of identically-shaped pytrees on a new leading axis."""
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *trees)


def unstack_tree(tree, i: int):
    """Member ``i``'s slice of a stacked pytree."""
    return jax.tree_util.tree_map(lambda l: l[i], tree)
