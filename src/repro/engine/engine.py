"""Cohort-batched async execution engine (event-queue driver).

Drives the compiled cohort step (``repro.engine.cohort_step``) from the
virtual-clock priority queue that the legacy per-client loop in
``repro.core.server`` uses:

  1. **dispatch**: a client pulls the current globals, its minibatch
     schedule / PRNG chain / tier-clock duration / accountant step are
     planned on the host (``LocalRoundPlan``) and its completion event is
     pushed on the heap — exactly the bookkeeping ``Client.local_train``
     does, but WITHOUT running the training yet;
  2. **cohort pop**: all completions within ``staleness_window`` virtual
     seconds of the earliest pending event come off the heap as one cohort;
  3. **compiled local phase**: the cohort runs as ONE jitted program.
     On the default device-resident data path the members' dispatch-time
     params / optimizer states are GATHERED inside the program from a
     per-client arena (dispatch wrote the pulled globals into the
     member's slot), minibatches are gathered from the once-uploaded
     per-client datasets by a (K, S_max, B) int32 index plan — the only
     per-cohort H2D traffic — and cohorts pad to bucket sizes that always
     partition on a mesh (pad members are zero-step masked and merge with
     coefficient 0);
  4. **merge**: FedAvg/FedAsync weights (n_k / sum n, alpha/(1+tau_i))
     are folded into a single weights-vector reduction over the client
     axis (``fold_cohort_weights`` makes the fused merge exactly equal to
     the legacy sequential merges); FedBuff / AdaptiveAsync / personalized
     clients route per-member through ``aggregation.apply_update`` — the
     same switch the legacy loop uses;
  5. **bookkeeping**: staleness, per-tier update counts, epsilon
     trajectories and influence land in the same ``RunLog`` the legacy
     loops produce, so every benchmark/figure works unchanged.

With ``staleness_window=0`` cohorts have size 1 and the engine reproduces
the legacy event loop update-for-update (the tier-1 parity tests assert
params allclose and identical update-count/epsilon bookkeeping).  A
positive window trades a bounded amount of merge reordering for wide
cohorts and is where the throughput win comes from (see
``benchmarks/fl_benchmarks.py::bench_engine_throughput``).

Pipelined scheduling (``EngineConfig.pipeline_depth``):

  Every quantity a cohort needs is deterministic at dispatch time (the
  virtual clock, the minibatch permutations and the PRNG chain are host
  state), so the host can assemble cohort *t+1* while cohort *t* still
  executes on device.  What breaks that overlap on the serial driver is
  buffer donation: a donated-input dispatch blocks the host until the
  computation finishes (measured: a donation-chained loop on jax CPU
  runs fully synchronously), and the PR-3 data path donates the opt
  arena, the params-arena writes and the merged globals — every cohort
  is a full host<->device sync.  With ``pipeline_depth >= 2`` the runner
  builds donation-free programs and the loops split into submit/drain:

      host   │ plan t   plan t+1   plan t+2        drain/eval
             │ stage t  stage t+1  stage t+2  ...  (the ONLY host
             │ submit t submit t+1 submit t+2       blocks)
      ───────┼────────────────────────────────────────────────────
      device │          step t ──► step t+1 ──► step t+2
             │           merge t ──► merge t+1 ──► ...

  *plan* (pop_cohort, batch plans, memoized accountant, clock/heap) and
  *stage* (the few-KB int32/key uploads via async device_put) run ahead
  of the device; *submit* enqueues the compiled step + merge without
  waiting.  At most ``pipeline_depth`` cohorts are in flight — beyond
  that the loop drains the OLDEST cohort's outputs (backpressure, no
  device->host transfer).  The host genuinely blocks only at eval
  boundaries, ``target_acc`` checks and end of run; ``RunLog`` is
  bit-identical to the serial path because every bookkeeping scalar
  (merge weight, staleness tau, epsilon, influence increment) is packed
  per cohort from host-deterministic plan state, never fetched from
  device.  ``RunLog.engine_stats`` reports the sync counters
  (``host_syncs_between_evals`` is 0 on the pipelined path;
  ``blocking_submits`` counts the serial path's donation syncs).
"""
from __future__ import annotations

import functools
import heapq
from collections import deque
from dataclasses import dataclass, replace
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import accountant as _accountant
from repro.core import spans
from repro.core.aggregation import (
    AdaptiveAsync, FedAsync, FedAvg, FedBuff, apply_update)
from repro.core.faults import FaultInjector, apply_deadline, zero_fault_stats
from repro.core.runlog import RunLog, eval_all, validate_engine_stats
from repro.core.screening import ScreeningState, zero_screen_stats
from repro.engine.cohort import (
    LocalRoundPlan, fedavg_weights, fold_cohort_weights, padded_cohort_size,
    plan_batches, pop_cohort, steps_per_round)
from repro.engine.cohort_step import (
    cached_arena_helpers, cached_cohort_step, stack_trees, unstack_tree,
    validate_client_axis)
from repro.engine.mesh_backend import CohortSharding
from repro.engine.statestore import (
    DataArena, StoreConfig, TieredStateStore, zero_store_stats)


@dataclass(frozen=True)
class EngineConfig:
    staleness_window: float = 0.0  # virtual seconds of completions per cohort
    max_cohort: int = 2            # cap on POPPED cohort size ("unroll" compile
                                   # time scales with it; see cohort_step) — on
                                   # a mesh the arena path pads the compiled
                                   # leading dim up to the next bucket that
                                   # divides the data-axis product
    fused_merge: bool = True       # fold FedAvg/FedAsync into the weights vector
    delta: float = 1e-5            # accountant delta (matches legacy loop)
    client_axis: str = "unroll"    # unroll (single CPU) | map | vmap (mesh,
                                   # sim math) | fl_step (mesh, production
                                   # per-microbatch-DP round) — see cohort_step
    pow2_cohorts: bool = True      # bucket cohort sizes to bound recompiles
    mesh: Optional[object] = None  # jax Mesh: partition the cohort axis over
                                   # its data axes (engine.mesh_backend builds
                                   # the CohortSharding); None = replicated
    fl_cfg: Optional[object] = None  # FLStepConfig for client_axis="fl_step"
    device_arena: bool = True      # device-resident data path: client
                                   # params/opt state live in a stacked arena,
                                   # datasets upload once, cohorts assemble as
                                   # a compiled gather fed by index plans only
                                   # (False = PR-2 host-fed baseline)
    pipeline_depth: int = 1        # cohorts in flight: 1 = the serial driver
                                   # (donation-chained, each submit blocks);
                                   # >= 2 = pipelined submit/drain — host
                                   # planning/staging overlaps device compute,
                                   # donation off so dispatch is async (see
                                   # module docstring pipeline diagram)
    store: StoreConfig = StoreConfig()  # tiered client-state store (see
                                   # repro.engine.statestore / STORE.md):
                                   # hot_slots=None keeps the all-resident
                                   # arena; a positive hot_slots bounds the
                                   # device arena to that many client rows
                                   # backed by a host cold store with
                                   # event-heap lookahead prefetch

    def __post_init__(self):
        validate_client_axis(self.client_axis)
        if int(self.pipeline_depth) < 1 or self.pipeline_depth != int(
                self.pipeline_depth):
            raise ValueError(
                f"pipeline_depth must be an integer >= 1: "
                f"{self.pipeline_depth!r}")
        if self.store.hot_slots is not None:
            if not self.device_arena:
                raise ValueError(
                    "StoreConfig.hot_slots requires device_arena=True — "
                    "the host data path has no device arena to bound")
            if self.store.hot_slots < self.max_cohort:
                raise ValueError(
                    f"StoreConfig.hot_slots={self.store.hot_slots} must be "
                    f">= max_cohort={self.max_cohort}: a staged cohort pins "
                    "one hot slot per member, so a smaller hot set "
                    "deadlocks slot acquisition")


def _resolve_mesh_cfg(cfg: EngineConfig, mesh) -> EngineConfig:
    """Fold a frontend-supplied mesh into the engine config (an explicit
    EngineConfig.mesh wins over the run_experiment/run_* keyword)."""
    if mesh is not None and cfg.mesh is None:
        cfg = replace(cfg, mesh=mesh)
    return cfg


def _host_fetch(runner, value) -> float:
    """The funnel for the engine loops' direct device->host scalar reads
    (the global-accuracy eval; ``eval_all``'s per-client fetches happen
    inside the same eval boundary but route through the shared
    ``Client.evaluate``).  Fetches — and the serial driver's
    donation-blocked submits, counted at the submit site — feed the
    runner's sync counters, and the pipelined-path acceptance criterion
    is that the between-evals count stays ZERO (the sync-count parity
    test monkeypatches this function to prove the fetch side)."""
    out = float(value)
    runner.note_host_sync()
    return out


def _host_fetch_array(runner, value):
    """The :func:`_host_fetch` sibling for the engine's ARRAY reads —
    today that is exactly one site, the per-cohort screen-verdict fetch
    (``CohortRunner.fetch_screen``).  Routing the ``device_get`` through
    this funnel keeps the sync accounting honest: the runner buckets the
    fetch into ``screen_verdict_syncs`` (the screening path's sanctioned
    blocking point), so ``host_syncs_between_evals`` stays 0 on the
    pipelined path whether screening is on or off."""
    out = jax.device_get(value)
    runner.note_host_sync()
    return out


@functools.lru_cache(maxsize=None)
def _key_chain_fn(n: int):
    def chain(key):
        def body(k, _):
            ks = jax.random.split(k)
            return ks[0], ks[1]
        return jax.lax.scan(body, key, None, length=n)
    return jax.jit(chain)


def split_key_chain(key, n: int):
    """``n`` sequential ``jax.random.split`` draws as ONE compiled scan.

    Bitwise identical to the Python loop ``key, sub = jax.random.split
    (key)`` repeated ``n`` times (the scan body IS that loop body), but
    O(1) dispatches instead of O(N) — the startup schedule at N=100k was
    dominated by per-client split dispatch overhead.  Returns ``(key,
    subs)`` with ``subs`` a host-side (n, 2) uint32 array so handing
    ``subs[i]`` to each plan costs no per-row device slicing."""
    key, subs = _key_chain_fn(n)(key)
    return key, np.asarray(subs)


@dataclass
class StagedCohort:
    """One cohort's device-ready inputs, assembled (and uploaded) ahead
    of submission: on the arena path a few KB of int32 index plans plus
    the stacked PRNG keys; on the host path the stacked state/batch
    tensors.  Staging cohort t+1 while cohort t executes is the
    'dispatch queue' of the pipelined scheduler — the H2D device_puts
    are async, so building one of these never waits on the device."""

    plans: list
    k: int
    degenerate: bool = False       # s_max == 0: no client has a full batch
    arena: bool = True
    slots: Optional[object] = None       # (K_pad,) int32 on device
    data_slots: Optional[object] = None  # (K_pad,) int32 dataset-arena rows
                                         # (the very `slots` object on the
                                         # all-resident identity layout)
    batch_idx: Optional[object] = None   # (K_pad, S_max, B) int32 on device
    keys: Optional[object] = None        # (K_pad, 2) uint32 on device
    n_steps: Optional[object] = None     # (K_pad,) int32 on device
    corrupt: Optional[object] = None     # (K_pad,) float32 transit-corruption
                                         # scales (1.0 = clean, incl. pads)
    stacked_params: Optional[object] = None  # host path only
    stacked_opt: Optional[object] = None
    batches: Optional[object] = None


class CohortRunner:
    """Owns the compiled cohort program and the host-side plan/IO glue.

    When ``cfg.mesh`` is set (or ``client_shardings`` is passed
    explicitly), the compiled step constrains every stacked input's
    leading cohort dim onto the mesh's data axes — the members of a
    full-size cohort then genuinely run on different devices (see
    :mod:`repro.engine.mesh_backend`).

    With ``cfg.device_arena`` (the default) the per-cohort hot path is
    device-resident end to end: every client's params and optimizer state
    live in one stacked arena (slot per client, sharded by the same
    shape-aware rule), every client's dataset uploads to device ONCE at
    construction, and a cohort is assembled inside the compiled step by a
    ``jnp.take`` over slots plus an in-step batch gather driven by the
    (K, S_max, B) int32 index plan — the only per-cohort H2D traffic.
    Cohorts additionally pad to the bucket size from
    :func:`repro.engine.cohort.padded_cohort_size`, so on a mesh the
    compiled leading dim always divides the data-axis product and the
    cohort ALWAYS partitions (pad members gather a spare slot, run zero
    masked steps and merge with coefficient zero).
    """

    def __init__(self, clients, cfg: EngineConfig,
                 client_shardings=None, data_arena=None):
        c0 = clients[0]
        for c in clients:
            if (c.dp_cfg != c0.dp_cfg or c.use_dp != c0.use_dp
                    or c.dp_path != c0.dp_path or c.opt != c0.opt
                    or c.batch_size != c0.batch_size
                    or not (c.loss_fn is c0.loss_fn
                            or c.loss_fn == c0.loss_fn)):
                raise ValueError(
                    "cohort engine requires homogeneous client training "
                    "configs (heterogeneity lives in the virtual clocks)")
        self.clients = clients
        self.cfg = cfg
        # run-level padded step count: every client's local round length is
        # fixed by (n_train, B, E), so padding all cohorts to the global max
        # keeps the compiled step's shapes constant across the whole run
        self.s_max = max(
            steps_per_round(c.n_train, c.batch_size, c.local_epochs)
            for c in clients)
        if cfg.client_axis == "fl_step" and c0.use_dp:
            # the host-side accountant (dispatch) charges the clients'
            # dp_cfg mechanism: eps depends on (q, sigma, steps) — the
            # sampling rate and step count are the same either way and
            # eps is clip-norm-independent, so the bound transfers to the
            # executed per-microbatch mechanism ONLY when the noise
            # multipliers agree and noise is actually added per step
            fl_dp = cfg.fl_cfg.dp if cfg.fl_cfg is not None else None
            if (fl_dp is None or fl_dp.granularity != "per_microbatch"
                    or fl_dp.noise_multiplier != c0.dp_cfg.noise_multiplier):
                raise ValueError(
                    "client_axis='fl_step' with DP clients requires "
                    "fl_cfg.dp to use granularity='per_microbatch' with the "
                    "same noise_multiplier as the clients' dp_cfg "
                    f"(got {fl_dp!r} vs sigma={c0.dp_cfg.noise_multiplier}) "
                    "— otherwise the reported epsilon does not describe "
                    "the executed mechanism")
        if client_shardings is None and cfg.mesh is not None:
            client_shardings = CohortSharding(cfg.mesh)
        self.client_shardings = client_shardings
        # a raw pytree of per-leaf shardings is congruent with one cohort
        # stack, not with the arenas — fall back to the host data path
        self.use_arena = bool(cfg.device_arena) and (
            client_shardings is None or callable(client_shardings))
        # tiered client-state store (repro.engine.statestore): bound the
        # device arena to cfg.store.hot_slots rows backed by a host cold
        # store.  Requires the arena path — EngineConfig.__post_init__
        # rejects hot_slots without device_arena, and a raw shardings
        # pytree silently falling back to the host path must fail loudly
        # rather than silently going all-resident
        self.tiered = self.use_arena and cfg.store.hot_slots is not None
        if cfg.store.hot_slots is not None and not self.use_arena:
            raise ValueError(
                "StoreConfig.hot_slots requires the device-arena data "
                "path, but these client_shardings force the host path "
                "(pass a callable shape-aware rule like CohortSharding)")
        # pipelined mode (pipeline_depth >= 2) submits cohorts without
        # waiting — donation must be OFF throughout the hot loop because
        # a donated-input dispatch blocks the host until the computation
        # finishes (the very sync the pipeline deletes)
        self.pipelined = cfg.pipeline_depth > 1
        # donate the globals into the fused merge only when nothing can
        # alias their buffer across merges: the host path keeps params0
        # snapshots in pending plans, and personalized clients keep
        # _personal / personal_snapshot refs to received globals.  The
        # engine loops read this flag and defensively copy the CALLER's
        # initial globals once per run (donation would otherwise delete
        # the caller's buffers at the first merge).
        self.donates_globals = (self.use_arena and not self.pipelined
                                and not self.tiered
                                and not any(
                                    c.personal_keys for c in clients))
        add_noise = bool(c0.use_dp and c0.dp_cfg.noise_multiplier > 0)
        self.dp_path = c0.dp_path if c0.use_dp else "jnp"
        # record the resolved Pallas interpret decision (backend + mode +
        # source) whenever the kernel path is in play: a silent
        # interpreted fallback on a compiled-capable backend must be
        # visible in RunLog.engine_stats and the bench rows
        from repro.kernels.common import interpret_info
        self.interpret_info = (interpret_info()
                               if self.dp_path == "pallas" else None)
        self.cohort_step, self.merge_cohort = cached_cohort_step(
            c0.loss_fn, c0.dp_cfg, c0.opt, use_dp=c0.use_dp,
            dp_path=self.dp_path, client_axis=cfg.client_axis,
            client_shardings=client_shardings, fl_cfg=cfg.fl_cfg,
            arena=self.use_arena, donate_globals=self.donates_globals,
            donate=not self.pipelined and not self.tiered,
            add_noise=add_noise)
        # the compiled step's runtime noise scale: sigma * C / B computed
        # on the HOST (float64) then rounded once to float32 — the same
        # constant the statically-folded legacy path multiplies by, so
        # sharing one program across a sigma sweep costs zero ulps
        self._noise_std = jnp.float32(
            c0.dp_cfg.noise_multiplier * c0.dp_cfg.clip_norm
            / c0.batch_size if c0.use_dp else 0.0)
        # data-axis product: arena cohorts pad to a multiple of it so the
        # compiled leading dim always partitions on the mesh (resolved
        # from cfg.mesh when set, else from the CohortSharding's mesh; a
        # custom callable rule without cfg.mesh cannot be introspected,
        # so such cohorts keep their natural size)
        self._n_data = 1
        mesh = cfg.mesh
        if mesh is None and isinstance(client_shardings, CohortSharding):
            mesh = client_shardings.mesh
        if self.use_arena and mesh is not None:
            from repro.launch.mesh import num_client_groups
            self._n_data = num_client_groups(mesh)
        self._arena_params = None
        self._arena_opt = None
        self._writeq = []
        self.cohorts_run = 0
        self.h2d_bytes_total = 0
        # host-sync accounting (RunLog.engine_stats): _host_fetch calls
        # split by whether the loop was inside an eval boundary, plus the
        # serial path's donation-blocked submits and the pipelined
        # path's backpressure drains
        self._in_eval = False
        self.host_syncs_at_eval = 0
        self.host_syncs_between_evals = 0
        self.drain_waits = 0
        self.blocking_submits = 0
        # fault oracle for the current run — set by the engine loops when
        # the spec carries a FaultModel; stats() folds its counters into
        # the ENGINE_STATS_KEYS schema (zeros on a fault-free run)
        self.fault_injector = None
        # update-screening oracle (core.screening.ScreeningState) — set by
        # the engine loops when the spec carries a ScreeningConfig; the
        # verdict fetches it forces are the pipelined path's third
        # sanctioned sync bucket (screen_verdict_syncs), so the
        # host_syncs_between_evals == 0 invariant survives screening
        self.screening = None
        self._in_screen = False
        self.screen_verdict_syncs = 0
        self._last_screen = None
        # tiered-store spills route device->host reads through the
        # _host_fetch funnel tagged _in_store (bucketed store_sync_reads)
        self._in_store = False
        # the serial driver consumes every submit's results before
        # planning the next cohort (and its donating merge/arena-write
        # helpers block dispatch anyway — see cohort_step): every
        # serial-path submit is therefore a per-cohort host sync,
        # counted at the submit site so the serial rows report a NONZERO
        # between-evals sync count that the pipelined path demonstrably
        # drops to 0
        self._submits_block = (not self.pipelined) and (
            self.use_arena or client_shardings is None)
        # epsilon-vs-round table per client (lazy; see dispatch)
        self._eps_sched = {}
        self.store = None
        if self.use_arena:
            self._adopt_data_arena(data_arena)
            if self.tiered:
                self.store = TieredStateStore(
                    cfg.store, len(clients), self)

    # -- cross-run reuse ---------------------------------------------------
    def reset_for_run(self):
        """Restore the runner to a fresh-construction state WITHOUT paying
        construction again: the once-uploaded dataset arena, the compiled
        step/merge/helper functions and the per-client epsilon schedules
        (all pure functions of the config) survive; the per-run state —
        params/opt arenas (stale trained state from the previous run),
        queued writes and the RunLog counters — is dropped.  The state
        arenas lazily re-init at the next dispatch exactly like a fresh
        runner's would.  ``repro.api.Session`` calls this between runs of
        a sweep so consecutive scenarios skip the testbed upload."""
        self._arena_params = None
        self._arena_opt = None
        self._writeq = []
        self.cohorts_run = 0
        self.h2d_bytes_total = 0
        self._in_eval = False
        self.host_syncs_at_eval = 0
        self.host_syncs_between_evals = 0
        self.drain_waits = 0
        self.blocking_submits = 0
        self.fault_injector = None
        self.screening = None
        self._in_screen = False
        self.screen_verdict_syncs = 0
        self._last_screen = None
        self._in_store = False
        if self.store is not None:
            # residency/LRU/cold state is per-run (the arenas re-init);
            # the dataset arena and compiled helpers stay warm
            self.store = TieredStateStore(
                self.cfg.store, len(self.clients), self)

    # -- host-sync accounting ---------------------------------------------
    def note_host_sync(self):
        if self._in_store:
            self.store.sync_reads += 1
        elif self._in_screen:
            self.screen_verdict_syncs += 1
        elif self._in_eval:
            self.host_syncs_at_eval += 1
        else:
            self.host_syncs_between_evals += 1

    def eval_boundary(self, inside: bool):
        """Mark the loop's eval sections: device->host fetches inside them
        are the sanctioned blocking points of the pipelined schedule."""
        self._in_eval = inside

    # -- device-resident arenas -------------------------------------------
    def _adopt_data_arena(self, data_arena):
        """Size the CLIENT-STATE arena and adopt (or build) the dataset
        arena — two separately-keyed residencies since the tiered store:

        * state arena — ``hot_slots`` rows under the tiered store (all N
          on the resident layout) plus the pad slot, rounded up to the
          data-axis product so it shards; rows churn with residency.
        * dataset arena — one row per DISTINCT dataset, uploaded once
          (:class:`repro.engine.statestore.DataArena`) and addressed by
          its own cid->row map; NEVER bounded by ``hot_slots`` and
          reusable across runners whose partition/mesh match (the
          Session passes a cached one in so sigma-only sweeps skip the
          re-upload).

        On the legacy all-resident layout both index spaces coincide
        (slot == cid == data row), recorded as
        ``_data_slots_identical`` so staging uploads ONE slot vector."""
        clients = self.clients
        n = len(clients)
        cs = self.client_shardings
        put = ((lambda a: jax.device_put(a, cs(a))) if callable(cs)
               else jnp.asarray)
        if data_arena is None:
            data_arena = DataArena.build(clients, self._n_data, put)
        self.data_arena = data_arena
        self._arena_data = data_arena.leaves
        self._data_slot_of = data_arena.slot_of_cid
        hot = self.cfg.store.hot_slots if self.tiered else n
        self.pad_slot = hot                     # gathered by padded members
        slots = hot + 1
        if self._n_data > 1:
            slots = -(-slots // self._n_data) * self._n_data
        self.arena_slots = slots
        self._data_slots_identical = (
            not self.tiered and data_arena.pad_slot == self.pad_slot
            and np.array_equal(self._data_slot_of, np.arange(n)))

    def _ensure_state_arenas(self, params):
        """Lazy-init the params/opt arenas from the first dispatched
        globals (shapes only — every slot is overwritten at dispatch
        before the compiled step reads it).  The compiled helpers come
        from the cross-runner cache in
        :func:`repro.engine.cohort_step.cached_arena_helpers` (dropped by
        ``invalidate_step_cache`` together with the step entries)."""
        if self._arena_params is not None:
            return
        (init, self._write, self._gather, self._write_rows,
         self._init_opt) = cached_arena_helpers(
            self.arena_slots, self.clients[0].opt, self.client_shardings,
            donate=not self.pipelined and not self.tiered)
        self._arena_params, self._arena_opt = init(params)

    def _queue_write(self, slot: int, params_tree):
        """Record 'slot trains from this params tree'; the device scatter
        is deferred so consecutive dispatches sharing one globals object
        (a whole FedAvg round, every post-merge re-dispatch) collapse
        into ONE compiled broadcast-write."""
        self._ensure_state_arenas(params_tree)
        self._writeq.append((slot, params_tree))

    def _cancel_writes(self, slot: int):
        """Drop queued params writes against ``slot`` — the tiered store
        calls this when it evicts the slot's occupant (the write belonged
        to the evicted cid; its replacement queues its own)."""
        self._writeq = [(s, t) for s, t in self._writeq if s != slot]

    # -- tiered-store device plumbing (see repro.engine.statestore) --------
    def spill_opt_slot(self, slot: int):
        """Fetch one hot opt row to the host for the cold store.  The
        read routes through the ``_host_fetch_array`` funnel tagged
        ``_in_store`` (counted ``store_sync_reads``), keeping the
        pipelined path's ``host_syncs_between_evals == 0`` proof honest."""
        row = self._gather(self._arena_opt, jnp.asarray([slot], jnp.int32))
        self._in_store = True
        try:
            host = _host_fetch_array(self, row)
        finally:
            self._in_store = False
        return jax.tree_util.tree_map(lambda l: l[0], host)

    def load_opt_rows(self, rows, slots):
        """Re-upload cold opt rows into freshly-assigned hot slots as ONE
        stacked scatter (async device_put under jit — the prefetcher's
        H2D overlaps device compute like every other staging upload)."""
        stacked = jax.tree_util.tree_map(
            lambda *ls: np.stack(ls), *rows)
        self.h2d_bytes_total += sum(
            l.nbytes for l in jax.tree_util.tree_leaves(stacked))
        self._arena_opt = self._write_rows(
            self._arena_opt, stacked, jnp.asarray(slots, jnp.int32))

    def init_opt_rows(self, params_tree, slots):
        """Re-initialize never-spilled slots' opt rows on device
        (``opt.init`` is value-independent — bitwise the state the
        all-resident arena holds for a not-yet-trained client)."""
        self._ensure_state_arenas(params_tree)
        self._arena_opt = self._init_opt(
            self._arena_opt, params_tree, jnp.asarray(slots, jnp.int32))

    def prefetch_upcoming(self, heap, pending):
        """Lookahead prefetch for the async loop: peek the next
        ``StoreConfig.lookahead`` completions of the virtual clock's
        event heap — O(k log N), pop k then push back, never a full
        sort — and stage their members' hot slots ahead of the cohort
        that will pop them.  ``pending`` filters ghosts (fault
        duplicates whose plan already delivered): prefetching a stale
        cid would stage stale params."""
        if self.store is None or not heap:
            return
        k = min(self.store.lookahead, len(heap))
        if k <= 0:
            return
        with spans.span("fl.prefetch"):
            head = [heapq.heappop(heap) for _ in range(k)]
            for entry in head:
                heapq.heappush(heap, entry)
            self.store.prefetch_cids(
                [cid for _, cid in head if cid in pending])

    def prefetch_plans(self, plans):
        """Lookahead prefetch for the fedavg barrier: stage the NEXT
        chunk's members while the current chunk's step executes.  Only
        same-round plans may be passed — a cross-round prefetch would
        stage the previous round's globals."""
        if self.store is not None:
            with spans.span("fl.prefetch"):
                self.store.prefetch_cids([p.cid for p in plans])

    def _flush_writes(self):
        q, self._writeq = self._writeq, []
        i = 0
        while i < len(q):
            tree = q[i][1]
            slots = [q[i][0]]
            j = i + 1
            while j < len(q) and q[j][1] is tree:
                slots.append(q[j][0])
                j += 1
            self._arena_params = self._write(
                self._arena_params, tree, jnp.asarray(slots, jnp.int32))
            i = j

    def stats(self) -> dict:
        """Data-path + scheduler counters for RunLog.engine_stats / the
        benchmarks.  ``host_syncs_between_evals`` is the pipelined-path
        acceptance number (0: the loop never pulls a device value to the
        host outside an eval boundary); ``blocking_submits`` counts the
        serial path's donation-chained submits (each one stalls the host
        for the cohort's full device time); ``drain_waits`` counts the
        pipelined path's backpressure waits on OLDER cohorts (overlapped,
        no device->host transfer)."""
        out = {
            "data_path": "arena" if self.use_arena else "host",
            "dp_path": self.dp_path,
            "pallas_interpret": self.interpret_info,
            "cohorts": self.cohorts_run,
            "h2d_bytes_total": int(self.h2d_bytes_total),
            "h2d_bytes_per_cohort": (
                self.h2d_bytes_total / self.cohorts_run
                if self.cohorts_run else 0.0),
            "pipeline_depth": int(self.cfg.pipeline_depth),
            "host_syncs_at_eval": self.host_syncs_at_eval,
            "host_syncs_between_evals": self.host_syncs_between_evals,
            "blocking_submits": self.blocking_submits,
            "drain_waits": self.drain_waits,
        }
        inj = self.fault_injector
        out.update(inj.stats() if inj is not None else zero_fault_stats())
        scr = zero_screen_stats()
        if self.screening is not None:
            scr.update(self.screening.counters)
        scr["screen_verdict_syncs"] = self.screen_verdict_syncs
        out.update(scr)
        st = zero_store_stats()
        if self.store is not None:
            st.update(self.store.stats())
        out.update(st)
        return out

    # -- dispatch ----------------------------------------------------------
    def dispatch(self, c, global_params, key, server_version: int
                 ) -> LocalRoundPlan:
        """Plan one local round: same host bookkeeping (RNG permutations,
        accountant step, clock draw, version pull) as Client.local_train,
        deferring the actual training to the compiled cohort step."""
        params0 = global_params
        personal_snapshot = None
        if c.personal_keys:
            if c._personal is None:  # first round: adopt global init
                c._personal = {k: global_params[k] for k in c.personal_keys}
            params0 = dict(global_params)
            params0.update(c._personal)
            personal_snapshot = {k: global_params[k] for k in c.personal_keys}
        if self.store is not None:
            # tiered path: the client may have no hot slot yet — remember
            # WHICH globals tree it pulled; the deferred write happens at
            # acquire/prefetch time against the slot it then holds
            self.store.note_dispatch(c.cid, params0)
        elif self.use_arena:
            # arena path: the dispatch-time params snapshot is a deferred
            # device-side slot write; optimizer state already lives in the
            # arena (initialized for every slot at first dispatch)
            self._queue_write(c.cid, params0)
        elif c.opt_state is None:
            c.opt_state = c.opt.init(params0)
        # the batch plan materializes LAZILY at staging (satellite of the
        # tiered-store PR: dispatch must be O(1) per client so the N-wide
        # startup/barrier schedules never do O(N) permutation work up
        # front); the step COUNT is a closed form of (n, B, E), and the
        # accountant charge needs only the count
        steps = steps_per_round(c.n_train, c.batch_size, c.local_epochs)
        if c.use_dp and steps > 0:
            c.accountant.step(c.q, c.dp_cfg.noise_multiplier, steps)
        duration = c.clock.round_duration()
        c.update_count += 1
        c.model_version = server_version
        plan = LocalRoundPlan(
            cid=c.cid,
            params0=None if self.use_arena else params0,
            opt_state=None if self.use_arena else c.opt_state,
            batch_idx=None, key=key, n_steps=steps, duration=duration,
            epsilon=self._client_epsilon(c, steps) if c.use_dp else 0.0,
            model_version=server_version)
        plan.personal_snapshot = personal_snapshot
        return plan

    def _materialize_plans(self, plans):
        """Draw the deferred minibatch permutations for the plans being
        staged (in plan order — each client's RNG advances exactly as the
        eager per-dispatch draws did, because a client's next dispatch
        can only follow the staging of its current plan)."""
        for p in plans:
            if p.batch_idx is None:
                c = self.clients[p.cid]
                p.batch_idx = plan_batches(
                    c.rng, c.n_train, c.batch_size, c.local_epochs)

    def _client_epsilon(self, c, steps: int) -> float:
        """Dispatch-time epsilon: a per-round table lookup on the shared
        :class:`repro.core.accountant.EpsilonSchedule` (bit-identical to
        ``c.accountant.epsilon`` — the schedule replays the accountant's
        exact float64 accumulation of the memoized one-step vector, so
        the per-dispatch min-over-orders recomputation leaves the host
        critical path).  With the fast path toggled off (the benchmark's
        pre-memoization baseline) fall back to the accountant itself."""
        if not _accountant.fast_accounting_enabled():
            return c.accountant.epsilon(self.cfg.delta)
        sched = self._eps_sched.get(c.cid)
        if sched is None:
            sched = _accountant.cached_epsilon_schedule(
                c.q, c.dp_cfg.noise_multiplier, steps, self.cfg.delta,
                orders=c.accountant.orders)
            self._eps_sched[c.cid] = sched
        # update_count was just incremented: the client has been charged
        # for exactly update_count rounds of `steps` DP-SGD steps
        return sched.epsilon_after_rounds(c.update_count)

    # -- compiled local phase ---------------------------------------------
    def _pad_idx(self, idx, batch_size: int):
        """Pad one member's (S, B) batch plan to (s_max, B) with masked
        tail rows (repeat the first row; all-zeros when S == 0)."""
        if idx.shape[0] >= self.s_max:
            return idx
        pad_row = idx[:1] if idx.shape[0] else np.zeros(
            (1, batch_size), np.int32)
        return np.concatenate(
            [idx, np.broadcast_to(
                pad_row, (self.s_max - idx.shape[0],) + pad_row.shape[1:])])

    def run_cohort(self, plans):
        """Run every member's local round in one compiled call; returns the
        stacked new params (leading dim K, or the padded bucket size on
        the arena path) and persists the members' new optimizer states
        (arena scatter, or per-client write-back on the host path).
        Stage + submit in one call — the serial driver's entry point; the
        pipelined loops call the two halves separately."""
        return self.submit_cohort(self.stage_cohort(plans))

    def stage_cohort(self, plans) -> StagedCohort:
        """Assemble one cohort's device inputs AHEAD of submission: flush
        the queued dispatch writes, build the host-side index plans and
        upload them (async device_put — a few KB on the arena path).
        Pure w.r.t. the compiled step: staging cohort t+1 while cohort t
        executes is safe because every input is host-deterministic plan
        state (the pipelined scheduler's lookahead relies on it)."""
        with spans.span("fl.stage"):
            self._materialize_plans(plans)
            k = len(plans)
            if not self.use_arena:
                if self.s_max == 0:  # degenerate: no client has a full batch
                    return StagedCohort(plans=plans, k=k, degenerate=True,
                                        arena=False)
                member_batches = []
                for p in plans:
                    c = self.clients[p.cid]
                    idx = self._pad_idx(p.batch_idx, c.batch_size)
                    member_batches.append(
                        {kk: v[idx] for kk, v in c.data.items()})
                batches_np = {
                    kk: np.stack([mb[kk] for mb in member_batches])
                    for kk in member_batches[0]
                }
                self.cohorts_run += 1
                self.h2d_bytes_total += (
                    sum(a.nbytes for a in batches_np.values()) + 4 * k + 4 * k)
                return StagedCohort(
                    plans=plans, k=k, arena=False,
                    stacked_params=stack_trees([p.params0 for p in plans]),
                    stacked_opt=stack_trees([p.opt_state for p in plans]),
                    batches={kk: jnp.asarray(v)
                             for kk, v in batches_np.items()},
                    keys=jnp.stack([p.key for p in plans]),
                    n_steps=jnp.asarray([p.n_steps for p in plans],
                                        jnp.int32),
                    corrupt=jnp.asarray(
                        [p.corrupt_scale for p in plans], jnp.float32))
            # slot resolution precedes the flush: the tiered store's
            # acquire queues params writes for faulted-in members, and
            # those must ride THIS cohort's flush (the all-resident path
            # queues nothing here, so the flush point is unchanged for it)
            if self.store is not None:
                member_slots = self.store.acquire_cohort(
                    [p.cid for p in plans])
            else:
                member_slots = [p.cid for p in plans]
            self._flush_writes()
            k_pad = (padded_cohort_size(k, self._n_data,
                                        self.cfg.pow2_cohorts)
                     if self._n_data > 1 else k)
            slots = np.full((k_pad,), self.pad_slot, np.int32)
            slots[:k] = member_slots
            slots_j = jnp.asarray(slots)
            data_slots_j = slots_j
            dslots = None
            if not self._data_slots_identical:
                dslots = np.full((k_pad,), self.data_arena.pad_slot, np.int32)
                dslots[:k] = self._data_slot_of[[p.cid for p in plans]]
                data_slots_j = jnp.asarray(dslots)
            if self.s_max == 0:  # degenerate: no client has a full batch
                return StagedCohort(plans=plans, k=k, degenerate=True,
                                    slots=slots_j, data_slots=data_slots_j)
            batch_size = self.clients[0].batch_size
            batch_idx = np.zeros((k_pad, self.s_max, batch_size), np.int32)
            for i, p in enumerate(plans):
                batch_idx[i] = self._pad_idx(p.batch_idx, batch_size)
            n_steps = np.zeros((k_pad,), np.int32)
            n_steps[:k] = [p.n_steps for p in plans]
            keys = jnp.stack(
                [p.key for p in plans]
                + [jnp.zeros_like(plans[0].key)] * (k_pad - k))
            scales = np.ones((k_pad,), np.float32)  # pad members stay clean
            scales[:k] = [p.corrupt_scale for p in plans]
            self.cohorts_run += 1
            self.h2d_bytes_total += (batch_idx.nbytes + slots.nbytes
                                     + n_steps.nbytes + scales.nbytes
                                     + (dslots.nbytes if dslots is not None
                                        else 0))
            return StagedCohort(
                plans=plans, k=k, slots=slots_j, data_slots=data_slots_j,
                batch_idx=jnp.asarray(batch_idx), keys=keys,
                n_steps=jnp.asarray(n_steps), corrupt=jnp.asarray(scales))

    def submit_cohort(self, staged: StagedCohort):
        """Enqueue the compiled local phase for a staged cohort.  On the
        pipelined (donation-free) path this returns without waiting for
        the device; on the serial path the donated state blocks the call
        until the cohort finishes — each such submit is counted as a
        ``blocking_submits`` host sync (between evals, where the hot
        loop lives)."""
        with spans.span("fl.submit"):
            plans = staged.plans
            if not staged.degenerate and self._submits_block:
                self.blocking_submits += 1
                self.note_host_sync()
            if not staged.arena:
                if staged.degenerate:
                    self._last_screen = None
                    return stack_trees([p.params0 for p in plans])
                new_stacked, new_opt, screen = self.cohort_step(
                    staged.stacked_params, staged.stacked_opt, staged.batches,
                    staged.keys, staged.n_steps, self._noise_std,
                    staged.corrupt)
                for i, p in enumerate(plans):
                    self.clients[p.cid].opt_state = unstack_tree(new_opt, i)
                self._last_screen = screen
                return new_stacked
            if staged.degenerate:
                self._last_screen = None
                return self._gather(self._arena_params, staged.slots)
            new_stacked, self._arena_opt, screen = self.cohort_step(
                self._arena_params, self._arena_opt, self._arena_data,
                staged.slots, staged.data_slots, staged.batch_idx, staged.keys,
                staged.n_steps, self._noise_std, staged.corrupt)
            if self.store is not None:
                # every real member's arena opt row was just scatter-updated
                # (dropped/screened members trained too — only their upload
                # was discarded), so eviction must spill before reuse
                self.store.note_trained([p.cid for p in staged.plans])
            self._last_screen = screen
            return new_stacked

    def take_screen_handle(self):
        """Return-and-clear the device handle for the LAST submitted
        cohort's screen outputs ((K_pad,) finite-mask + update norms).
        The handle is a future on the pipelined path — nothing syncs
        until :meth:`fetch_screen` pulls it."""
        screen, self._last_screen = self._last_screen, None
        return screen

    def fetch_screen(self, handle, k: int):
        """Materialize one cohort's screen verdict inputs on the host:
        ONE device->host fetch of the (finite, norm) pair, bucketed as a
        ``screen_verdict_syncs`` sanctioned sync (the pipelined clean
        path keeps ``host_syncs_between_evals == 0``).  Degenerate
        cohorts (``handle is None``) never trained, so every member is
        trivially finite with a zero-delta norm."""
        if handle is None:
            return np.ones((k,), bool), np.zeros((k,), np.float32)
        self._in_screen = True
        try:
            fin, nrm = _host_fetch_array(self, handle)
        finally:
            self._in_screen = False
        return np.asarray(fin[:k]), np.asarray(nrm[:k])

    # -- upload ------------------------------------------------------------
    def upload(self, plan: LocalRoundPlan, new_params):
        """Turn a member's trained params into its uploaded model (personal
        subtrees stay on-device; the upload carries the received globals
        for those keys, exactly like Client.local_train)."""
        c = self.clients[plan.cid]
        if not c.personal_keys:
            return new_params
        c._personal = {k: new_params[k] for k in c.personal_keys}
        up = dict(new_params)
        up.update(plan.personal_snapshot)
        return up


_MERGE_COEFF_DTYPE = np.float32  # the dtype merge_cohort reduces in


def _pad_coeffs(coeffs, stacked):
    """Zero-extend the cohort's merge coefficients to the compiled stack's
    (possibly padded) leading dim — pad members contribute exactly 0.

    Built AT the merge dtype: the float64 fold from
    ``fold_cohort_weights`` rounds to float32 on assignment (the same
    values ``jnp.asarray`` used to produce by silently downcasting a
    float64 buffer under jax's default x64-disabled config, minus the
    double-width round-trip and the 8-bytes-per-member H2D)."""
    k_pad = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    out = np.zeros((k_pad,), _MERGE_COEFF_DTYPE)
    out[: len(coeffs)] = coeffs
    out_j = jnp.asarray(out)
    assert out_j.dtype == _MERGE_COEFF_DTYPE, (
        f"merge coefficients must stay {_MERGE_COEFF_DTYPE}: {out_j.dtype}")
    return out_j


def _fused_ok(strategy, clients, plans, cfg: EngineConfig) -> bool:
    """The weights-vector merge is exact only for plain FedAsync (Eq. 11
    folding) and FedAvg; FedBuff keeps cross-cohort buffer state and
    AdaptiveAsync mixes in the privacy budget, so they go per-member
    through aggregation.apply_update (as do personalized clients)."""
    if not cfg.fused_merge:
        return False
    if type(strategy) not in (FedAvg, FedAsync):
        return False
    return not any(clients[p.cid].personal_keys for p in plans)


def run_fedavg_engine(
    clients: list,
    global_params,
    accuracy_fn: Callable,
    test_data: dict,
    rounds: int = 60,
    seed: int = 0,
    eval_every: int = 1,
    target_acc: Optional[float] = None,
    engine_cfg: Optional[EngineConfig] = None,
    mesh=None,
    runner: Optional[CohortRunner] = None,
    faults=None,
    checkpoint=None,
    resume_from: Optional[str] = None,
    strategy=None,
    screening=None,
) -> tuple:
    """Synchronous FedAvg (Eq. 9): each round is one full-population
    barrier, executed as ceil(N / max_cohort) compiled cohort chunks whose
    dataset-size-weighted partial sums accumulate into the new globals.
    ``mesh`` partitions the cohort axis (see CohortRunner).  ``runner``
    injects a prebuilt (and already reset) CohortRunner — the Session
    sweep path, which keeps the dataset arena uploaded across runs; its
    config wins over ``engine_cfg``/``mesh``.

    ``faults`` (a :class:`repro.core.faults.FaultModel`) makes updates
    lossy: members whose upload is lost stay in the compiled cohort as
    zero-weight mask slots (no recompile), the barrier honors
    ``round_deadline_s``/``min_quorum`` partial aggregation with
    survivor-renormalized Eq. 9 weights, and leave/rejoin churn stretches
    the member's round.  ``checkpoint`` (a
    :class:`repro.engine.resilience.CheckpointPolicy`) snapshots the full
    run state every ``checkpoint.every`` rounds; ``resume_from`` (a
    checkpoint directory) resumes an aborted run bit-identically.

    ``strategy`` selects the synchronous aggregator (default plain
    :class:`~repro.core.aggregation.FedAvg`); robust variants like
    ``TrimmedMeanFedAvg`` route per-member through ``aggregate`` exactly
    like the legacy loop.  ``screening`` (a
    :class:`repro.core.screening.ScreeningConfig`) screens every
    delivered upload against the compiled step's always-computed
    finite-mask/update-norm outputs: a rejected member keeps its compiled
    slot and merges with coefficient exactly 0.0 — same degradation rule
    as a lost update, same program, ``step_builds`` delta 0."""
    if runner is None:
        cfg = _resolve_mesh_cfg(engine_cfg or EngineConfig(), mesh)
        runner = CohortRunner(clients, cfg)
    else:
        cfg = runner.cfg
    if strategy is None:
        strategy = FedAvg()
    if strategy.is_async:
        raise ValueError(
            f"run_fedavg_engine requires a synchronous strategy, got "
            f"{strategy.name!r} — use run_async_engine")
    with spans.span("fl.startup"):
        injector = (FaultInjector(faults, len(clients))
                    if faults is not None else None)
        runner.fault_injector = injector
        screener = (ScreeningState(screening, len(clients))
                    if screening is not None else None)
        runner.screening = screener
        log = RunLog(strategy=strategy.name)
        key = jax.random.PRNGKey(seed)
        t_virtual = 0.0
        for c in clients:
            log.update_counts[c.tier] = 0
            log.staleness.setdefault(c.tier, [])
            log.eps_trajectory.setdefault(c.tier, [])

        start_rnd = 1
        if resume_from is not None:
            from repro.engine import resilience as _rez
            global_params, key, t_virtual, rnd0 = _rez.restore_fedavg(
                resume_from, runner, clients, log, injector, global_params)
            start_rnd = rnd0 + 1
            if checkpoint is not None:
                checkpoint.mark(rnd0)

    # pipelined submit/drain across rounds: the barrier is algorithmic
    # (round r+1 trains from round r's merged globals) but not a host
    # sync — the merge output is a device future the next round's
    # dispatch writes reference, so up to cfg.pipeline_depth rounds of
    # compiled work stay in flight between eval boundaries
    inflight = deque()
    for rnd in range(start_rnd, rounds + 1):
        with spans.step_span("fl.round", rnd):
            with spans.span("fl.dispatch"):
                plans = []
                # one compiled scan for the round's whole PRNG chain
                # (bitwise the old per-client split loop; O(1) dispatches
                # instead of O(N))
                key, subs = split_key_chain(key, len(clients))
                for c, sub in zip(clients, subs):
                    p = runner.dispatch(c, global_params, sub, rnd - 1)
                    if injector is not None and rnd > 1:
                        # leave/rejoin churn: the member rejoins late,
                        # stretching its whole barrier round (the initial
                        # round never draws)
                        p.duration += injector.redispatch_delay(
                            c.cid, t_virtual)
                    plans.append(p)
            # per-plan delivery times for the screening ledger (None = the
            # upload never arrived, so there is nothing to screen)
            t_round0 = t_virtual
            t_deliver = [t_round0 + p.duration for p in plans]
            if injector is not None:
                # fates resolve BEFORE staging so a delivered member's
                # transit-corruption scale rides into the compiled step's
                # runtime corrupt vector (the draws are host-only state,
                # so the event sequence is identical to the submit-first
                # order earlier revisions used)
                with spans.span("fl.pop"):
                    fates = [injector.fedavg_fate(p.cid, t_virtual,
                                                  p.duration)
                             for p in plans]
                    offsets = [off for off, _ in fates]
                    keep, round_time = apply_deadline(injector.model,
                                                      offsets)
                    for i, (p, off, kept) in enumerate(
                            zip(plans, offsets, keep)):
                        p.dropped = not kept
                        t_deliver[i] = None if off is None else t_round0 + off
                        if off is not None:
                            p.corrupt_scale = injector.take_corruption(p.cid)
                            if not kept:
                                injector.note_deadline_drop(
                                    p.cid, t_round0 + off)
                    if any(p.dropped for p in plans):
                        injector.note_degraded()
                # the barrier waits for the effective deadline when it cut
                # anyone off, else the slowest surviving delivery; a round
                # that lost EVERY update still burns the full barrier wait
                t_virtual += (round_time if round_time is not None
                              else max(p.duration for p in plans))
            else:
                t_virtual += max(p.duration for p in plans)
            chunks = [plans[i:i + cfg.max_cohort]
                      for i in range(0, len(plans), cfg.max_cohort)]
            stacked_chunks, screen_handles = [], []
            for ci, ch in enumerate(chunks):
                stacked_chunks.append(
                    runner.submit_cohort(runner.stage_cohort(ch)))
                screen_handles.append(runner.take_screen_handle())
                if ci + 1 < len(chunks):
                    # tiered store: stage the NEXT chunk's hot slots while
                    # this chunk's compiled step executes (same-round
                    # plans only — their dispatch-time globals are current)
                    runner.prefetch_plans(chunks[ci + 1])
            log.cohort_sizes.extend(len(ch) for ch in chunks)
            if screener is not None:
                # judge every DELIVERED member against the compiled step's
                # finite-mask/update-norm outputs (one fetch per chunk, the
                # screen_verdict_syncs bucket); a reject keeps its compiled
                # slot and merges with coefficient exactly 0.0 below
                with spans.span("fl.screen"):
                    i0 = 0
                    for ch, handle in zip(chunks, screen_handles):
                        fin, nrm = runner.fetch_screen(handle, len(ch))
                        for j, p in enumerate(ch):
                            if not p.dropped and not screener.screen(
                                    p.cid, t_deliver[i0 + j], fin[j],
                                    nrm[j]):
                                p.dropped = True
                        i0 += len(ch)

            with spans.span("fl.merge"):
                if _fused_ok(strategy, clients, plans, cfg):
                    # Eq. 9 as chunked weights-vector reductions: the new
                    # globals accumulate sum_k (n_k / sum n) p_k across
                    # the chunks, the sum running over SURVIVING members
                    # only (dropped members keep their compiled slot with
                    # coefficient exactly 0, so a degraded round re-uses
                    # the very same program).  (`merged`, not `acc`: the
                    # eval scalar below is `acc` — the accumulator pytree
                    # must never share its name)
                    if any(not p.dropped for p in plans):
                        _, kept_coeffs = fedavg_weights(
                            [clients[p.cid].n_train for p in plans
                             if not p.dropped])
                        it = iter(kept_coeffs)
                        coeffs = [0.0 if p.dropped else next(it)
                                  for p in plans]
                        merged = jax.tree_util.tree_map(jnp.zeros_like,
                                                        global_params)
                        off = 0
                        for ch, st in zip(chunks, stacked_chunks):
                            merged = runner.merge_cohort(
                                merged, st,
                                _pad_coeffs(coeffs[off:off + len(ch)], st),
                                1.0)
                            off += len(ch)
                        global_params = merged
                else:
                    updates = []
                    for ch, st in zip(chunks, stacked_chunks):
                        updates.extend(
                            (runner.upload(p, unstack_tree(st, i)),
                             clients[p.cid].n_train)
                            for i, p in enumerate(ch) if not p.dropped)
                    if updates:
                        global_params = strategy.aggregate(global_params,
                                                           updates)

            with spans.span("fl.books"):
                for p in plans:
                    if p.dropped:
                        continue
                    c = clients[p.cid]
                    log.update_counts[c.tier] += 1
                    log.staleness[c.tier].append(0)  # barrier: no staleness
                    log.eps_trajectory[c.tier].append(p.epsilon)

            if rnd % eval_every == 0 or rnd == rounds:
                with spans.span("fl.eval"):
                    runner.eval_boundary(True)
                    acc = _host_fetch(
                        runner, accuracy_fn(global_params, test_data))
                    log.times.append(t_virtual)
                    log.global_acc.append(acc)
                    log.server_version.append(rnd)
                    eval_all(clients, global_params, accuracy_fn, log)
                    runner.eval_boundary(False)
                inflight.clear()
                if target_acc is not None and acc >= target_acc:
                    break
            elif runner.pipelined:
                inflight.append(jax.tree_util.tree_leaves(global_params))
                while len(inflight) > cfg.pipeline_depth:
                    with spans.span("fl.drain"):
                        runner.drain_waits += 1
                        jax.block_until_ready(inflight.popleft())

            if checkpoint is not None and rnd < rounds and checkpoint.due(rnd):
                from repro.engine import resilience as _rez
                with spans.span("fl.checkpoint"):
                    _rez.save_fedavg(checkpoint, runner, clients, log,
                                     injector, global_params, key, t_virtual,
                                     rnd)

    _finish(runner, clients, log, injector, screener)
    return global_params, log


def _finish(runner, clients, log: RunLog, injector, screener):
    """After an engine loop: each tier's resource sample and dropouts,
    the fault and screening events, and the runner's counters."""
    with spans.span("fl.finish"):
        for c in clients:
            log.resources[c.tier] = c.clock.resource_sample()
            log.dropouts[c.tier] = c.clock.dropouts
        if injector is not None or screener is not None:
            ev = list(injector.events) if injector is not None else []
            if screener is not None:
                ev += list(screener.events)
            log.fault_events = ev
        log.engine_stats = validate_engine_stats(runner.stats())


def run_async_engine(
    clients: list,
    global_params,
    accuracy_fn: Callable,
    test_data: dict,
    strategy,                      # FedAsync / FedBuff / AdaptiveAsync
    max_updates: int = 300,
    max_time: Optional[float] = None,
    seed: int = 0,
    eval_every: int = 5,
    target_acc: Optional[float] = None,
    engine_cfg: Optional[EngineConfig] = None,
    mesh=None,
    runner: Optional[CohortRunner] = None,
    faults=None,
    checkpoint=None,
    resume_from: Optional[str] = None,
    screening=None,
) -> tuple:
    """Event-driven async FL (Eq. 10-11) over cohorts popped from the
    virtual-clock heap.  ``staleness_window=0`` reproduces the legacy loop
    update-for-update; a positive window batches near-simultaneous
    completions into one compiled step.  ``mesh`` partitions the cohort
    axis (see CohortRunner).  ``runner`` injects a prebuilt (and already
    reset) CohortRunner — the Session sweep path; its config wins over
    ``engine_cfg``/``mesh``.

    ``faults`` (a :class:`repro.core.faults.FaultModel`) resolves every
    popped completion event through the seeded
    :class:`~repro.core.faults.FaultInjector`: retried/late deliveries
    re-enter the heap at backoff-delayed virtual times, duplicates are
    deduped, and lost updates keep their compiled cohort slot as a
    zero-weight mask member (no recompile).  ``checkpoint`` (a
    :class:`repro.engine.resilience.CheckpointPolicy`) snapshots the run
    — server params, arenas, RNG streams, the serialized event heap —
    every ``checkpoint.every`` merged updates; ``resume_from`` resumes an
    aborted run bit-identically.

    ``screening`` (a :class:`repro.core.screening.ScreeningConfig`)
    screens every delivered upload against the compiled step's
    always-computed finite-mask/update-norm outputs — rejects (and
    quarantine drops) become zero-coefficient mask slots like lost
    updates, thresholds are host-side runtime scalars so the one
    compiled program is shared across every screening setting."""
    if runner is None:
        cfg = _resolve_mesh_cfg(engine_cfg or EngineConfig(), mesh)
        runner = CohortRunner(clients, cfg)
    else:
        cfg = runner.cfg
    if ((checkpoint is not None or resume_from is not None)
            and isinstance(strategy, FedBuff)):
        raise ValueError(
            "checkpoint/resume does not support FedBuff — its cross-cohort "
            "buffer holds live device trees the snapshot cannot capture")
    with spans.span("fl.startup"):
        injector = (FaultInjector(faults, len(clients))
                    if faults is not None else None)
        runner.fault_injector = injector
        screener = (ScreeningState(screening, len(clients))
                    if screening is not None else None)
        runner.screening = screener
        if runner.donates_globals:
            # the fused merge donates its globals argument; copy ONCE so
            # the first merge consumes our copy, not the caller's buffers
            # (which the caller may still read — e.g. a baseline eval or
            # a second run from the same initial params)
            global_params = jax.tree_util.tree_map(jnp.copy, global_params)
        log = RunLog(strategy=strategy.name)
        key = jax.random.PRNGKey(seed)
        for c in clients:
            log.update_counts[c.tier] = 0
            log.influence.setdefault(c.tier, 0.0)
            log.staleness.setdefault(c.tier, [])
            log.eps_trajectory.setdefault(c.tier, [])

        # Seed the event queue: every client starts training version 0
        # at t=0.
        heap, pending = [], {}
        server_version = 0
        t_virtual = 0.0
        if resume_from is not None:
            from repro.engine import resilience as _rez
            global_params, key, t_virtual, server_version = (
                _rez.restore_async(resume_from, runner, clients, log,
                                   injector, global_params, heap, pending))
            if checkpoint is not None:
                checkpoint.mark(sum(log.update_counts.values()))
        else:
            # startup schedule: one compiled scan for the N-wide PRNG
            # chain (bitwise the old per-client split loop),
            # O(1)-per-client dispatches (the batch permutations
            # materialize lazily at staging), and a single O(N) heapify
            # instead of N pushes — pop-order-identical since every
            # (duration, cid) is distinct
            key, subs = split_key_chain(key, len(clients))
            entries = []
            for c, sub in zip(clients, subs):
                plan = runner.dispatch(c, global_params, sub, server_version)
                pending[c.cid] = plan
                entries.append((plan.duration, c.cid))
            heap.extend(entries)
            heapq.heapify(heap)
            # tiered store: warm the hot set for the first cohorts
            # (restore skips this — the snapshot already reflects it)
            runner.prefetch_upcoming(heap, pending)

    done = False
    # pipelined submit/drain: cohorts in flight are capped at
    # cfg.pipeline_depth — past that the loop blocks on the OLDEST
    # cohort's outputs (backpressure; the device keeps executing newer
    # cohorts while the host waits).  Serial runs (depth 1) never enter
    # the queue: their donation-chained submits already block per cohort.
    inflight = deque()
    n_cohort = 0
    while heap and not done:
        with spans.step_span("fl.cohort", n_cohort):
            n_cohort += 1
            with spans.span("fl.pop"):
                events = pop_cohort(heap, cfg.staleness_window,
                                    cfg.max_cohort,
                                    bucket_pow2=cfg.pow2_cohorts)
                plans = []
                if injector is None:
                    for t, cid in events:
                        p = pending.pop(cid)
                        p.t_complete = t
                        plans.append(p)
                else:
                    # every popped completion is a delivery ATTEMPT the
                    # injector resolves: duplicates are deduped,
                    # retried/late uploads re-enter the heap at a later
                    # virtual time (the pending plan stays pending), lost
                    # updates consume their plan as a zero-weight mask
                    # member (dropped=True)
                    for t, cid in events:
                        verdict, aux = injector.on_completion(cid, t)
                        if verdict == "duplicate":
                            continue
                        if verdict == "requeue":
                            heapq.heappush(heap, (aux, cid))
                            continue
                        p = pending.pop(cid)
                        p.t_complete = t
                        if verdict == "drop":
                            p.dropped = True
                        else:
                            p.corrupt_scale = injector.take_corruption(cid)
                            if aux is not None:  # deliver + a dup copy
                                heapq.heappush(heap, (aux, cid))
                        plans.append(p)
            if not plans:                   # the whole pop was ghosts/retries
                continue
            t_virtual = plans[-1].t_complete
            new_stacked = runner.submit_cohort(runner.stage_cohort(plans))
            screen_handle = runner.take_screen_handle()
            log.cohort_sizes.append(len(plans))
            n_dropped = sum(1 for p in plans if p.dropped)
            if n_dropped:
                injector.note_degraded()
            if screener is not None:
                # screen every DELIVERED member at its completion time
                # (one fetch per cohort — the screen_verdict_syncs
                # bucket); a reject becomes a zero-coefficient mask slot
                # exactly like a lost update, so the merge below re-uses
                # the same program
                with spans.span("fl.screen"):
                    fin, nrm = runner.fetch_screen(screen_handle, len(plans))
                    for j, p in enumerate(plans):
                        if not p.dropped and not screener.screen(
                                p.cid, p.t_complete, fin[j], nrm[j]):
                            p.dropped = True
                    n_dropped = sum(1 for p in plans if p.dropped)

            with spans.span("fl.merge"):
                if _fused_ok(strategy, clients, plans, cfg):
                    # staleness weights alpha/(1+tau_i), folded so the
                    # single weights-vector reduction equals the
                    # sequential merges; member i's tau accounts for the i
                    # earlier DELIVERED merges in this cohort (dropped
                    # members merge with weight 0 — the fold gives them
                    # coefficient exactly 0 and leaves the survivors'
                    # terms bit-identical to a cohort they were never
                    # part of)
                    taus, weights = [], []
                    n_del = 0
                    for p in plans:
                        if p.dropped:
                            taus.append(0)
                            weights.append(0.0)
                        else:
                            tau = (server_version + n_del) - p.model_version
                            taus.append(tau)
                            weights.append(strategy.mixing_weight(tau))
                            n_del += 1
                    g_coeff, coeffs = fold_cohort_weights(weights)
                    global_params = runner.merge_cohort(
                        global_params, new_stacked,
                        _pad_coeffs(coeffs, new_stacked), g_coeff)
                    server_version += n_del
                else:
                    taus, weights = [], []
                    for i, p in enumerate(plans):
                        if p.dropped:
                            taus.append(0)
                            weights.append(0.0)
                            continue
                        up = runner.upload(p, unstack_tree(new_stacked, i))
                        tau = server_version - p.model_version
                        global_params, inc, w = apply_update(
                            strategy, global_params, up, tau,
                            eps_spent=p.epsilon)
                        server_version += inc
                        taus.append(tau)
                        weights.append(w)

            with spans.span("fl.books"):
                for p, tau, w in zip(plans, taus, weights):
                    if p.dropped:
                        continue
                    c = clients[p.cid]
                    log.staleness[c.tier].append(tau)
                    log.update_counts[c.tier] += 1
                    log.eps_trajectory[c.tier].append(p.epsilon)
                    log.influence[c.tier] += float(w)
                total_updates = sum(log.update_counts.values())
                crossed = any((total_updates - j) % eval_every == 0
                              for j in range(len(plans) - n_dropped))
            if crossed:
                # eval boundary — the pipelined schedule's ONLY sanctioned
                # host block between start and end of run: fetching the
                # global accuracy synchronizes every older cohort too
                with spans.span("fl.eval"):
                    runner.eval_boundary(True)
                    acc = _host_fetch(
                        runner, accuracy_fn(global_params, test_data))
                    log.times.append(t_virtual)
                    log.global_acc.append(acc)
                    log.server_version.append(server_version)
                    eval_all(clients, global_params, accuracy_fn, log)
                    runner.eval_boundary(False)
                inflight.clear()
                if target_acc is not None and acc >= target_acc:
                    done = True
            if total_updates >= max_updates or (
                    max_time and t_virtual >= max_time):
                done = True

            if not done:
                with spans.span("fl.dispatch"):
                    for p in plans:
                        c = clients[p.cid]
                        # joint aggregation-privacy adaptation: a client
                        # that has exhausted its budget STOPS training
                        # (see legacy loop) — dropped members DO
                        # re-dispatch (their device crashed at upload,
                        # the budget was still spent)
                        if (isinstance(strategy, AdaptiveAsync)
                                and p.epsilon >= strategy.eps_target):
                            continue
                        key, sub = jax.random.split(key)
                        plan = runner.dispatch(c, global_params, sub,
                                               server_version)
                        pending[c.cid] = plan
                        t_next = p.t_complete + plan.duration
                        if injector is not None:
                            # leave/rejoin churn delays the next round
                            t_next += injector.redispatch_delay(
                                c.cid, p.t_complete)
                        heapq.heappush(heap, (t_next, c.cid))
                # tiered store: stage the heap head's members while the
                # submitted cohort executes (O(lookahead * log N) peek)
                runner.prefetch_upcoming(heap, pending)
                if runner.pipelined:
                    inflight.append(jax.tree_util.tree_leaves(new_stacked)
                                    + jax.tree_util.tree_leaves(global_params))
                    while len(inflight) > cfg.pipeline_depth:
                        with spans.span("fl.drain"):
                            runner.drain_waits += 1
                            jax.block_until_ready(inflight.popleft())
                if checkpoint is not None and checkpoint.due(total_updates):
                    from repro.engine import resilience as _rez
                    with spans.span("fl.checkpoint"):
                        _rez.save_async(checkpoint, runner, clients, log,
                                        injector, global_params, key, heap,
                                        pending, t_virtual, server_version,
                                        total_updates)

    _finish(runner, clients, log, injector, screener)
    return global_params, log
