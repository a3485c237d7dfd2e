"""Tiered client-state store: bounded hot device arena + host cold store.

The PR-3 arena stacks ALL N clients' params + optimizer state in one
device pytree — perfect for the paper's 32-client testbed, impossible
for the ROADMAP's million-client populations.  This module splits client
state into two tiers (see STORE.md for the full contract):

* **hot set** — the existing mesh-sharded device arena, now bounded to
  ``StoreConfig.hot_slots`` rows (+1 pad row).  A staged cohort gathers
  its members from hot slots exactly as before; the compiled cohort step
  is unchanged except that dataset rows are gathered through their own
  slot map (``DataArena``).
* **cold store** — host-side numpy rows, one optimizer-state tree per
  evicted client.  Params never spill: a client's dispatch-time params
  are a reference to the globals tree it pulled (``pending_params``), so
  re-residency re-stages them as the same deferred broadcast write the
  all-resident path uses — a few KB of H2D, not a device round-trip.
* **lookahead prefetcher** — the engine loops peek the virtual clock's
  event heap (O(k log N): pop k, push back) and stage upcoming members'
  slots ahead of their cohort, riding the PR-4 submit/drain overlap so a
  demand stall (``store_stall_waits``) is the exception, not the rule.

Residency policy: LRU over a monotonic touch tick, with the cohort (and
prefetch batch) being staged pinned via a keep-set; free slots assign in
ascending order.  Every decision is a pure function of the acquire /
prefetch call sequence — host-deterministic plan state — so a tiered
run's RunLog and params are bit-identical to the all-resident arena
(parity-tested), and checkpoint resume replays residency exactly
(``state_meta``/``load_state_meta`` round-trip the whole store through
:mod:`repro.engine.resilience`).

Spills route through the runner's ``_host_fetch_array`` funnel tagged
``_in_store`` (counted as ``store_sync_reads``), so a pipelined tiered
run still proves ``host_syncs_between_evals == 0``.  The counters land
in ``RunLog.engine_stats`` under :data:`repro.core.runlog.
STORE_STATS_KEYS` with the ledger law checked by ``audit_engine_stats``:
``store_fetches == store_hot_hits + store_prefetch_hits +
store_stall_waits``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import numpy as np

from repro.core import spans
from repro.core.runlog import STORE_STATS_KEYS


def zero_store_stats() -> dict:
    """All-resident runs report every store counter as 0 (the schema in
    ``ENGINE_STATS_KEYS`` is unconditional, like the fault/screen keys)."""
    return {k: 0 for k in STORE_STATS_KEYS}


def _tree_nbytes(tree) -> int:
    return int(sum(l.nbytes for l in jax.tree_util.tree_leaves(tree)))


@dataclass(frozen=True)
class StoreConfig:
    """Spec-serializable knobs for the tiered client-state store.

    ``hot_slots=None`` (the default) is the all-resident arena — every
    client keeps a device slot and no store machinery runs, so existing
    specs/checkpoints decode and replay unchanged.  A positive
    ``hot_slots`` bounds the device arena to that many client rows;
    ``lookahead`` is how many upcoming event-heap completions the
    prefetcher stages ahead of their cohort (0 disables prefetch — every
    miss becomes a counted demand stall)."""

    hot_slots: Optional[int] = None
    lookahead: int = 8

    def __post_init__(self):
        if self.hot_slots is not None and (
                self.hot_slots != int(self.hot_slots) or self.hot_slots < 1):
            raise ValueError(
                f"StoreConfig.hot_slots must be None (all-resident) or an "
                f"integer >= 1: {self.hot_slots!r}")
        if self.lookahead != int(self.lookahead) or self.lookahead < 0:
            raise ValueError(
                f"StoreConfig.lookahead must be an integer >= 0: "
                f"{self.lookahead!r}")


@dataclass
class DataArena:
    """The once-uploaded device dataset arena, keyed SEPARATELY from
    client state: rows are deduped by dataset identity (``id(c.data)``)
    and addressed through ``slot_of_cid``, so (a) shared-dataset
    populations upload one row however many clients reference it — the
    100k-client scale bench fits on CPU because of exactly this — and
    (b) a :class:`repro.api.Session` sweep whose axes only touch
    client-state config (sigma, strategy, store) re-uses the arena
    across runners and skips the re-upload entirely."""

    leaves: dict              # data key -> (n_slots, n_max, ...) device array
    slot_of_cid: np.ndarray   # (N,) int32: cid -> data slot
    pad_slot: int             # row gathered by cohort pad members (zeros)
    n_slots: int              # pad_slot + 1 rounded up to the data-axis product
    n_max: int                # longest client dataset (short rows zero-pad)
    nbytes: int               # host-side bytes uploaded (bench provenance)

    @classmethod
    def build(cls, clients, n_data: int, put) -> "DataArena":
        """Upload every DISTINCT dataset once (slot = order of first
        encounter; identical to the legacy slot-per-cid layout when no
        clients share data, so all-resident arenas stay bit-identical),
        zero-pad short datasets, and round the slot count up to a
        multiple of ``n_data`` so the arena itself shards under the
        shape-aware mesh rule.  ``put`` is the runner's H2D placement
        closure (sharded ``device_put`` on a mesh, ``jnp.asarray``
        otherwise)."""
        reps = []
        rep_slot = {}
        slot_of_cid = np.empty((len(clients),), np.int32)
        for c in clients:
            s = rep_slot.get(id(c.data))
            if s is None:
                s = len(reps)
                rep_slot[id(c.data)] = s
                reps.append(c.data)
            slot_of_cid[c.cid] = s
        pad_slot = len(reps)
        n_slots = pad_slot + 1
        if n_data > 1:
            n_slots = -(-n_slots // n_data) * n_data
        n_max = max(c.n_train for c in clients)
        leaves = {}
        nbytes = 0
        for k, v0 in clients[0].data.items():
            buf = np.zeros((n_slots, n_max) + v0.shape[1:], v0.dtype)
            for s, data in enumerate(reps):
                buf[s, : data[k].shape[0]] = data[k]
            nbytes += buf.nbytes
            leaves[k] = put(buf)
        return cls(leaves=leaves, slot_of_cid=slot_of_cid, pad_slot=pad_slot,
                   n_slots=n_slots, n_max=n_max, nbytes=int(nbytes))


@dataclass
class TieredStateStore:
    """Residency manager for the bounded hot arena (see module docstring).

    The store owns the cid<->slot maps, the LRU clock, the dirty set and
    the host cold rows; the device work (slot writes, opt-row loads and
    spills) goes through the owning :class:`repro.engine.engine.
    CohortRunner`'s compiled helpers so every byte lands in the runner's
    H2D/sync accounting.  All methods are host-only bookkeeping — no
    raw device fetches (REP005/REP006-lintable) and no per-client O(N)
    loops: every loop below walks a cohort, a prefetch batch or the
    lookahead head."""

    cfg: StoreConfig
    n_clients: int
    runner: object

    def __post_init__(self):
        self.hot_slots = int(self.cfg.hot_slots)
        self.lookahead = int(self.cfg.lookahead)
        self.slot_of = {}         # cid -> hot slot (resident clients)
        self.cid_of = {}          # hot slot -> cid
        # free slots pop() in ascending order — deterministic assignment
        self.free = list(range(self.hot_slots - 1, -1, -1))
        self.seq = {}             # cid -> last-touch tick (LRU order)
        self.tick = 0
        self.dirty = set()        # resident cids whose hot opt row was trained
        self.prefetched = set()   # resident via prefetch, not yet acquired
        self.cold = {}            # cid -> host opt-row tree (numpy leaves)
        self.pending_params = {}  # cid -> dispatch-time globals tree (by ref)
        self.fetches = 0
        self.hot_hits = 0
        self.prefetch_hits = 0
        self.stall_waits = 0
        self.evictions = 0
        self.spill_bytes = 0
        self.sync_reads = 0

    # -- dispatch/train bookkeeping ---------------------------------------
    def note_dispatch(self, cid: int, params_tree):
        """The tiered twin of the all-resident path's dispatch-time slot
        write: remember WHICH params tree the client pulled (a reference,
        not a copy) so the deferred broadcast write happens at acquire /
        prefetch time, against whatever slot the client then holds."""
        self.pending_params[cid] = params_tree

    def note_trained(self, cids):
        """Mark a submitted cohort's members dirty: their hot opt rows
        now differ from any cold copy, so eviction must spill them (a
        dropped/screened member still trained — its budget was spent and
        its arena row was written — so it is dirty too)."""
        self.dirty.update(cids)

    # -- residency ---------------------------------------------------------
    def acquire_cohort(self, cids) -> list:
        """Return the hot slot for every member of the cohort being
        staged, faulting in misses (counted ``store_stall_waits``) and
        classifying hits by whether the prefetcher staged them.  The
        whole cohort is pinned while slots are grabbed — a cohort larger
        than the hot set is a config error surfaced as a deadlock."""
        with spans.span("fl.store.acquire"):
            keep = set(cids)
            loads, slots = [], []
            for cid in cids:
                self.tick += 1
                self.fetches += 1
                slot = self.slot_of.get(cid)
                if slot is not None:
                    if cid in self.prefetched:
                        self.prefetched.discard(cid)
                        self.prefetch_hits += 1
                        # the prefetch already queued this cid's params write
                    else:
                        self.hot_hits += 1
                        self.runner._queue_write(
                            slot, self.pending_params[cid])
                else:
                    self.stall_waits += 1
                    slot = self._grab_slot(keep)
                    self._assign(cid, slot)
                    self.runner._queue_write(slot, self.pending_params[cid])
                    loads.append((cid, slot))
                self.seq[cid] = self.tick
                slots.append(slot)
            self._load_slots(loads)
            return slots

    def prefetch_cids(self, cids):
        """Stage upcoming members' slots ahead of their cohort.  Callers
        pass only cids whose CURRENT dispatch is still pending (the
        engine loops filter against the pending map / the live round's
        plans) — prefetching a stale cid would write stale params.  Slot
        pressure degrades gracefully: a soft grab that finds every
        resident row pinned stops prefetching instead of deadlocking."""
        targets = [c for c in cids
                   if c not in self.slot_of and c in self.pending_params]
        if not targets:
            return
        keep = set(cids) | self.prefetched
        loads = []
        for cid in targets:
            slot = self._grab_slot(keep, soft=True)
            if slot is None:
                break
            self.tick += 1
            self._assign(cid, slot)
            self.seq[cid] = self.tick
            self.prefetched.add(cid)
            keep.add(cid)
            self.runner._queue_write(slot, self.pending_params[cid])
            loads.append((cid, slot))
        self._load_slots(loads)

    def _assign(self, cid: int, slot: int):
        self.slot_of[cid] = slot
        self.cid_of[slot] = cid

    def _grab_slot(self, keep, soft: bool = False):
        """One free (ascending) or LRU-evicted hot slot; ``keep`` pins
        the cohort/prefetch batch being staged.  The LRU victim is the
        strict minimum of the per-cid touch ticks (unique by
        construction), so eviction order is deterministic regardless of
        dict iteration details."""
        if self.free:
            return self.free.pop()
        victim, vseq = None, None
        for cid in self.slot_of:
            if cid in keep:
                continue
            sq = self.seq[cid]
            if vseq is None or sq < vseq:
                victim, vseq = cid, sq
        if victim is None:
            if soft:
                return None
            raise RuntimeError(
                f"TieredStateStore deadlock: all {self.hot_slots} hot slots "
                f"are pinned by the cohort being staged ({len(keep)} "
                "members) — raise StoreConfig.hot_slots above "
                "EngineConfig.max_cohort")
        return self._evict(victim)

    def _evict(self, cid: int) -> int:
        """Surrender ``cid``'s hot slot.  Dirty rows spill device->host
        through the runner (the ``_in_store`` sanctioned sync); clean
        rows free instantly — their cold copy (or, for never-trained
        rows, the value-independent fresh ``opt.init``) already
        reproduces them bit-for-bit."""
        slot = self.slot_of.pop(cid)
        del self.cid_of[slot]
        self.seq.pop(cid, None)
        self.prefetched.discard(cid)
        self.runner._cancel_writes(slot)
        self.evictions += 1
        if cid in self.dirty:
            self.dirty.discard(cid)
            with spans.span("fl.store.spill"):
                row = self.runner.spill_opt_slot(slot)
            self.cold[cid] = row
            self.spill_bytes += _tree_nbytes(row)
        return slot

    def _load_slots(self, loads):
        """Materialize freshly-assigned slots' optimizer rows: cold rows
        re-upload as ONE stacked scatter; never-spilled rows re-init
        in-place on device (``opt.init`` is value-independent — zeros —
        so a fresh init is bitwise the state the all-resident arena
        would hold)."""
        if not loads:
            return
        with spans.span("fl.store.load"):
            cold_pairs = [(c, s) for c, s in loads if c in self.cold]
            fresh_pairs = [(c, s) for c, s in loads if c not in self.cold]
            if fresh_pairs:
                self.runner.init_opt_rows(
                    self.pending_params[fresh_pairs[0][0]],
                    [s for _, s in fresh_pairs])
            if cold_pairs:
                self.runner.load_opt_rows(
                    [self.cold[c] for c, _ in cold_pairs],
                    [s for _, s in cold_pairs])

    # -- stats / checkpoint state -----------------------------------------
    def stats(self) -> dict:
        return {
            "store_fetches": int(self.fetches),
            "store_hot_hits": int(self.hot_hits),
            "store_prefetch_hits": int(self.prefetch_hits),
            "store_stall_waits": int(self.stall_waits),
            "store_evictions": int(self.evictions),
            "store_spill_bytes": int(self.spill_bytes),
            "store_sync_reads": int(self.sync_reads),
        }

    def state_meta(self) -> dict:
        """The store's residency/LRU/counter state as a JSON-able dict
        (the cold rows and pending params trees travel separately as
        checkpoint arrays — see resilience._snapshot_common)."""
        return {
            "slot_of": {str(c): int(s) for c, s in self.slot_of.items()},
            "free": [int(s) for s in self.free],
            "seq": {str(c): int(t) for c, t in self.seq.items()},
            "tick": int(self.tick),
            "dirty": sorted(int(c) for c in self.dirty),
            "prefetched": sorted(int(c) for c in self.prefetched),
            "counters": self.stats(),
        }

    def load_state_meta(self, meta: dict):
        self.slot_of = {int(c): int(s) for c, s in meta["slot_of"].items()}
        self.cid_of = {s: c for c, s in self.slot_of.items()}
        self.free = [int(s) for s in meta["free"]]
        self.seq = {int(c): int(t) for c, t in meta["seq"].items()}
        self.tick = int(meta["tick"])
        self.dirty = set(int(c) for c in meta["dirty"])
        self.prefetched = set(int(c) for c in meta["prefetched"])
        c = meta["counters"]
        self.fetches = int(c["store_fetches"])
        self.hot_hits = int(c["store_hot_hits"])
        self.prefetch_hits = int(c["store_prefetch_hits"])
        self.stall_waits = int(c["store_stall_waits"])
        self.evictions = int(c["store_evictions"])
        self.spill_bytes = int(c["store_spill_bytes"])
        self.sync_reads = int(c["store_sync_reads"])


__all__ = ["DataArena", "StoreConfig", "TieredStateStore",
           "zero_store_stats", "STORE_STATS_KEYS"]
