"""Per-experiment failure test and the compile clock.

``CompileClock`` reads JAX's own monitoring events: the seconds the XLA
backend spent compiling, how many programs it compiled, and how many the
persistent compilation cache served instead.  Tracing and lowering are
not counted (their events nest and would count twice).

``check_run`` fails one experiment: params not finite, the update budget
missed, an epsilon not finite and positive, or the engine's counters
breaking their ledger laws.
"""
from __future__ import annotations

import math

import numpy as np

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    def __init__(self):
        from jax import monitoring
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        return self.seconds, self.compiles, self.cache_hits


class RunFailed(Exception):
    pass


def require(cond, msg):
    if not cond:
        raise RunFailed(msg)


def audit_stats(stats: dict, label: str, compiled_kernel: bool = True):
    """The engine counters' ledger laws: every store fetch is a hot hit,
    a prefetch hit or a stall, every lost upload a retry or a lost
    update, every rejection nonfinite or over the norm; the pipelined
    path never syncs the host between evals; a Pallas run says whether
    its kernel ran compiled, and on the chip it did."""
    def ledger(total, *parts):
        require(stats[total] == sum(stats[p] for p in parts),
                f"{label}: {total} {stats[total]} != "
                + " + ".join(f"{p} {stats[p]}" for p in parts))

    ledger("store_fetches", "store_hot_hits", "store_prefetch_hits",
           "store_stall_waits")
    ledger("fault_upload_losses", "fault_retries", "fault_lost_updates")
    ledger("screen_rejections", "screen_nonfinite", "screen_norm_rejects")
    if stats["pipeline_depth"] > 1:
        require(stats["host_syncs_between_evals"] == 0,
                f"{label}: {stats['host_syncs_between_evals']} host syncs "
                "between evals on the pipelined path")
    if stats["dp_path"] == "pallas":
        info = stats["pallas_interpret"]
        require(info is not None, f"{label}: no Pallas interpret record")
        require(not compiled_kernel or info.get("interpret") is False,
                f"{label}: the Pallas kernel did not run compiled: {info}")
    require(stats["cohorts"] > 0, f"{label}: no cohort ran")


def check_run(label: str, params, log, updates: int, overshoot: int = 0,
              compiled_kernel: bool = True):
    """Finite params, the budget met (a last cohort of K may pass it by up
    to K - 1 = ``overshoot``), epsilons finite and positive, the engine
    counters' laws kept.  Returns the merged updates."""
    import jax
    for leaf in jax.tree_util.tree_leaves(params):
        require(np.isfinite(np.asarray(leaf)).all(),
                f"{label}: non-finite params")
    total = sum(log.update_counts.values())
    require(updates <= total <= updates + overshoot,
            f"{label}: {total} merged updates, budget {updates}")
    eps = [e for traj in log.eps_trajectory.values() for e in traj]
    require(eps and all(math.isfinite(e) and e > 0 for e in eps),
            f"{label}: epsilon not finite and positive")
    audit_stats(log.engine_stats, label, compiled_kernel)
    return total
