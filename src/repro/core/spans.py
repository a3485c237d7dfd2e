"""The program's host spans: one frozen list of names, and the two ways
to open one.

The spans are ``jax.profiler`` annotations, so they land in the
profiler's own trace, on the clock of the ``/device:TPU:n`` planes: a
trace reduction can put every device idle gap down to the innermost span
that was open on the host.  With no trace being taken a span costs well
under a microsecond; the engine opens about fifteen per cohort.

Every span under ``src/`` goes through :func:`span` or :func:`step_span`
with a name from :data:`SPAN_NAMES` (lint rule REP007).  Lives in
``repro.core`` so the api, the engine and the store import it without a
cycle.
"""
from __future__ import annotations

from jax.profiler import StepTraceAnnotation, TraceAnnotation

SPAN_NAMES = (
    "fl.session.run",         # Session.run: the whole call
    "fl.session.testbed",     # Session._materialize: clients, initial params
    "fl.session.partitions",  # synthetic corpus and split (per data config)
    "fl.session.runner",      # CohortRunner build / data arena / reset
    "fl.startup",             # engine loop set-up: key chain, first dispatches
    "fl.cohort",              # step span: one iteration of the async loop
    "fl.round",               # step span: one FedAvg barrier round
    "fl.pop",                 # pop_cohort and fault resolution
    "fl.stage",               # CohortRunner.stage_cohort: plans, writes, H2D
    "fl.submit",              # CohortRunner.submit_cohort
    "fl.screen",              # screen-verdict fetch and verdicts
    "fl.merge",               # weight fold and merge, or per-member updates
    "fl.books",               # RunLog bookkeeping of the merged members
    "fl.eval",                # eval boundary: global and per-client accuracy
    "fl.dispatch",            # re-dispatch: key splits, plans, heap pushes
    "fl.prefetch",            # tiered-store lookahead prefetch
    "fl.drain",               # pipelined backpressure wait
    "fl.checkpoint",          # checkpoint save
    "fl.store.acquire",       # TieredStateStore.acquire_cohort
    "fl.store.load",          # cold/fresh optimizer rows into hot slots
    "fl.store.spill",         # a dirty hot row read back to the host
    "fl.finish",              # after the loop: resources, engine stats
)


def span(name: str) -> TraceAnnotation:
    """A host span named ``name`` (one of :data:`SPAN_NAMES`)."""
    return TraceAnnotation(name)


def step_span(name: str, n: int) -> StepTraceAnnotation:
    """A host span for step ``n`` of a loop: the trace carries ``n`` as
    the event's ``step_num`` stat, and the name stays ``name``."""
    return StepTraceAnnotation(name, step_num=n)
