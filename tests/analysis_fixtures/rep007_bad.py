"""Seeded REP007 violations: host spans that bypass the span registry
(a raw profiler annotation, a name without the ``fl.`` prefix, a
computed name, and a name the registry does not list, which fires only
when ``repro/core/spans.py`` is linted alongside)."""
import jax

from repro.core import spans

SPAN_PREFIX = "fl."


def stage(plans):
    with jax.profiler.TraceAnnotation("fl.stage"):       # raw annotation
        return list(plans)


def pop(heap):
    with spans.span("pop"):                               # no fl. prefix
        return heap.pop()


def cohort(n):
    with spans.step_span(SPAN_PREFIX + "cohort", n):      # not a literal
        return n


def submit(staged):
    with spans.span("fl.submitt"):                        # not registered
        return staged
