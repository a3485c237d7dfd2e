"""REP007 clean twin: the registry module may open annotations, and
every other span is a registered literal opened through it."""
from jax.profiler import StepTraceAnnotation, TraceAnnotation

SPAN_NAMES = ("fl.stage", "fl.cohort")


def span(name):
    return TraceAnnotation(name)


def step_span(name, n):
    return StepTraceAnnotation(name, step_num=n)


def stage(plans):
    with span("fl.stage"):
        return list(plans)


def cohort(n):
    with step_span("fl.cohort", n):
        return n
