"""Device milliseconds of the compiled cohort-step program per cohort,
from the trace (programs named ``cohort_step``), averaged over the
chips."""


def read(run):
    t, cohorts = run.trace, run.window.stats["cohorts"]
    if t is None or not cohorts:
        return None
    s = t.program_seconds("cohort_step")
    if s <= 0:
        return None
    return 1e3 * s / cohorts
