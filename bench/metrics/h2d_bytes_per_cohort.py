"""Host-to-device bytes staged per cohort, from the engine's counters."""


def read(run):
    s = run.window.stats
    if not s["cohorts"]:
        return None
    return s["h2d_bytes_total"] / s["cohorts"]
