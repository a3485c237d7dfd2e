"""Host waits on the device per merged update: fetches at eval
boundaries, fetches between evals and the serial driver's blocking
submits, from the engine's counters."""


def read(run):
    s, n = run.window.stats, run.window.updates
    if not n:
        return None
    return (s["host_syncs_at_eval"] + s["host_syncs_between_evals"]
            + s["blocking_submits"]) / n
