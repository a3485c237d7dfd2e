"""Share of the tiered store's slot fetches that stalled on a demand
load, from the engine's counters."""


def read(run):
    s = run.window.stats
    if not s["store_fetches"]:
        return None
    return 100.0 * s["store_stall_waits"] / s["store_fetches"]
