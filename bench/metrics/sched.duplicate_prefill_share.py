"""Share (%) of the prompt tokens computed in the window that another
request in flight was computing at the same time: Δduplicated ÷ Δcomputed,
from the engine's counters."""


def read(run):
    a, b = run.stats_open, run.stats_close
    computed = b["computed"] - a["computed"]
    if b.get("duplicated") is None or computed <= 0:
        return None
    return 100.0 * (b["duplicated"] - a["duplicated"]) / computed
