"""Share (%) of the prompt tokens admitted in the window that the prefix
cache served: reused / (computed + reused), from the engine's counters."""


def read(run):
    a, b = run.stats_open, run.stats_close
    reused = b["reused"] - a["reused"]
    total = reused + b["computed"] - a["computed"]
    return 100.0 * reused / total if total > 0 else None
