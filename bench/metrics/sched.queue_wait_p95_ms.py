"""95th percentile, over requests due in the window, from the due time to
the scheduler's ``routed`` event of the request's trace (ms)."""
from bench.measure import p95


def read(run):
    v = p95(r.routed - r.due for r in run.window if r.routed is not None)
    return None if v is None else 1e3 * v
