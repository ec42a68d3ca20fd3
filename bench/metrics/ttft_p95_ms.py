"""95th percentile, over every request due in the window, from its due time
to its first streamed token (ms)."""
from bench.measure import first_of, p95


def read(run):
    v = p95(first_of(run, r) - r.due for r in run.window)
    return None if v is None else 1e3 * v
