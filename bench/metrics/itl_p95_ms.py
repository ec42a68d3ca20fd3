"""95th percentile of every gap between consecutive streamed tokens of the
requests due in the window (ms)."""
import numpy as np

from bench.measure import p95


def read(run):
    gaps = [g for r in run.window for g in np.diff(r.token_times)]
    v = p95(gaps)
    return None if v is None else 1e3 * v
