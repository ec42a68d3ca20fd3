"""The trace reduction against a small trace recorded on a TPU v5e: the
device-busy union, kernel time by name, which programs are decode and which
chunk steps, and what the host was doing in each idle gap."""
import os

import numpy as np
import pytest

from bench import tracefile
from bench.measure import STEP_KERNELS

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "trace_v5e.json.gz")


@pytest.fixture(scope="module")
def rec():
    return tracefile.load(FIXTURE)


@pytest.fixture(scope="module")
def window(rec):
    [(_, t0, dur)] = [h for h in rec["host"] if h[0] == "bench.window"]
    return t0, t0 + dur


def test_busy_union_matches_a_microsecond_grid(rec, window):
    t0, t1 = window
    grid = np.zeros((t1 - t0) // 1000 + 1, bool)
    for _, a, d, dev, _ in rec["ops"]:
        lo, hi = max(a, t0), min(a + d, t1)
        if hi > lo:
            grid[(lo - t0) // 1000:-(-(hi - t0) // 1000)] = True
    busy = tracefile.busy_ns(rec, t0, t1)
    assert 0 < busy < t1 - t0
    # the grid rounds each interval out to whole microseconds
    assert abs(grid.sum() * 1000 - busy) <= 2000 * len(rec["ops"]) ** 0.5 \
        + 0.01 * busy


def test_kernel_time_by_name(rec, window):
    t0, t1 = window
    for kernel in ("paged_decode", "flash_prefill_paged"):
        want = sum(min(a + d, t1) - max(a, t0) for n, a, d, _, _ in rec["ops"]
                   if tracefile.short_name(n) == kernel
                   and a < t1 and a + d > t0)
        assert want > 0
        assert tracefile.kernel_ns(rec, kernel, t0, t1) == want
    # one kernel's name inside another's does not count
    assert not tracefile.matches(["%flash_prefill_paged.6 = x", 0, 1, 0, ""],
                                 "paged_decode")


def test_decode_and_chunk_programs(rec):
    """The fused decode plane's program runs paged_decode and the chunk
    step's runs flash_prefill_paged; the small conversions run neither."""
    kinds = tracefile.classify_programs(rec, STEP_KERNELS)
    seen = set()
    for (kind, *_), (name, *_) in zip(kinds, rec["modules"]):
        if name.startswith("jit_fused("):
            assert kind == "decode"
        elif name.startswith("jit__step("):
            assert kind == "chunk"
        else:
            assert kind is None
        seen.add(kind)
    assert {"decode", "chunk"} <= seen


def test_idle_gaps_are_named_by_the_open_host_span(rec, window):
    t0, t1 = window
    gaps = tracefile.idle_gaps(rec, t0, t1)
    busy = tracefile.busy_ns(rec, t0, t1)
    assert sum(ns for _, ns in gaps) + busy == t1 - t0
    assert [ns for _, ns in gaps] == sorted((ns for _, ns in gaps),
                                            reverse=True)
    names = {n for n, _ in gaps}
    assert names <= {h[0] for h in rec["host"]} | {"none"}
    assert "bench.step" in names
    # a gap wholly inside one engine step is that step's
    steps = [(a, a + d) for n, a, d in rec["host"] if n == "bench.step"]
    busy_iv = tracefile.union(tracefile.clip(rec["ops"], t0, t1))
    at = {start: n for n, _, start in tracefile.idle_gaps(rec, t0, t1,
                                                          where=True)}
    inside = 0
    for (_, b0), (a1, _) in zip(busy_iv, busy_iv[1:]):
        if any(s <= b0 and a1 <= e for s, e in steps):
            assert at[b0] == "bench.step"
            inside += 1
    assert inside > 0


def test_top_ops_leave_out_loops(rec, window):
    names = [n for n, _ in tracefile.top_ops(rec, *window)]
    assert "while" not in names
    assert {"paged_decode", "flash_prefill_paged"} <= set(names)
