"""Observability subsystem (serving/metrics.py): histogram math vs numpy,
trace-span ordering + abort paths, disabled-mode guarantees, and the
Prometheus exposition + lint."""
import math
import time
import tracemalloc

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import init_params
from repro.serving.api import SamplingParams
from repro.serving.engine import LocalDisaggEngine
from repro.serving.metrics import (SPAN_ABORTED, SPAN_CHUNK,
                                   SPAN_FIRST_TOKEN, SPAN_FINISHED,
                                   SPAN_HANDOFF, SPAN_QUEUED, SPAN_ROUTED,
                                   SPAN_TOKEN, Histogram, MetricsRegistry,
                                   NullGauge, NullHistogram, lint_prometheus)

CFG = ModelConfig(name="metrics-eng", arch_type="dense", n_layers=2,
                  d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
                  vocab_size=64, dtype="float32")


def _engine(**kw):
    kw.setdefault("num_pages", 128)
    kw.setdefault("page_size", 8)
    eng = LocalDisaggEngine(CFG, init_params(CFG, jax.random.PRNGKey(0)),
                            **kw)
    eng.models.register("m0", init_params(CFG, jax.random.PRNGKey(7)))
    return eng


# ----------------------------------------------------------------------
# histogram math


@pytest.mark.parametrize("dist", ["lognormal", "uniform", "exponential"])
def test_histogram_percentiles_vs_numpy(dist):
    """Interpolated log-bucket percentiles track numpy quantiles within the
    bucket growth factor (docstring bound: relative error <= growth - 1)."""
    rng = np.random.default_rng(0)
    xs = {"lognormal": rng.lognormal(-2.0, 1.5, size=5000),
          "uniform": rng.uniform(1e-4, 10.0, size=5000),
          "exponential": rng.exponential(0.05, size=5000)}[dist]
    growth = 1.25
    h = Histogram("h", lo=1e-6, hi=4e3, growth=growth)
    for x in xs:
        h.observe(float(x))
    assert h.count == len(xs)
    assert h.sum == pytest.approx(float(xs.sum()), rel=1e-9)
    for q in (50, 90, 95, 99):
        est, ref = h.percentile(q), float(np.percentile(xs, q))
        assert abs(est - ref) <= (growth - 1.0) * ref + 1e-12, \
            (dist, q, est, ref)


def test_histogram_edge_cases():
    h = Histogram("h")
    assert math.isnan(h.percentile(50))
    assert math.isnan(h.mean)
    h.observe(0.5)
    # one sample: every percentile is that sample (min/max clamp)
    assert h.percentile(0) == h.percentile(50) == h.percentile(100) == 0.5
    # below-lo and above-hi samples land in the edge buckets, still counted
    h.observe(1e-9)
    h.observe(1e6)
    assert h.count == 3
    assert h.percentile(100) == 1e6
    snap = h.snapshot()
    assert snap["count"] == 3 and snap["min"] == 1e-9 and snap["max"] == 1e6


def test_histogram_cumulative_buckets_monotone():
    rng = np.random.default_rng(1)
    h = Histogram("h")
    for x in rng.lognormal(0.0, 2.0, size=1000):
        h.observe(float(x))
    buckets = h.cumulative_buckets()
    assert math.isinf(buckets[-1][0])          # +Inf bucket always present
    assert buckets[-1][1] == h.count           # cumulative total = count
    cums = [c for _, c in buckets]
    assert cums == sorted(cums)                # non-decreasing


# ----------------------------------------------------------------------
# registry + disabled mode


def test_registry_typed_factories_and_conflicts():
    reg = MetricsRegistry()
    c = reg.counter("x_total")
    assert reg.counter("x_total") is c         # get-or-create
    with pytest.raises(TypeError):
        reg.gauge("x_total")                   # same name, different kind
    g = reg.gauge("g", labels={"k": "a"})
    g2 = reg.gauge("g", labels={"k": "b"})
    assert g is not g2                         # labeled series are distinct


def test_disabled_registry_null_singletons_counters_real():
    reg = MetricsRegistry(enabled=False)
    h1 = reg.histogram("h1")
    h2 = reg.histogram("h2")
    assert isinstance(h1, NullHistogram) and h1 is h2   # shared singleton
    assert isinstance(reg.gauge("g"), NullGauge)
    assert math.isnan(h1.percentile(95))
    # counters stay REAL: the legacy engine.stats() surface runs on them
    c = reg.counter("c_total")
    c.inc(3)
    assert reg.counter("c_total").value == 3


def test_disabled_observe_is_allocation_free():
    """The decode hot loop's would-be samples must not allocate when
    metrics are off: fixed-arity no-op methods on shared singletons."""
    reg = MetricsRegistry(enabled=False)
    h, g = reg.histogram("h"), reg.gauge("g")
    v = 0.125
    h.observe(v)                               # warm up any lazy state
    g.set(v)
    spins = [None] * 1000                      # preallocated loop carrier:
    tracemalloc.start()                        # the measured region must
    try:                                       # itself allocate nothing
        before = tracemalloc.take_snapshot()
        for _ in spins:
            h.observe(v)
            g.set(v)
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    leaked = sum(s.size_diff for s in after.compare_to(before, "lineno")
                 if "test_metrics" in (s.traceback[0].filename or ""))
    assert leaked == 0, f"disabled-mode sampling allocated {leaked} bytes"


def test_disabled_engine_bit_identical_tokens():
    """metrics=False must not perturb decode: greedy streams match the
    metrics=True engine token for token."""
    rng = np.random.default_rng(2)
    ctxs = [list(rng.integers(4, 60, size=18 + i)) for i in range(3)]
    streams = []
    for metrics in (True, False):
        eng = _engine(metrics=metrics)
        outs = [eng.generate("m0", c, SamplingParams(max_tokens=6))
                for c in ctxs]
        eng.run()
        streams.append([list(o.tokens) for o in outs])
        if not metrics:
            snap = eng.metrics()
            assert snap["histograms"] == {}    # nothing registered
            assert snap["traces"] == []
    assert streams[0] == streams[1]


# ----------------------------------------------------------------------
# lifecycle traces


def test_trace_span_ordering_and_ttft():
    eng = _engine(chunked=True, chunk_size=8, token_budget=64)
    rng = np.random.default_rng(3)
    out = eng.generate("m0", list(rng.integers(4, 60, size=20)),
                       SamplingParams(max_tokens=5))
    eng.run()
    assert out.finished
    traces = [t for t in eng.metrics_registry.traces()
              if t.rid == out.request_id]
    assert len(traces) == 1
    tr = traces[0]
    assert tr.done
    names = [n for n, _, _ in tr.events]
    # lifecycle vocabulary in causal order (chunk/token repeat)
    for a, b in [(SPAN_QUEUED, SPAN_ROUTED), (SPAN_ROUTED, SPAN_CHUNK),
                 (SPAN_CHUNK, SPAN_HANDOFF), (SPAN_HANDOFF, SPAN_FIRST_TOKEN),
                 (SPAN_FIRST_TOKEN, SPAN_FINISHED)]:
        assert names.index(a) < names.index(b), names
    # first token + one token span per later streamed token
    assert names.count(SPAN_TOKEN) == len(out.tokens) - 1
    # timestamps are monotone through the pipeline
    times = [t for _, t, _ in tr.events]
    assert times == sorted(times)
    # the derived TTFT span is the same clock RequestOutput exposes (the
    # queued span and submit_time are separate perf_counter reads µs apart)
    assert tr.ttft_s == pytest.approx(out.ttft, abs=5e-3)
    # and the registry's TTFT histogram saw exactly this engine's requests
    snap = eng.metrics()["histograms"]["engine_ttft_seconds"]
    assert snap["count"] == 1
    assert snap["min"] <= out.ttft <= snap["max"] + 1e-12


def test_abort_closes_trace_at_every_stage():
    """Abort at queued / prefilling / decoding all terminate the trace with
    an ``aborted`` span; a finished request is not re-terminated."""
    eng = _engine(chunked=True, chunk_size=4, token_budget=16)
    rng = np.random.default_rng(4)
    mk = lambda n: list(rng.integers(4, 60, size=n))

    def trace_of(out):
        (tr,) = [t for t in eng.metrics_registry.traces()
                 if t.rid == out.request_id]
        return tr

    # queued: aborted before any step ran
    q = eng.generate("m0", mk(24), SamplingParams(max_tokens=4))
    assert eng.abort(q)
    assert trace_of(q).events[-1][0] == SPAN_ABORTED
    assert trace_of(q).done

    # prefilling: one step admits + runs a first chunk of the 40-token
    # prompt (chunk_size=4), then abort reclaims mid-prefill
    p = eng.generate("m0", mk(40), SamplingParams(max_tokens=4))
    eng.step()
    assert any(r.rid == p.request_id for r in eng.scheduler.prefilling)
    assert eng.abort(p)
    tr = trace_of(p)
    assert tr.events[-1][0] == SPAN_ABORTED and tr.done
    assert any(n == SPAN_CHUNK for n, _, _ in tr.events)

    # decoding: step until the first token streamed, then abort
    d = eng.generate("m0", mk(12), SamplingParams(max_tokens=8))
    while not d.tokens and not d.finished:
        eng.step()
    assert eng.abort(d)
    tr = trace_of(d)
    assert tr.events[-1][0] == SPAN_ABORTED and tr.done
    assert any(n == SPAN_FIRST_TOKEN for n, _, _ in tr.events)

    # finished: abort is a no-op and must NOT double-terminate the trace
    f = eng.generate("m0", mk(10), SamplingParams(max_tokens=2))
    eng.run()
    assert f.finished
    assert not eng.abort(f)
    tr = trace_of(f)
    assert tr.events[-1][0] == SPAN_FINISHED
    # closed traces ignore later events (idempotent terminal)
    tr.event(SPAN_TOKEN)
    assert tr.events[-1][0] == SPAN_FINISHED


def test_trace_ring_bounded():
    reg = MetricsRegistry(trace_capacity=4)
    for rid in range(10):
        reg.start_trace(rid)
    traces = reg.traces()
    assert len(traces) == 4
    assert [t.rid for t in traces] == [6, 7, 8, 9]   # oldest evicted


# ----------------------------------------------------------------------
# exposition + lint


def test_render_prometheus_lints_clean_and_carries_series():
    eng = _engine()
    rng = np.random.default_rng(5)
    outs = [eng.generate("m0", list(rng.integers(4, 60, size=16 + i)),
                        SamplingParams(max_tokens=4)) for i in range(2)]
    eng.run()
    assert all(o.finished for o in outs)
    text = eng.render_prometheus()
    assert lint_prometheus(text) == []
    for series in ("engine_ttft_seconds_bucket", "engine_ttft_seconds_count",
                   "engine_itl_seconds_sum", "engine_pool_free_pages",
                   "engine_decode_tokens_total"):
        assert series in text, series
    # fn-backed gauges export live values
    free = eng.block_pool.free_count
    assert f"engine_pool_free_pages {free}" in text


def test_lint_prometheus_catches_format_bugs():
    assert lint_prometheus(
        "# HELP a_total ok\n# TYPE a_total counter\na_total 1\n") == []
    # duplicate series
    bad = ("# HELP a_total ok\n# TYPE a_total counter\n"
           "a_total 1\na_total 2\n")
    assert any("duplicate series" in p for p in lint_prometheus(bad))
    # sample without TYPE/HELP headers
    assert any("no TYPE" in p for p in lint_prometheus("b_total 1\n"))
    # non-numeric value
    bad = "# HELP g ok\n# TYPE g gauge\ng NaNopeNope\n"
    assert any("non-numeric" in p for p in lint_prometheus(bad))
    # histogram with no +Inf bucket
    bad = ("# HELP h ok\n# TYPE h histogram\n"
           'h_bucket{le="1.0"} 1\nh_sum 1\nh_count 1\n')
    assert any("+Inf" in p for p in lint_prometheus(bad))
    # non-monotonic cumulative buckets
    bad = ("# HELP h ok\n# TYPE h histogram\n"
           'h_bucket{le="1.0"} 5\nh_bucket{le="+Inf"} 3\n'
           "h_sum 1\nh_count 3\n")
    assert any("decrease" in p for p in lint_prometheus(bad))


def test_stats_surface_still_runs_on_registry_counters():
    """engine.stats() is a view over registry counters — incrementing via
    either surface shows up in both."""
    eng = _engine()
    rng = np.random.default_rng(6)
    out = eng.generate("m0", list(rng.integers(4, 60, size=16)),
                       SamplingParams(max_tokens=3))
    eng.run()
    assert out.finished
    snap = eng.metrics()["counters"]
    assert snap["engine_handoffs_total"] == eng.stats.handoffs > 0
    assert snap["engine_decode_tokens_total"] == eng.stats.decode_tokens > 0


def test_routed_event_time_is_the_pick_and_carries_the_radix_hit():
    """The ``routed`` event keeps the instant the router picked the worker
    (before the radix match and page allocation) and carries ``cached``:
    the prompt tokens the radix tree served, here a second request over
    the first one's prefix."""
    eng = _engine(chunked=True, chunk_size=8, token_budget=64)
    marks = {}
    pick, worker = eng._pick_worker, eng.prefill_workers[0]
    begin = worker.mgr.begin

    def timed_pick(*a, **kw):
        w = pick(*a, **kw)
        marks.setdefault("picked", []).append(time.perf_counter())
        return w

    def timed_begin(*a, **kw):
        marks.setdefault("begin", []).append(time.perf_counter())
        return begin(*a, **kw)

    eng._pick_worker, worker.mgr.begin = timed_pick, timed_begin
    rng = np.random.default_rng(8)
    prefix = list(rng.integers(4, 60, size=37))
    first = eng.generate("m0", prefix, SamplingParams(max_tokens=2))
    eng.run()
    reused = eng.stats.prefill_tokens_reused
    second = eng.generate("m0", prefix + list(rng.integers(4, 60, size=9)),
                          SamplingParams(max_tokens=2))
    eng.run()
    assert first.finished and second.finished
    hit = eng.stats.prefill_tokens_reused - reused
    assert hit == 32                        # whole 8-token pages of 37
    for i, out in enumerate((first, second)):
        (tr,) = [t for t in eng.metrics_registry.traces()
                 if t.rid == out.request_id]
        (ev,) = [(t, attrs) for n, t, attrs in tr.events
                 if n == SPAN_ROUTED]
        t, attrs = ev
        assert marks["picked"][i] <= t <= marks["begin"][i]
        assert attrs["cached"] == (0, hit)[i]


def test_program_traces_exported_by_program():
    """The process-wide per-program trace table shows in engine.stats(),
    engine.metrics() and the Prometheus exposition, one series a program."""
    eng = _engine(chunked=True, chunk_size=8, token_budget=64)
    rng = np.random.default_rng(9)
    eng.generate("m0", list(rng.integers(4, 60, size=12)),
                 SamplingParams(max_tokens=2))
    eng.run()
    traces = eng.stats()["program_traces"]
    assert traces["decode_step"] >= 1 and traces["chunk_step"] >= 1
    key = 'engine_program_traces_total{program="decode_step"}'
    assert eng.metrics()["counters"][key] == traces["decode_step"]
    text = eng.render_prometheus()
    assert lint_prometheus(text) == []
    assert text.count("# TYPE engine_program_traces_total counter") == 1
    assert f"{key} {traces['decode_step']}" in text
