"""The split of the device's idle time inside ``engine.step`` by the
program's own host spans: on hand-made intervals, on a trace recorded on a
TPU v5e with the program's ``ps.*`` spans in it, on the older trace that has
none, and through a traced ``bench/run.py`` on the CPU at a tiny size."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import tracefile
from bench.harness import TraceView
from bench.spec import reader

DATA = Path(__file__).parent / "data"
ROOT = Path(__file__).resolve().parents[2]


def _load(name):
    rec = tracefile.load(str(DATA / name))
    [(_, t0, dur)] = [h for h in rec["host"] if h[0] == "bench.window"]
    return rec, t0, t0 + dur


@pytest.fixture(scope="module")
def traced():
    return _load("trace_v5e_spans.json.gz")


def _idle_inside_steps(rec, t0, t1) -> int:
    """Nanoseconds in [t0, t1) inside ``bench.step`` with no operation on
    device 0, by a sweep over both sets of intervals."""
    pts = []
    for e in rec["host"]:
        if e[0] == "bench.step":
            pts += [(e[1], "s", 1), (e[1] + e[2], "s", -1)]
    for e in rec["ops"]:
        if e[3] == 0:
            pts += [(e[1], "b", 1), (e[1] + e[2], "b", -1)]
    pts.sort()
    depth = {"s": 0, "b": 0}
    total, prev = 0, None
    for t, kind, d in pts:
        if prev is not None and depth["s"] > 0 and depth["b"] == 0:
            total += max(0, min(t, t1) - max(prev, t0))
        depth[kind] += d
        prev = t
    return total


def test_innermost_span_names_each_idle_nanosecond():
    rec = {"ops": [["op", 60, 10, 0, ""]], "modules": [],
           "host": [["bench.window", 0, 100], ["bench.step", 0, 100],
                    ["ps.step", 0, 100], ["ps.run.decode_step", 10, 40],
                    ["backend_compile_and_load", 20, 20],
                    ["ps.decode.push", 90, 0]]}
    assert tracefile.idle_by_span(rec, 0, 100) == {
        "ps.step": 10 + 10 + 30, "ps.run.decode_step": 10 + 10,
        "backend_compile_and_load": 20}
    split = tracefile.host_split(rec, 0, 100)
    assert split["host.compile_idle_share"] == 20.0
    assert split["host.dispatch_idle_share"] == 20.0
    assert split["rest"] == 50.0
    assert split["idle_ns"] == 90


def test_shares_and_rest_are_the_idle_inside_steps(traced):
    rec, t0, t1 = traced
    by = tracefile.idle_by_span(rec, t0, t1)
    want = _idle_inside_steps(rec, t0, t1)
    assert 0 < want and sum(by.values()) == want
    names = set(by)
    assert names <= ({n for n, *_ in rec["host"] if n.startswith("ps.")}
                     | set(tracefile.JAX_COMPILE_EVENTS) | {"none"})
    split = tracefile.host_split(rec, t0, t1)
    shares = [split[g] for g in tracefile.HOST_GROUPS] + [split["rest"]]
    assert sum(shares) == pytest.approx(100.0 * want / split["inside_ns"],
                                        rel=1e-12)
    # a trace of the program's step names its idle by the step's phases
    assert split["host.dispatch_idle_share"] > 0
    assert split["host.prep_idle_share"] > 0


def test_trace_without_program_spans_reads_all_idle_as_rest():
    rec, t0, t1 = _load("trace_v5e.json.gz")
    want = _idle_inside_steps(rec, t0, t1)
    assert tracefile.idle_by_span(rec, t0, t1) == {"none": want} and want > 0
    assert tracefile.host_split(rec, t0, t1) is None


def test_program_spans_leave_the_trace_readers_as_they_were(traced):
    """The readers of the device trace read the same numbers whether or not
    the record holds the program's spans and JAX's compile events."""
    rec, t0, t1 = traced
    bare = dict(rec, host=[e for e in rec["host"]
                           if e[0].startswith(tracefile.HOST_PREFIX)])

    class Run:
        def __init__(self, r):
            self.trace = TraceView(rec=r, t0_ns=t0, t1_ns=t1, perf_open=0.0)

    idle = reader("device.idle_share")
    assert idle(Run(rec)) == idle(Run(bare))
    assert tracefile.busy_ns(rec, t0, t1) == tracefile.busy_ns(bare, t0, t1)
    assert tracefile.top_ops(rec, t0, t1) == tracefile.top_ops(bare, t0, t1)
    assert (sum(ns for _, ns in tracefile.idle_gaps(rec, t0, t1))
            == sum(ns for _, ns in tracefile.idle_gaps(bare, t0, t1)))


class _TracedRun:
    def __init__(self, rec, t0, t1):
        self.trace = TraceView(rec=rec, t0_ns=t0, t1_ns=t1, perf_open=0.0)
        self.notes = {"host_split": tracefile.host_split(rec, t0, t1)}


def test_host_share_readers_and_the_rest_are_the_idle_inside_steps(traced):
    rec, t0, t1 = traced
    run = _TracedRun(rec, t0, t1)
    shares = [reader(g)(run) for g in tracefile.HOST_GROUPS]
    split = run.notes["host_split"]
    assert shares == [split[g] for g in tracefile.HOST_GROUPS]
    want = 100.0 * _idle_inside_steps(rec, t0, t1) / split["inside_ns"]
    assert sum(shares) + split["rest"] == pytest.approx(want, rel=1e-12)
    # every operation of this window ran inside a step, so
    # ``device.idle_share`` reads the same idle
    assert reader("device.idle_share")(run) == pytest.approx(want, abs=0.3)
    assert all(v >= 0 for v in shares)
    counts = split["span_counts"]
    assert counts["ps.step"] > 0
    assert all(n.startswith(tracefile.PROGRAM_PREFIX) for n in counts)


@pytest.mark.parametrize("name", list(tracefile.HOST_GROUPS))
def test_host_share_readers_read_nothing_without_program_spans(name):
    rec, t0, t1 = _load("trace_v5e.json.gz")
    assert reader(name)(_TracedRun(rec, t0, t1)) is None

    class Untraced:
        trace, notes = None, {}
    assert reader(name)(Untraced()) is None


@pytest.mark.parametrize("before,after,want", [
    ({"computed": 100, "duplicated": 10}, {"computed": 500, "duplicated": 210},
     50.0),
    ({"computed": 100, "duplicated": 10}, {"computed": 100, "duplicated": 10},
     None),
    ({"computed": 100, "duplicated": None},
     {"computed": 500, "duplicated": None}, None),
])
def test_duplicate_prefill_share_over_the_window(before, after, want):
    class Counted:
        stats_open, stats_close = before, after
    assert reader("sched.duplicate_prefill_share")(Counted()) == want


def test_extract_keeps_program_spans_and_compile_events(tmp_path):
    """A trace taken here keeps the benchmark's and the program's spans and
    JAX's compile events, and no other host event."""
    import glob

    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("ps.run.probe"):
                jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
            with jax.profiler.TraceAnnotation("other.span"):
                pass
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e[0] for e in tracefile.extract(path)["host"]}
    assert {"bench.window", "ps.run.probe"} <= names
    assert names & set(tracefile.JAX_COMPILE_EVENTS)
    assert all(n.startswith(tracefile.KEPT_HOST) for n in names)
    assert "other.span" not in names


def test_traced_rehearsal_splits_a_tiny_cell_on_the_cpu(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "src", tmp_path / "src")
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(DATA / "tiny.json", tmp_path / "bench" / "configs"
                / "tiny.json")
    shutil.copy(DATA / "tiny-fanout.json", tmp_path / "bench" / "traffic"
                / "tiny-fanout.json")
    bm["configs"].append({"name": "tiny", "source": "test",
                          "file": "bench/configs/tiny.json", "reduced": [],
                          "why": "test"})
    bm["workloads"].append({"name": "tiny.tiny-fanout", "config": "tiny",
                            "traffic": "tiny-fanout", "chips": 1,
                            "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    dump = tmp_path / "dump"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "tiny.tiny-fanout",
         "--seed", "2147483701", "--seconds", "2", "--trace", "1",
         "--rehearse", "--dump", str(dump)],
        cwd=tmp_path, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 3, p.stderr[-3000:]
    summary = json.loads((dump / "summary.json").read_text())
    split = summary["notes"]["host_split"]
    assert split["idle_ns"] > 0
    shares = [split[g] for g in tracefile.HOST_GROUPS]
    assert sum(shares) + split["rest"] == \
        pytest.approx(100.0 * split["idle_ns"] / split["inside_ns"])
    assert split["span_counts"]["ps.step"] > 0
    metrics = summary["result"]["metrics"]
    assert [metrics[g]["value"] for g in tracefile.HOST_GROUPS] == shares
    assert metrics["sched.duplicate_prefill_share"]["value"] > 0


def test_step_programs_carry_their_names(traced):
    """The decode plane's program is ``jit_decode_step`` and runs
    ``paged_decode``; the chunk step's is ``jit_chunk_step`` and runs
    ``flash_prefill_paged``."""
    from bench.measure import STEP_KERNELS
    rec, _, _ = traced
    kinds = tracefile.classify_programs(rec, STEP_KERNELS)
    seen = set()
    for (kind, *_), (name, *_) in zip(kinds, rec["modules"]):
        program = name.split("(")[0]
        assert kind == {"jit_decode_step": "decode",
                        "jit_chunk_step": "chunk"}.get(program), name
        seen.add(program)
    assert {"jit_decode_step", "jit_chunk_step"} <= seen
