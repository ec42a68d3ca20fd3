#!/usr/bin/env python3
"""The engine's own host spans on the device trace's clock, and the device's
idle time inside ``engine.step`` split by them.

The program opens ``ps.*`` profiler spans around the phases of its step and
``ps.run.<program>`` around each program's dispatch; JAX opens its own
around tracing, lowering and compiling (``JAX_COMPILE_EVENTS``). ``extract``
keeps those beside the benchmark's ``bench.*`` spans; ``idle_by_span``
names every nanosecond of [t0, t1) in which the device idles inside
``bench.step`` by the innermost of them; ``host_split`` sums that into four
shares of the time spent in ``bench.step``, plus the rest (idle under
``ps.step`` alone, or under no span of the program).

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s> [--out DIR]

runs one cell traced, as ``bench/run.py --trace 1`` does, and prints that
result line with the split, the duplicate-prefill share of the window and
the idle gaps named by span added to it. ``--out`` keeps the compact trace
and a summary there. Off the chip, ``--rehearse`` runs the cell and prints
the split alone, then exits 3.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import defaultdict  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import tracefile  # noqa: E402

PROGRAM_PREFIX = "ps."
#: JAX's own host events around making a program ready, inside
#: ``ps.run.<program>`` on a v5e trace of the engine: jax 0.9 opens them with
#: ``profiler.annotate_function`` in ``interpreters/partial_eval.py``,
#: ``interpreters/pxla.py`` and ``compiler.py``. A jit's own tracing and a
#: load from the persistent compile cache open none, so they stay dispatch.
JAX_COMPILE_EVENTS = ("trace_to_jaxpr_dynamic", "trace_to_jaxpr_nounits",
                      "lower_sharding_computation", "backend_compile",
                      "backend_compile_and_load")
KEPT_PREFIXES = (tracefile.HOST_PREFIX, PROGRAM_PREFIX) + JAX_COMPILE_EVENTS

#: the four shares of ``host_split``, each a test on the innermost span
GROUPS = {
    "host.sched_idle_share": lambda n: n.startswith("ps.sched."),
    "host.prep_idle_share": lambda n: n in ("ps.chunk.build",
                                            "ps.decode.build",
                                            "ps.decode.push"),
    "host.dispatch_idle_share": lambda n: (n.startswith("ps.run.")
                                           or n in ("ps.chunk.wait",
                                                    "ps.decode.readback")),
    "host.compile_idle_share": lambda n: n in JAX_COMPILE_EVENTS,
}

_BASE_EXTRACT = tracefile.extract


def extract(path: str) -> dict:
    """``tracefile.extract`` that also keeps the program's ``ps.*`` spans
    and JAX's compile events among the host events."""
    saved = tracefile.HOST_PREFIX
    tracefile.HOST_PREFIX = KEPT_PREFIXES     # str.startswith takes a tuple
    try:
        return _BASE_EXTRACT(path)
    finally:
        tracefile.HOST_PREFIX = saved


def _minus(outer: list, inner: list) -> list:
    """Parts of the disjoint sorted intervals ``outer`` that no interval of
    the disjoint sorted ``inner`` covers."""
    out, j = [], 0
    for a, b in outer:
        while j < len(inner) and inner[j][1] <= a:
            j += 1
        k, at = j, a
        while k < len(inner) and inner[k][0] < b:
            if inner[k][0] > at:
                out.append((at, inner[k][0]))
            at = max(at, inner[k][1])
            k += 1
        if at < b:
            out.append((at, b))
    return out


def _segments(spans: list, t0: int, t1: int) -> list:
    """[t0, t1) cut where any span opens or closes: (start, end, name of the
    innermost open span, the one opened last, or "none")."""
    pts = [(t0, 1, -1), (t1, 1, -1)]
    for i, (_, s, d) in enumerate(spans):
        if d > 0:
            pts += [(s, 1, i), (s + d, 0, i)]
    pts.sort()
    open_, out = set(), []
    for (t, kind, i), (nxt, _, _) in zip(pts, pts[1:] + [(t1, 0, -1)]):
        if i >= 0:
            (open_.add if kind else open_.discard)(i)
        a, b = max(t, t0), min(nxt, t1)
        if b > a:
            name = (spans[max(open_, key=lambda j: (spans[j][1],
                                                    -spans[j][2]))][0]
                    if open_ else "none")
            out.append((a, b, name))
    return out


def idle_by_span(rec: dict, t0: int, t1: int, inside: str = "bench.step",
                 device: int = 0) -> dict:
    """Every nanosecond of [t0, t1) in which ``device`` runs no operation
    and the host is inside ``inside``, by the innermost span of the program
    or of JAX's compiler open then ("none" where none is): {name: ns}."""
    busy = tracefile.union(tracefile.clip(
        [e for e in rec["ops"] if e[3] == device], t0, t1))
    steps = tracefile.union(tracefile.clip(
        [e for e in rec["host"] if e[0] == inside], t0, t1))
    idle = _minus(steps, busy)
    spans = [e for e in rec["host"]
             if not e[0].startswith(tracefile.HOST_PREFIX)]
    out, j = defaultdict(int), 0
    for a, b, name in _segments(spans, t0, t1):
        while j < len(idle) and idle[j][1] <= a:
            j += 1
        k = j
        while k < len(idle) and idle[k][0] < b:
            out[name] += min(b, idle[k][1]) - max(a, idle[k][0])
            k += 1
    return dict(out)


def host_split(rec: dict, t0: int, t1: int) -> dict:
    """The four shares (%) of the time inside ``bench.step`` in which the
    device idles under each group of spans, the rest, and the ns they are
    shares of."""
    inside = tracefile.host_ns(rec, "bench.step", t0, t1)
    by = idle_by_span(rec, t0, t1)
    ns = {g: sum(v for n, v in by.items() if test(n))
          for g, test in GROUPS.items()}
    ns["rest"] = sum(by.values()) - sum(ns.values())
    out = {g: 100.0 * v / inside if inside else None for g, v in ns.items()}
    out.update(inside_ns=inside, idle_ns=sum(by.values()),
               by_span=sorted(by.items(), key=lambda kv: -kv[1]))
    return out


def annotation_ns(n: int = 200_000) -> float:
    """Mean nanoseconds of entering and leaving one profiler span."""
    import jax
    t = time.perf_counter_ns()
    for _ in range(n):
        with jax.profiler.TraceAnnotation("ps.cost"):
            pass
    return (time.perf_counter_ns() - t) / n


def _window_counts(rec: dict, t0: int, t1: int) -> dict:
    counts = defaultdict(int)
    for name, s, _ in rec["host"]:
        if t0 <= s < t1 and name.startswith(PROGRAM_PREFIX):
            counts[name] += 1
    return dict(counts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from bench import run as brun
    from bench.spec import cell as find_cell
    harness.setup_compile_cache()
    devices = jax.devices()
    on_chip = devices[0].platform == "tpu"
    if not on_chip and not args.rehearse:
        print(f"spans: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return brun.EXIT_NO_CHIP
    cost_off = annotation_ns()
    tracefile.extract = extract
    stats = harness._stats
    harness._stats = lambda engine: {
        **stats(engine),
        "duplicated": getattr(engine.stats, "prefill_tokens_duplicated",
                              None)}
    cell = find_cell(args.workload)
    run, checks = harness.run_cell(cell, args.seed, args.seconds, True,
                                   T_START)
    tv = run.trace
    split = host_split(tv.rec, tv.t0_ns, tv.t1_ns)
    a, b = run.stats_open, run.stats_close
    computed = b["computed"] - a["computed"]
    counts = _window_counts(tv.rec, tv.t0_ns, tv.t1_ns)
    extra = {
        "split": split,
        "duplicate_prefill_share": (
            100.0 * (b["duplicated"] - a["duplicated"]) / computed
            if b["duplicated"] is not None and computed > 0 else None),
        "spans_per_step": (sum(counts.values()) / counts["ps.step"]
                           if counts.get("ps.step") else None),
        "span_counts": counts,
        "annotation_ns": {"profiler_off": cost_off},
        "idle_gaps": tracefile.idle_gaps(tv.rec, tv.t0_ns, tv.t1_ns)[:20],
    }
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        tracefile.save(tv.rec, os.path.join(args.out, "trace.json.gz"))
        with open(os.path.join(args.out, "window.json"), "w") as f:
            json.dump({"t0_ns": tv.t0_ns, "t1_ns": tv.t1_ns}, f)
    if not on_chip:
        print(json.dumps(extra, default=str), flush=True)
        return brun.EXIT_REHEARSAL
    out = brun.result(run, checks, cell, True, devices)
    out["end_to_end_traced"] = brun.metrics_of(run, cell.end_to_end)
    cost_dir = tempfile.mkdtemp(prefix="spans_cost_")
    jax.profiler.start_trace(cost_dir)
    extra["annotation_ns"]["profiler_on"] = annotation_ns()
    jax.profiler.stop_trace()
    shutil.rmtree(cost_dir, ignore_errors=True)
    out.update(extra)
    print(json.dumps(out, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
