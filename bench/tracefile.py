"""From the profiler's trace to the intervals the per-layer metrics read.

``extract`` reads an ``.xplane.pb`` into a compact record: the device's
operations and programs (one entry per execution, with its start and
duration in nanoseconds on the trace's clock) and the host spans the
readers name: the benchmark's own (``bench.*``), the program's
(``ps.*``) and JAX's own around making a program ready
(``JAX_COMPILE_EVENTS``). The rest works on that record, so a small trace
saved from the chip checks the reduction in a test.

``innermost`` names each stretch of a window by the innermost host span open
over it, the one opened last; ``idle_gaps`` names the device's idle gaps by
it, and ``host_split`` sums the device's idle time inside ``bench.step`` by
the program's innermost span into the four ``host.*_idle_share`` groups.
"""
from __future__ import annotations

import gzip
import json
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PREFIX = "bench."
PROGRAM_PREFIX = "ps."
#: JAX's own host events around making a program ready, inside
#: ``ps.run.<program>`` on a v5e trace of the engine: jax 0.9 opens them with
#: ``profiler.annotate_function`` in ``interpreters/partial_eval.py``,
#: ``interpreters/pxla.py`` and ``compiler.py``. A jit's own tracing and a
#: load from the persistent compile cache open none, so they stay dispatch.
JAX_COMPILE_EVENTS = ("trace_to_jaxpr_dynamic", "trace_to_jaxpr_nounits",
                      "lower_sharding_computation", "backend_compile",
                      "backend_compile_and_load")
#: host events ``extract`` keeps, by the start of their name
KEPT_HOST = (HOST_PREFIX, PROGRAM_PREFIX) + JAX_COMPILE_EVENTS
WINDOW_SPAN = "bench.window"          # holds every other span
HINT_STATS = ("tf_op", "long_name", "hlo_op", "kernel_details")


def _hint(event) -> str:
    stats = dict((k, v) for k, v in event.stats)
    for key in HINT_STATS:
        v = stats.get(key)
        if isinstance(v, str) and v:
            return v[:160]
    return ""


def extract(path: str) -> dict:
    """The compact record of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    rec = {"ops": [], "modules": [], "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    rec["ops"] += [[e.name, int(e.start_ns), int(e.duration_ns),
                                    dev, _hint(e)] for e in line.events]
                elif line.name == MODULES_LINE:
                    rec["modules"] += [[e.name, int(e.start_ns),
                                        int(e.duration_ns), dev]
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                rec["host"] += [[e.name, int(e.start_ns), int(e.duration_ns)]
                                for e in line.events
                                if e.name.startswith(KEPT_HOST)]
    for key in rec:
        rec[key].sort(key=lambda e: e[1])
    return rec


def save(rec: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(rec, f, separators=(",", ":"))


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def clip(events, t0: int, t1: int):
    """(start, end) of each event, cut to [t0, t1); empty ones dropped."""
    out = []
    for e in events:
        a, b = max(e[1], t0), min(e[1] + e[2], t1)
        if b > a:
            out.append((a, b))
    return out


def union(intervals) -> list:
    """Merged, sorted, disjoint intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [tuple(m) for m in merged]


def busy_ns(rec: dict, t0: int, t1: int, device: int = 0) -> int:
    """Nanoseconds of [t0, t1) in which some operation ran on ``device``."""
    ops = [e for e in rec["ops"] if e[3] == device]
    return sum(b - a for a, b in union(clip(ops, t0, t1)))


def matches(op, kernel: str) -> bool:
    pat = re.compile(rf"(^|[^A-Za-z0-9_]){re.escape(kernel)}($|[^A-Za-z0-9_])")
    return bool(pat.search(op[0]) or pat.search(op[4]))


def kernel_ns(rec: dict, kernel: str, t0: int, t1: int) -> int:
    """Device nanoseconds of ``kernel``'s executions inside [t0, t1)."""
    return sum(b - a for a, b in clip([e for e in rec["ops"]
                                       if matches(e, kernel)], t0, t1))


def classify_programs(rec: dict, kinds: dict) -> list:
    """Each program execution with the kind of step it is: the first kind
    (in ``kinds``' order, kind -> kernel name) whose kernel ran inside it,
    or None. Returns [(kind, start, end, device)]."""
    marks = {kind: sorted(e[1] for e in rec["ops"] if matches(e, k))
             for kind, k in kinds.items()}
    out = []
    for name, start, dur, dev in rec["modules"]:
        end = start + dur
        kind = None
        for k, starts in marks.items():
            i = _first_at_or_after(starts, start)
            if i < len(starts) and starts[i] < end:
                kind = k
                break
        out.append((kind, start, end, dev))
    return out


def _first_at_or_after(xs, x) -> int:
    lo, hi = 0, len(xs)
    while lo < hi:
        mid = (lo + hi) // 2
        if xs[mid] < x:
            lo = mid + 1
        else:
            hi = mid
    return lo


def program_ns(rec: dict, kinds: dict, kind: str, t0: int, t1: int) -> int:
    progs = [(None, a, b - a) for k, a, b, _ in classify_programs(rec, kinds)
             if k == kind]
    return sum(b - a for a, b in clip(progs, t0, t1))


def host_ns(rec: dict, name: str, t0: int, t1: int) -> int:
    return sum(b - a for a, b in clip([e for e in rec["host"]
                                       if e[0] == name], t0, t1))


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


def innermost(spans: list, t0: int, t1: int) -> list:
    """[t0, t1) cut where any of ``spans`` opens or closes: (start, end,
    name of the innermost span open, the one opened last, or "none")."""
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


def idle_gaps(rec: dict, t0: int, t1: int, device: int = 0,
              where: bool = False) -> list:
    """Every stretch of [t0, t1) with no operation on the device, longest
    first, each named by the ``innermost`` host span kept by ``extract``
    open at its middle ("none" when none but the window's is open):
    [(name, ns)], or with ``where`` [(name, ns, start)]."""
    busy = union(clip([e for e in rec["ops"] if e[3] == device], t0, t1))
    gaps, at = [], t0
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if at < t1:
        gaps.append((at, t1))
    segs = innermost([e for e in rec["host"] if e[0] != WINDOW_SPAN], t0, t1)
    k, out = 0, []
    for a, b in gaps:                   # their middles ascend
        mid = (a + b) // 2
        while segs[k][1] <= mid:
            k += 1
        out.append((segs[k][2], b - a, a) if where else (segs[k][2], b - a))
    out.sort(key=lambda g: -g[1])
    return out


def idle_by_span(rec: dict, t0: int, t1: int, inside: str = "bench.step",
                 device: int = 0) -> dict:
    """Every nanosecond of [t0, t1) in which ``device`` runs no operation
    and the host is inside ``inside``, by the ``innermost`` span of the
    program or of JAX's compiler open then ("none" where none is):
    {name: ns}."""
    busy = union(clip([e for e in rec["ops"] if e[3] == device], t0, t1))
    steps = union(clip([e for e in rec["host"] if e[0] == inside], t0, t1))
    idle = _minus(steps, busy)
    spans = [e for e in rec["host"] if not e[0].startswith(HOST_PREFIX)]
    out, j = defaultdict(int), 0
    for a, b, name in innermost(spans, t0, t1):
        while j < len(idle) and idle[j][1] <= a:
            j += 1
        k = j
        while k < len(idle) and idle[k][0] < b:
            out[name] += min(b, idle[k][1]) - max(a, idle[k][0])
            k += 1
    return dict(out)


#: the four ``host.*_idle_share`` groups, each a test on the innermost span
HOST_GROUPS = {
    "host.sched_idle_share": lambda n: n.startswith("ps.sched."),
    "host.prep_idle_share": lambda n: n in ("ps.chunk.build",
                                            "ps.decode.build",
                                            "ps.decode.push"),
    "host.dispatch_idle_share": lambda n: (n.startswith("ps.run.")
                                           or n in ("ps.chunk.wait",
                                                    "ps.decode.readback")),
    "host.compile_idle_share": lambda n: n in JAX_COMPILE_EVENTS,
}


def host_split(rec: dict, t0: int, t1: int) -> dict | None:
    """The shares (%) of the time inside ``bench.step`` in [t0, t1) in which
    the device idles under each of ``HOST_GROUPS`` and under none of them
    (``rest``: ``ps.step`` alone, or no span of the program), the ns they
    are shares of, and how often each span of the program opened; None
    where no span of the program opened in [t0, t1)."""
    counts = defaultdict(int)
    for name, s, _ in rec["host"]:
        if t0 <= s < t1 and name.startswith(PROGRAM_PREFIX):
            counts[name] += 1
    if not counts:
        return None
    inside = host_ns(rec, "bench.step", t0, t1)
    by = idle_by_span(rec, t0, t1)
    ns = {g: sum(v for n, v in by.items() if test(n))
          for g, test in HOST_GROUPS.items()}
    ns["rest"] = sum(by.values()) - sum(ns.values())
    out = {g: 100.0 * v / inside if inside else None for g, v in ns.items()}
    out.update(inside_ns=inside, idle_ns=sum(by.values()),
               by_span=sorted(by.items(), key=lambda kv: -kv[1]),
               span_counts=dict(counts))
    return out


#: operations that only hold others (a layer scan's loop): their time is
#: their children's
CONTAINERS = {"while", "conditional", "call"}


def short_name(op_name: str) -> str:
    """``%paged_decode.16 = bf16[...] custom-call(...)`` -> ``paged_decode``."""
    head = op_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)


def top_ops(rec: dict, t0: int, t1: int, n: int = 10) -> list:
    """The device operations that took the most time in [t0, t1), grouped
    by short name, loops left out: [(name, ns)]."""
    total = defaultdict(int)
    for e in rec["ops"]:
        name = short_name(e[0])
        a, b = max(e[1], t0), min(e[1] + e[2], t1)
        if b > a and name not in CONTAINERS:
            total[name] += b - a
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]
