"""From the profiler's trace to the intervals the per-layer metrics read.

``extract`` reads an ``.xplane.pb`` into a compact record: the device's
operations and programs (one entry per execution, with its start and
duration in nanoseconds on the trace's clock) and the benchmark's own host
spans (``bench.*``). The rest works on that record, so a small trace saved
from the chip checks the reduction in a test.
"""
from __future__ import annotations

import gzip
import json
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PREFIX = "bench."
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
                                if e.name.startswith(HOST_PREFIX)]
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


def idle_gaps(rec: dict, t0: int, t1: int, device: int = 0,
              where: bool = False) -> list:
    """Every stretch of [t0, t1) with no operation on the device, longest
    first, each named by the innermost benchmark host span open at its
    middle ("none" when none but the window's is open): [(name, ns)], or with ``where``
    [(name, ns, start)]."""
    busy = union(clip([e for e in rec["ops"] if e[3] == device], t0, t1))
    gaps, at = [], t0
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if at < t1:
        gaps.append((at, t1))
    out = []
    for a, b in gaps:
        mid = (a + b) // 2
        open_ = [e for e in rec["host"]
                 if e[1] <= mid < e[1] + e[2] and e[0] != WINDOW_SPAN]
        name = min(open_, key=lambda e: e[2])[0] if open_ else "none"
        out.append((name, b - a, a) if where else (name, b - a))
    out.sort(key=lambda g: -g[1])
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
