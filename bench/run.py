#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, one process per chip. ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics, read from the
profiler's trace of the window. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` a ``breakdown``, and last the ``checks``: each number compared,
with its limit. The checks are also the last lines of standard error.

Without a TPU it exits 2 and prints no result. ``--rehearse`` (for tests)
runs the whole cell on any backend and then exits 3 without a result line:
a run off the chip has no device metrics to print.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

EXIT_NO_CHIP, EXIT_REHEARSAL = 2, 3


def metrics_of(run, entries) -> dict:
    from bench.spec import reader
    out = {}
    for m in entries:
        v = reader(m["name"])(run)
        if v is not None and math.isfinite(v):
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def breakdown_of(run) -> dict | None:
    from bench import tracefile
    tv = run.trace
    if tv is None:
        return None
    ops = tracefile.top_ops(tv.rec, tv.t0_ns, tv.t1_ns)
    gaps = tracefile.idle_gaps(tv.rec, tv.t0_ns, tv.t1_ns)[:10]
    return {"device_ops": [[n, ns / 1e9] for n, ns in ops],
            "idle_gaps": [[n, ns / 1e9] for n, ns in gaps]}


def result(run, checks, cell, trace: bool, devices) -> dict:
    from bench import tracefile
    limit = checks["limit"]
    failed = sum(1 for r in run.window if not r.finished)
    correct = (limit is not None
               and checks["sampled_tokens"] >= checks["sampled_tokens_min"]
               and checks["logit_gap"] <= limit)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.notes["memory_peak_bytes"]}
    out = {"correct": bool(correct), "attempted": len(run.window),
           "failed": failed,
           "metrics": metrics_of(run, cell.per_layer if trace
                                 else cell.end_to_end),
           "device": device}
    if trace and run.trace is not None:
        tv = run.trace
        device["busy_s"] = tracefile.busy_ns(tv.rec, tv.t0_ns, tv.t1_ns) / 1e9
        device["window_s"] = (tv.t1_ns - tv.t0_ns) / 1e9
        out["breakdown"] = breakdown_of(run)
    out["checks"] = {
        "logit_gap": {"value": checks["logit_gap"], "limit": limit},
        "sampled_tokens": {"value": checks["sampled_tokens"],
                           "limit": checks["sampled_tokens_min"]},
    }
    return out


def dump(run, checks, out, directory) -> None:
    """Write the run's compact trace and a summary under ``directory``."""
    from bench import tracefile
    os.makedirs(directory, exist_ok=True)
    summary = {"result": out, "checks": checks, "notes": run.notes,
               "setup_s": run.setup_s, "attempted": len(run.window)}
    if run.trace is not None:
        tv = run.trace
        tracefile.save(tv.rec, os.path.join(directory, "trace.json.gz"))
        names = {}
        for e in tv.rec["ops"]:
            key = e[0] + " | " + e[4]
            names[key] = names.get(key, 0) + e[2]
        summary["op_names"] = sorted(names.items(), key=lambda kv: -kv[1])[:80]
        summary["modules"] = sorted({e[0] for e in tv.rec["modules"]})
        summary["window_ns"] = [tv.t0_ns, tv.t1_ns]
    with open(os.path.join(directory, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1, default=str)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--dump", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from bench.spec import cell as find_cell
    harness.setup_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.rehearse:
        print(f"bench: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return EXIT_NO_CHIP
    cell = find_cell(args.workload)
    if len(devices) < cell.chips and not args.rehearse:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return EXIT_NO_CHIP
    run, checks = harness.run_cell(cell, args.seed, args.seconds,
                                   bool(args.trace), T_START,
                                   log_compiles=bool(args.dump))
    out = result(run, checks, cell, bool(args.trace), devices)
    if args.dump:
        dump(run, checks, out, args.dump)
    print(f"bench: notes {json.dumps(run.notes, default=str)}",
          file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr)
    if args.rehearse or devices[0].platform != "tpu":
        print("bench: rehearsal off the chip; no device metrics printed",
              file=sys.stderr)
        return EXIT_REHEARSAL
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
