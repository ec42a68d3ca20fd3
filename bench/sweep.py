#!/usr/bin/env python3
"""Find a cell's knee: offer its traffic at several fixed rates, one window
each, in one process with one deployment, and print a line per rate.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 0.05,0.1,0.2 [--out FILE]

Each line gives the offered and completed tokens per second, the tails, the
requests that did not finish in the drain, and the decode batches seen. The
highest rate whose window completes what it offers, with no request left
over and no growing tail, is the knee; a cell runs at a fixed rate below it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--traffic-override", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from bench import harness
    from bench import model as bmodel
    from bench.measure import first_of
    from bench.spec import cell as find_cell
    from bench.spec import reader
    harness.setup_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    cell = find_cell(args.workload)
    conf = bmodel.load(cell.config_path)
    traffic = bmodel.load(cell.traffic_path)
    if args.traffic_override:
        traffic.update(json.loads(args.traffic_override))
    dep = harness.Deployment(conf, traffic, args.seed, T_START)
    lines = []
    for rate in [float(r) for r in args.rates.split(",")]:
        t = {**traffic, "rate_sessions_per_s": rate}
        run = dep.serve(t, args.seconds, False, time.perf_counter(),
                        name=cell.name)
        offered = sum(r.max_tokens for r in run.window) / args.seconds
        line = {"rate": rate, "attempted": len(run.window),
                "failed": sum(not r.finished for r in run.window),
                "offered_tokens_per_s": offered,
                "drain_s": run.drain_end - run.t_close,
                "compiles_in_window": run.compiles_window,
                "ttft_p50_ms": 1e3 * float(np.median(
                    [first_of(run, r) - r.due for r in run.window])),
                "decode_steps": run.notes["decode_steps"]}
        for m in cell.end_to_end:
            if m["name"] != "setup_s":
                line[m["name"]] = reader(m["name"])(run)
        print(json.dumps(line), flush=True)
        lines.append(line)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(lines, f, indent=1)
    dep.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
