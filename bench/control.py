#!/usr/bin/env python3
"""Read the two ends of a cell's correctness limit on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, in one process: the cell's deployment serves a short window
of its own traffic at its own rate; then the float32 reference runs over a
seeded sample of the finished requests, as in a benchmark run, and so does
the control: the same reference computed through float8 (e4m3), one step
below the program's bf16. Each seed prints one line with the program's
widest logit gap (the lower reading) and the control's (the upper one), and
the verdict of the benchmark's own comparison on each: ``correct`` for the
program, ``control_correct`` with the control in the program's place, which
has to be false. The benchmark's own runs never run the control.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from bench import model as bmodel
    from bench.run import result
    from bench.spec import cell as find_cell
    harness.setup_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    cell = find_cell(args.workload)
    conf = bmodel.load(cell.config_path)
    traffic = bmodel.load(cell.traffic_path)
    lines = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        dep = harness.Deployment(conf, traffic, seed, t0, shape_warmup=False)
        run = dep.serve(traffic, args.seconds, False, t0, name=cell.name)
        dep.close()
        del dep
        checks = harness.with_limits(
            conf, harness.check(conf, seed, run, control=True))
        program = result(run, checks, cell, False, devices)
        control = result(run, harness.as_control(checks), cell, False,
                         devices)
        line = {"seed": seed, "correct": program["correct"],
                "control_correct": control["correct"], **checks,
                "attempted": len(run.window),
                "failed": sum(not r.finished for r in run.window),
                "seconds": time.perf_counter() - t0}
        print(json.dumps(line), flush=True)
        lines.append(line)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(lines, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
