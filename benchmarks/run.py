"""Benchmark harness — one entry per paper table/figure.

Usage: PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]

Prints one ``name,us_per_call,derived`` CSV line per benchmark (us_per_call =
wall time of the bench; derived = its headline metric), plus each benchmark's
own CSV block. The heavy training benches (fig2/table1) run in quick mode by
default; --full runs paper-scale sweeps.
"""
from __future__ import annotations

import sys
import time

sys.path.insert(0, "src")


def _bench_fig3(quick):
    from benchmarks import fig3_serving
    rows = fig3_serving.main(quick=quick)
    hi = max(r["rate"] for r in rows)
    b = next(r for r in rows if r["rate"] == hi and r["mode"] == "baseline"
             and r["pattern"] == "react")
    p = next(r for r in rows if r["rate"] == hi and r["mode"] == "prefillshare"
             and r["pattern"] == "react")
    return f"p95_speedup={b['p95_e2e_s'] / p['p95_e2e_s']:.2f}x"


def _bench_fig4(quick):
    from benchmarks import fig4_concurrency
    rows = fig4_concurrency.main(quick=quick)
    ps = [r for r in rows if r["mode"] == "prefillshare"]
    return f"ps_hit_ratio={max(r['prefix_hit_ratio'] for r in ps):.2f}"


def _bench_memory(quick):
    from benchmarks import memory_model
    rows = memory_model.main(quick=quick)
    return f"mem_ratio_4models={rows[1]['ratio']:.2f}x"


def _bench_fig2(quick):
    from benchmarks import fig2_sharing
    rows = fig2_sharing.main(quick=quick)
    full_at_1 = next(r for r in rows if r["ratio"] == 1.0)
    return (f"naive@1.0={full_at_1['full_ft']:.2f},"
            f"ps@1.0={full_at_1['prefillshare']:.2f}")


def _bench_table1(quick):
    from benchmarks import table1_accuracy
    rows = table1_accuracy.main(quick=quick)
    r = rows[0]
    return (f"fullft={r['full_ft_selfcache']:.2f},"
            f"ps={r['prefillshare']:.2f}")


def _bench_b2(quick):
    from benchmarks import b2_alternatives
    rows = b2_alternatives.main(quick=quick)
    hi = max(r["rate"] for r in rows)
    best = max((r for r in rows if r["rate"] == hi),
               key=lambda r: r["throughput_tok_s"])
    return f"best_policy={best['policy']}"


def _bench_roofline(quick):
    from benchmarks import roofline
    rows = roofline.analyze()
    ok = [r for r in rows if "error" not in r and "skipped" not in r]
    if not ok:
        return "no-dryrun-data"
    doms = [r["dominant"] for r in ok]
    return f"combos={len(ok)},compute_bound={doms.count('compute')}"


def _bench_kernels(quick):
    import jax
    import jax.numpy as jnp
    from repro.kernels.ops import flash_attention
    from repro.kernels.ref import ref_flash_prefill
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 256, 8, 64))
    k = jax.random.normal(key, (1, 256, 4, 64))
    o = flash_attention(q, k, k, interpret=True)
    r = ref_flash_prefill(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          k.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
    return f"flash_maxerr={float(jnp.abs(o - r).max()):.1e}"


BENCHES = [
    ("fig3_serving", _bench_fig3),
    ("fig4_concurrency", _bench_fig4),
    ("memory_model_eq8_9", _bench_memory),
    ("b2_alternatives_beyond_paper", _bench_b2),
    ("roofline", _bench_roofline),
    ("kernels_allclose", _bench_kernels),
    ("fig2_sharing", _bench_fig2),
    ("table1_accuracy", _bench_table1),
]


def main() -> None:
    quick = "--full" not in sys.argv
    only = None
    if "--only" in sys.argv:
        only = sys.argv[sys.argv.index("--only") + 1]
    summary = []
    for name, fn in BENCHES:
        if only and only != name:
            continue
        print(f"\n===== {name} =====", flush=True)
        t0 = time.time()
        derived = fn(quick)          # a failed bench fails the run
        us = (time.time() - t0) * 1e6
        summary.append((name, us, derived))
    print("\nname,us_per_call,derived")
    for name, us, derived in summary:
        print(f"{name},{us:.0f},{derived}")


if __name__ == "__main__":
    main()
