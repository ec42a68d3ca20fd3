"""Serving launcher: run the disaggregated simulator (production cost terms)
or the real-JAX local engine, from the CLI.

  PYTHONPATH=src python -m repro.launch.serve --arch llama31-8b \
      --pattern react --rate 4 --mode prefillshare
  PYTHONPATH=src python -m repro.launch.serve --engine local --gen 8
"""
from __future__ import annotations

import argparse
import json


def run_sim(args):
    from repro.configs.base import get_config
    from repro.serving.simulator import ServingConfig, Simulator
    from repro.serving.workload import make_sessions

    cfg = get_config(args.arch)
    sessions = make_sessions(args.pattern, n_sessions=args.sessions,
                             arrival_rate=args.rate, seed=args.seed)
    scfg = ServingConfig(mode=args.mode, max_concurrent=args.max_concurrent,
                         chips_per_worker=args.chips,
                         hbm_per_worker=args.chips * 16e9)
    sim = Simulator(cfg, scfg, sessions)
    print(json.dumps(sim.run(), indent=1))


def run_engine(args):
    import jax
    import numpy as np
    from repro.configs.base import ModelConfig
    from repro.models import init_params
    from repro.runtime import setup_compile_cache
    from repro.serving.api import SamplingParams
    from repro.serving.engine import LocalDisaggEngine

    cfg = ModelConfig(name="local", arch_type="dense", n_layers=3,
                      d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      vocab_size=64, dtype="float32")
    setup_compile_cache()
    base = init_params(cfg, jax.random.PRNGKey(0))
    decs = {f"agent{i}": init_params(cfg, jax.random.PRNGKey(3 + i))
            for i in range(args.agents)}
    eng = LocalDisaggEngine(cfg, base, capacity=512)
    for mid, p in decs.items():
        eng.models.register(mid, p)
    rng = np.random.default_rng(0)
    ctx = list(rng.integers(4, 60, size=32))
    for turn in range(args.turns):
        for a in decs:
            ctx += list(rng.integers(4, 60, size=8))
            out = eng.generate(a, ctx, SamplingParams(max_tokens=args.gen),
                               session=0).result()
            ctx += list(out)
            print(f"turn {turn} {a}: ctx={len(ctx)} gen={out.tolist()}")
    s = eng.stats
    print(f"hit_ratio={s.hit_ratio:.3f} handoff_mb={s.handoff_bytes / 1e6:.2f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", choices=["sim", "local"], default="sim")
    ap.add_argument("--arch", default="llama31-8b")
    ap.add_argument("--pattern", default="react")
    ap.add_argument("--mode", default="prefillshare",
                    choices=["baseline", "prefillshare"])
    ap.add_argument("--rate", type=float, default=4.0)
    ap.add_argument("--sessions", type=int, default=80)
    ap.add_argument("--max-concurrent", type=int, default=64)
    ap.add_argument("--chips", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--agents", type=int, default=3)
    ap.add_argument("--turns", type=int, default=2)
    ap.add_argument("--gen", type=int, default=6)
    args = ap.parse_args()
    (run_engine if args.engine == "local" else run_sim)(args)


if __name__ == "__main__":
    main()
