"""Batched paged decode vs the seed B=1 dense loop (acceptance benchmark),
plus the fused cross-model decode plane vs the per-model dispatch loop.

Same real models, same greedy outputs, execution paths:

  dense-B1  — the seed engine's path: dense per-session prefill, full-cache
              ``transfer_cache`` handoff copy, then a Python B=1 decode loop
              per sequence (one un-jitted forward per token per sequence).
  paged     — the paged data plane: pool prefill + zero-copy block-table
              handoff, then CONTINUOUS-BATCH decode (all sequences advance
              one token per jitted batched step over the shared page pool).

``--models N > 1`` adds the multi-model workload: N task-specific decoders
fan out over shared contexts, comparing

  per-model — one jitted forward per decode model per step (fused=False),
  fused     — stacked decoder params, ONE vmapped jitted forward per step
              for every active sequence of every model (serving/decode.py),

reporting dispatches/step and tokens/s for both, with greedy outputs
asserted identical.

``--adapters`` adds the weight-side memory comparison (Eq. 9, weight side):
the same N decode models registered as LoRA specs
(``engine.models.register(mid, DecodeModelSpec(lora=...))`` — one base copy
+ N stacked A/B factors, merged inside the jitted vmapped step) vs
registered as N materialized ``lora_apply`` full models. Reports decode-
plane weight bytes for both layouts (the N×full / (base + N·adapters) ratio
is asserted against the array shapes) and tok/s of the in-step merge vs the
materialized plane, with greedy outputs asserted bit-identical.

Usage: PYTHONPATH=src python -m benchmarks.paged_decode_bench
           [--batch 4] [--models 4] [--adapters] [--json PATH]
"""
from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, "src")

try:
    from bench_json import gate, write_bench_json
except ImportError:
    from benchmarks.bench_json import gate, write_bench_json

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.lora import LoRAPair, lora_apply, lora_init
from repro.models import init_params
from repro.runtime import program_traces
from repro.serving.api import SamplingParams
from repro.serving.engine import LocalDisaggEngine
from repro.serving.registry import DecodeModelSpec, LoRAAdapter

CFG = ModelConfig(name="bench", arch_type="dense", n_layers=3, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64,
                  dtype="float32")


def main(batch: int = 4, gen: int = 32, ctx_len: int = 48, seed: int = 0):
    base = init_params(CFG, jax.random.PRNGKey(0))
    decs = {"m0": init_params(CFG, jax.random.PRNGKey(7))}
    rng = np.random.default_rng(seed)
    ctxs = [list(rng.integers(4, 60, size=ctx_len + i)) for i in range(batch)]

    # --- paged continuous batching -----------------------------------
    eng = LocalDisaggEngine(CFG, base, num_pages=2048)
    for mid, p in decs.items():
        eng.models.register(mid, p)
    outs = [eng.generate("m0", c, SamplingParams(max_tokens=gen), session=sid)
            for sid, c in enumerate(ctxs)]
    t0 = time.perf_counter()
    eng.run()
    t_paged = time.perf_counter() - t0
    paged_out = [o.result() for o in outs]
    paged_tps = batch * gen / t_paged

    # --- seed path: dense handoff copy + B=1 loop --------------------
    dense = LocalDisaggEngine(CFG, base, capacity=1024, paged=False)
    for mid, p in decs.items():
        dense.models.register(mid, p)
    t_dense = 0.0
    dense_out = []
    for sid, c in enumerate(ctxs):
        sc = dense.prefill_workers[0].prefill(sid, c)   # not timed: decode bench
        from repro.kvcache.handoff import transfer_cache
        cache = transfer_cache(sc.cache)
        t0 = time.perf_counter()
        toks, _ = dense.decoders["m0"].generate(
            cache, sc.n_tokens, 2, SamplingParams(max_tokens=gen))
        dense_out.append(toks)
        t_dense += time.perf_counter() - t0
    dense_tps = batch * gen / t_dense

    for a, b in zip(paged_out, dense_out):
        np.testing.assert_array_equal(a, b)

    rows = [{"path": "dense-B1", "tok_s": dense_tps, "batch": 1},
            {"path": "paged-batched", "tok_s": paged_tps, "batch": batch}]
    print("path,batch,tok_s")
    for r in rows:
        print(f"{r['path']},{r['batch']},{r['tok_s']:.1f}")
    speedup = paged_tps / dense_tps
    print(f"# speedup={speedup:.2f}x (greedy outputs identical, "
          f"mean decode batch={eng.stats.decode_batch_mean:.1f}, "
          f"handoff_bytes={eng.stats.handoff_bytes})")
    return rows, speedup


def multi_model(n_models: int = 4, seqs_per_model: int = 2, gen: int = 32,
                ctx_len: int = 48, seed: int = 0):
    """Agent fan-out workload: every session's context is decoded by several
    task-specific models over ONE shared prefill. Reports dispatches/step and
    tokens/s for the per-model loop vs the fused vmapped step."""
    base = init_params(CFG, jax.random.PRNGKey(0))
    decs = {f"m{i}": init_params(CFG, jax.random.PRNGKey(7 + i))
            for i in range(n_models)}
    rng = np.random.default_rng(seed)
    # ONE context per session, fanned out to every model (the paper's agent
    # pattern): sibling submits reuse the session's pages, so the decode
    # plane — not prefill — dominates the measured window.
    ctxs = [list(rng.integers(4, 60, size=ctx_len + 2 * sid))
            for sid in range(seqs_per_model)]
    jobs = [(sid, ctxs[sid], f"m{i}")
            for sid in range(seqs_per_model)
            for i in range(n_models)]

    def run(fused):
        eng = LocalDisaggEngine(CFG, base, num_pages=2048, fused=fused)
        for mid, p in decs.items():
            eng.models.register(mid, p)
        ros = [eng.generate(mid, ctx, SamplingParams(max_tokens=gen),
                            session=sid)
               for sid, ctx, mid in jobs]
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        outs = [o.result() for o in ros]
        return (outs, len(jobs) * gen / dt,
                eng.stats.decode_dispatches / max(1, eng.stats.decode_steps),
                eng)

    loop_out, loop_tps, loop_dps, _ = run(fused=False)
    fused_out, fused_tps, fused_dps, eng = run(fused=True)
    for a, b in zip(fused_out, loop_out):
        np.testing.assert_array_equal(a, b)
    assert fused_dps == 1.0, f"fused plane issued {fused_dps} dispatches/step"

    rows = [{"path": "per-model-loop", "models": n_models, "tok_s": loop_tps,
             "dispatches_per_step": loop_dps},
            {"path": "fused-vmapped", "models": n_models, "tok_s": fused_tps,
             "dispatches_per_step": fused_dps}]
    print("path,models,dispatches_per_step,tok_s")
    for r in rows:
        print(f"{r['path']},{r['models']},{r['dispatches_per_step']:.1f},"
              f"{r['tok_s']:.1f}")
    print(f"# fused speedup={fused_tps / loop_tps:.2f}x over per-model loop "
          f"(greedy outputs identical, {n_models} models, "
          f"{len(jobs)} sequences, "
          f"traces={program_traces().get('decode_step', 0)})")
    return rows, fused_tps / loop_tps


def _random_adapter(key, base, rank: int, alpha: float) -> LoRAAdapter:
    """A lora_init adapter with nonzero B, so every model's merge is a real
    task-specific perturbation (B=0 would make all N models decode as the
    base and trivialize the parity check)."""
    tree = lora_init(key, base, rank=rank)
    flat, td = jax.tree_util.tree_flatten(
        tree, is_leaf=lambda x: x is None or isinstance(x, LoRAPair))
    kb = jax.random.fold_in(key, 1)
    out = []
    for i, p in enumerate(flat):
        if p is None:
            out.append(None)
        else:
            b = 0.02 * jax.random.normal(jax.random.fold_in(kb, i),
                                         p.B.shape, p.B.dtype)
            out.append(LoRAPair(p.A, b))
    return LoRAAdapter(jax.tree_util.tree_unflatten(td, out),
                       alpha=alpha, rank=rank)


def adapters_mode(n_models: int = 4, seqs_per_model: int = 2, gen: int = 32,
                  ctx_len: int = 48, seed: int = 0, rank: int = 8,
                  alpha: float = 16.0):
    """Adapter-factored decode plane vs N materialized models: same N LoRA
    fine-tunes, registered either as LoRA specs (one base copy + N stacked
    A/B factor sets, merged inside the jitted vmapped step) or as N full
    ``lora_apply`` pytrees. Reports weight bytes + tok/s; outputs asserted
    bit-identical."""
    base = init_params(CFG, jax.random.PRNGKey(0))
    ads = {f"m{i}": _random_adapter(jax.random.PRNGKey(7 + i), base,
                                    rank, alpha)
           for i in range(n_models)}
    rng = np.random.default_rng(seed)
    ctxs = [list(rng.integers(4, 60, size=ctx_len + 2 * sid))
            for sid in range(seqs_per_model)]
    jobs = [(sid, ctxs[sid], mid)
            for sid in range(seqs_per_model) for mid in ads]

    def run(lora: bool):
        eng = LocalDisaggEngine(CFG, base, num_pages=2048)
        for mid, ad in ads.items():
            spec = (DecodeModelSpec(lora=ad) if lora else
                    DecodeModelSpec(full=lora_apply(
                        base, ad.params, alpha=alpha, rank=rank)))
            eng.models.register(mid, spec)
        ros = [eng.generate(mid, ctx, SamplingParams(max_tokens=gen),
                            session=sid)
               for sid, ctx, mid in jobs]
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        return [o.result() for o in ros], len(jobs) * gen / dt, eng

    full_out, full_tps, eng_full = run(lora=False)
    lora_out, lora_tps, eng_lora = run(lora=True)
    for a, b in zip(lora_out, full_out):
        np.testing.assert_array_equal(a, b)

    base_bytes = sum(x.nbytes for x in jax.tree.leaves(base))
    one_full = sum(x.nbytes for x in jax.tree.leaves(
        lora_apply(base, ads["m0"].params, alpha=alpha, rank=rank)))
    one_ad = sum(x.nbytes for x in jax.tree.leaves(ads["m0"].params))
    full_bytes = eng_full.decode_plane.param_bytes()          # N × full
    lora_bytes = base_bytes + eng_lora.decode_plane.param_bytes()  # base + N·ad
    # plane accounting must agree exactly with the array shapes
    assert full_bytes == n_models * one_full, (full_bytes, n_models, one_full)
    assert lora_bytes == base_bytes + n_models * one_ad, \
        (lora_bytes, base_bytes, n_models, one_ad)
    ratio = full_bytes / lora_bytes

    print("path,models,plane_weight_bytes,tok_s")
    print(f"materialized-full,{n_models},{full_bytes},{full_tps:.1f}")
    print(f"lora-instep-merge,{n_models},{lora_bytes},{lora_tps:.1f}")
    print(f"# weight ratio N*full/(base+N*adapters) = {ratio:.2f}x "
          f"(rank {rank}, outputs bit-identical, tok/s parity "
          f"{lora_tps / full_tps:.2f}x)")
    return ratio, lora_tps / full_tps


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--ctx", type=int, default=48)
    ap.add_argument("--models", type=int, default=4)
    ap.add_argument("--adapters", action="store_true",
                    help="LoRA-spec'd plane (base + N adapters, in-step "
                         "merge) vs N materialized models")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write BENCH_serving.json here")
    args = ap.parse_args()
    all_rows, gates = [], {}
    rows, speedup = main(batch=args.batch, gen=args.gen, ctx_len=args.ctx)
    all_rows += rows
    gates["paged_over_dense_tok_s"] = gate(speedup, 2.0)
    if args.models > 1:
        rows, fused_speedup = multi_model(n_models=args.models, gen=args.gen,
                                          ctx_len=args.ctx)
        all_rows += rows
        gates["fused_over_loop_tok_s"] = gate(fused_speedup, 0.0)
    if args.adapters:
        ratio, parity = adapters_mode(n_models=args.models, gen=args.gen,
                                      ctx_len=args.ctx)
        gates["adapter_weight_ratio"] = gate(ratio, 1.5)
        gates["adapter_tok_s_parity"] = gate(parity, 0.0)
    if args.json:
        write_bench_json(args.json, "paged_decode", all_rows, gates=gates)
    assert gates["paged_over_dense_tok_s"]["passed"], \
        f"batched paged decode only {speedup:.2f}x"
    if args.adapters:
        assert gates["adapter_weight_ratio"]["passed"], \
            f"adapter factoring saved only {gates['adapter_weight_ratio']}"
