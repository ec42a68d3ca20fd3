#!/usr/bin/env python3
"""Smoke test of the PrefillShare serving engine on one TPU chip.

Drives the engine's main path through the entry points a user calls
(``LocalDisaggEngine``, ``engine.models.register``, ``shared_context`` /
``ctx.generate``, ``result``) at the full width of qwen3-1.7b in bf16: one
frozen base (prefill) module and two full-weight decode modules with seeded
random weights, over one shared paged KV pool of 1024 pages of 16 tokens.

Phases:
  (c) each Pallas kernel of the main path (paged_decode,
      flash_prefill_paged, paged_write) against its ``kernels/ref.py`` twin
      at qwen3-1.7b widths, with a stated error bound; paged_decode also
      over a 1024-page table whose rows end at 1, 17, 12,000 and 16,384;
  (a) the default engine (eager prefill): one shared context of 2048
      tokens, each decode module generates 32 greedy tokens; the context is
      extended by 256 tokens and both modules fan out again, the second turn
      hitting the prefix cache;
  (b) the same traffic with ``chunked=True`` (flash_prefill_paged under the
      chunk scheduler, the fused decode plane);
  (d) each request's first token against a plain float32 reference built
      from ``base_prefill`` into a dense cache plus one step of the decode
      module's ``forward``, under ``default_matmul_precision("highest")``.

Run it as ``python chip_smoke.py`` from the repository root, one process
per chip. It exits non-zero and prints no result line unless JAX's first
device is a TPU, or if any phase fails. The last line of its output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.runtime import setup_compile_cache  # noqa: E402

MODEL = "qwen3-1.7b"
SEED = 0
PROMPT_TOKENS, EXTEND_TOKENS, GEN_TOKENS = 2048, 256, 32
NUM_PAGES, PAGE_SIZE = 1024, 16
CHUNK = 256
FIRST_TOKEN = 2                 # the engine's default first decode input
#: bf16 kernel outputs against their f32-math twins: one bf16 rounding of
#: outputs of magnitude <= ~2 plus reordered f32 accumulation
KERNEL_BOUND = 2e-2
#: a reference top-2 logit gap under this many standard deviations of the
#: reference logits is too close to call for a bf16 engine (28 bf16 layers
#: against float32 "highest"): printed, not failed
TOP2_TOL = 0.1
#: the seeded init feeds token embeddings in at sqrt(d_model) scale and ties
#: them to the output, so a random model's top logit is the token it was
#: just fed, whatever the context. Shrinking the tied table (by a power of
#: two: exact in bf16) lets the context decide the argmax, so the
#: first-token check sees attention over the paged KV.
EMBED_SCALE = 0.25


class Phases:
    """Runs named phases, timing each and recording failures."""

    def __init__(self):
        self.wall: dict = {}
        self.compile_s: dict = {}
        self.failed: list = []
        self._compile_total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self._compile_total += duration

    def run(self, name, fn, *args, **kw):
        c0, t0 = self._compile_total, time.perf_counter()
        try:
            return fn(*args, **kw)
        except Exception:
            traceback.print_exc()
            self.failed.append(name)
            print(f"phase {name}: FAILED", flush=True)
            return None
        finally:
            self.wall[name] = time.perf_counter() - t0
            self.compile_s[name] = self._compile_total - c0
            print(f"phase {name}: wall_s={self.wall[name]:.3f} "
                  f"compile_s={self.compile_s[name]:.3f}", flush=True)


def _seeded_params(cfg, seed):
    from repro.models import init_params

    @jax.jit
    def init(key):
        p = init_params(cfg, key)
        return {**p, "embed": p["embed"] * jnp.asarray(EMBED_SCALE,
                                                        p["embed"].dtype)}
    return jax.block_until_ready(init(jax.random.PRNGKey(seed)))


def check_kernels(cfg):
    """Phase (c): each main-path kernel against its jnp twin."""
    from repro.kernels.flash_prefill_paged import flash_prefill_paged
    from repro.kernels.paged_decode import paged_decode_attention
    from repro.kernels.paged_write import paged_write
    from repro.kernels.ref import (ref_paged_decode, ref_paged_prefill,
                                   ref_paged_write)
    hq, hkv, d, page = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, PAGE_SIZE
    f, pool, npages, layers = hkv * d, 96, 32, 2
    ks = jax.random.split(jax.random.PRNGKey(SEED), 8)
    dt = jnp.bfloat16
    kp = jax.random.normal(ks[0], (layers, pool, page, f), dt)
    vp = jax.random.normal(ks[1], (layers, pool, page, f), dt)
    bt = jax.random.randint(ks[2], (4, npages), 1, pool)
    ok = True

    def report(name, err, bound):
        nonlocal ok
        good = bool(np.isfinite(err)) and err <= bound
        ok &= good
        print(f"kernel {name}: max_abs_err={err:.6g} bound={bound:g} "
              f"{'ok' if good else 'FAIL'}", flush=True)

    def max_err(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))

    q = jax.random.normal(ks[3], (4, hq, d), dt)
    lengths = jax.random.randint(ks[4], (4,), 1, npages * page + 1)
    lengths = lengths.astype(jnp.int32)
    o = jax.jit(paged_decode_attention)(q, kp, vp, bt, lengths, layer=1)
    r = ref_paged_decode(q, kp, vp, bt, lengths, layer=1)
    report("paged_decode", max_err(o, r), KERNEL_BOUND)

    # a 1024-page table (the 8b cell's bucket) over the same pool: a
    # padding row, a row ending inside its first page, one inside a block
    # and one filling the whole table
    wide = jax.random.randint(ks[2], (4, 1024), 1, pool)
    lengths = jnp.asarray([1, 17, 12_000, 16_384], jnp.int32)
    o = jax.jit(paged_decode_attention)(q, kp, vp, wide, lengths, layer=1)
    r = ref_paged_decode(q, kp, vp, wide, lengths, layer=1)
    report("paged_decode 1024-page table", max_err(o, r), KERNEL_BOUND)

    s = CHUNK
    qc = jax.random.normal(ks[5], (2, s, hq, d), dt)
    start = jnp.asarray([page * npages - s - 5, 37], jnp.int32)
    o = jax.jit(flash_prefill_paged)(qc, kp, vp, bt[:2], start, layer=1)
    r = ref_paged_prefill(qc, kp, vp, bt[:2], start, layer=1)
    report("flash_prefill_paged", max_err(o, r), KERNEL_BOUND)

    span = 4
    nk = jax.random.normal(ks[6], (2, span * page, f), dt)
    nv = jax.random.normal(ks[7], (2, span * page, f), dt)
    dst = jnp.asarray(np.random.default_rng(SEED).permutation(
        np.arange(1, pool))[:2 * span].reshape(2, span), jnp.int32)
    nvalid = jnp.asarray([span, span - 1], jnp.int32)
    ko, vo = jax.jit(paged_write)(nk, nv, kp[0], vp[0], dst, nvalid)
    rk, rv = ref_paged_write(nk, nv, kp[0], vp[0], dst, nvalid)
    report("paged_write", max(max_err(ko, rk), max_err(vo, rv)), 0.0)
    if not ok:
        raise AssertionError("a kernel disagrees with its reference")


def serve(cfg, base, decoders, prompt, extension, *, chunked, num_pages):
    """Phases (a)/(b): two turns of fan-out over one shared context.
    Returns one record per turn: context length, each module's tokens, and
    the turn's prefix hit ratio."""
    from repro.serving.api import SamplingParams
    from repro.serving.engine import LocalDisaggEngine
    from repro.serving.registry import DecodeModelSpec
    eng = LocalDisaggEngine(cfg, base, num_pages=num_pages,
                            page_size=PAGE_SIZE, chunked=chunked,
                            chunk_size=CHUNK, token_budget=2 * CHUNK)
    for mid, params in decoders.items():
        eng.models.register(mid, DecodeModelSpec(full=params))
    st = eng.stats
    turns = []
    with eng.shared_context() as ctx:
        for tail in (prompt, extension):
            c0, r0 = st.prefill_tokens_computed, st.prefill_tokens_reused
            ctx.extend(tail)
            handles = {mid: ctx.generate(
                mid, params=SamplingParams(max_tokens=GEN_TOKENS))
                for mid in decoders}
            outs = {mid: h.result().tolist() for mid, h in handles.items()}
            computed = st.prefill_tokens_computed - c0
            reused = st.prefill_tokens_reused - r0
            turns.append({"n": len(ctx.tokens), "out": outs,
                          "hit_ratio": reused / max(1, computed + reused)})
    for t, rec in enumerate(turns):
        lens = {m: len(o) for m, o in rec["out"].items()}
        print(f"{'chunked' if chunked else 'eager'} turn {t}: "
              f"context={rec['n']} tokens_out={lens} "
              f"prefix_hit_ratio={rec['hit_ratio']:.4f}", flush=True)
        for mid, out in rec["out"].items():
            print(f"  {mid}: first tokens {out[:8]}", flush=True)
            if len(out) != GEN_TOKENS:
                raise AssertionError(f"{mid} generated {len(out)} tokens")
    if turns[1]["hit_ratio"] <= 0:
        raise AssertionError("the second turn missed the prefix cache")
    return {"turns": turns, "tokens": st.decode_tokens}


def reference_first_tokens(cfg, seeds, contexts):
    """Phase (d) reference: for each decode module and context, the float32
    greedy first token and the top-2 logit gap. Weights are re-made from
    their seeds and cast to float32 one module at a time, so at most one
    float32 copy is on the device."""
    from repro.core.prefillshare import base_prefill
    from repro.models import forward
    cfg32 = dataclasses.replace(cfg, dtype="float32")

    def f32(tree):
        return jax.tree.map(lambda x: x.astype(jnp.float32), tree)

    def ref_params(seed):
        p = _seeded_params(cfg, seed)
        out = jax.block_until_ready(jax.jit(f32)(p))
        del p
        return out

    cache_len = max(len(c) for c in contexts) + 1   # + the decode step
    with jax.default_matmul_precision("highest"):
        base = ref_params(seeds["base"])
        caches, prev = [], None
        for ctx in contexts:                 # each extends the previous one
            n0 = 0 if prev is None else prev[0]
            toks = jnp.asarray(ctx[n0:], jnp.int32)[None]
            _, cache = base_prefill(
                cfg32, base, toks, cache_len=cache_len,
                cache=None if prev is None else prev[1],
                pos=jnp.asarray([n0], jnp.int32))
            prev = (len(ctx), cache)
            caches.append(prev)
        del base
        gc.collect()
        step = jax.jit(lambda p, c, n: forward(
            cfg32, p, jnp.full((1, 1), FIRST_TOKEN, jnp.int32), cache=c,
            pos=n[None])[0][0])
        ref = {}
        for mid, seed in seeds.items():
            if mid == "base":
                continue
            params = ref_params(seed)
            for n, cache in caches:
                logits = np.asarray(step(params, cache, jnp.int32(n)))
                top2 = np.sort(logits)[-2:]
                ref[(mid, n)] = (int(np.argmax(logits)),
                                 float(top2[1] - top2[0]), logits)
            del params
            gc.collect()
    return ref


def compare_first_tokens(ref, results):
    """Phase (d) verdict: argmax agreement where the reference is decisive."""
    ok = True
    for mode, res in results.items():
        for rec in res["turns"]:
            for mid, out in rec["out"].items():
                want, gap, logits = ref[(mid, rec["n"])]
                got = out[0]
                regret = float(logits[want] - logits[got])
                tol = TOP2_TOL * float(logits.std())
                if got == want:
                    verdict = "match"
                elif gap < tol:
                    verdict = "mismatch-within-tol"
                else:
                    verdict = "MISMATCH"
                    ok = False
                print(f"first-token {mode} {mid} context={rec['n']}: "
                      f"engine={got} ref={want} top2_gap={gap:.4f} "
                      f"ref_logit_regret={regret:.4f} tol={tol:.4f} "
                      f"{verdict}", flush=True)
    if not ok:
        raise AssertionError("engine first tokens disagree with the "
                             "float32 reference")


def main() -> int:
    setup_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    from repro.configs.base import get_config
    cfg = get_config(MODEL)
    phases = Phases()
    rng = np.random.default_rng(SEED)
    prompt = rng.integers(4, cfg.vocab_size, PROMPT_TOKENS).tolist()
    extension = rng.integers(4, cfg.vocab_size, EXTEND_TOKENS).tolist()
    seeds = {"base": SEED, "agent_a": SEED + 1, "agent_b": SEED + 2}
    print(f"device_kind={dev.device_kind} count={len(devices)} model={MODEL} "
          f"pool_pages={NUM_PAGES} page={PAGE_SIZE}", flush=True)

    phases.run("c_kernels", check_kernels, cfg)
    params = phases.run("weights", lambda: {m: _seeded_params(cfg, s)
                                            for m, s in seeds.items()})
    results = {}
    if params is not None:
        decoders = {m: p for m, p in params.items() if m != "base"}
        for mode, chunked in (("eager", False), ("chunked", True)):
            res = phases.run(f"{'a' if mode == 'eager' else 'b'}_{mode}",
                             serve, cfg, params["base"], decoders, prompt,
                             extension, chunked=chunked,
                             num_pages=NUM_PAGES)
            gc.collect()
            if res is not None:
                results[mode] = res
        del params, decoders
        gc.collect()
    contexts = [prompt, prompt + extension]
    ref = phases.run("d_reference", reference_first_tokens, cfg, seeds,
                     contexts)
    if ref is not None and results:
        phases.run("d_compare", compare_first_tokens, ref, results)
    if len(results) < 2:
        phases.failed.append("serving results missing")

    stats = dev.memory_stats() or {}
    tokens = sum(r["tokens"] for r in results.values())
    hit = {m: [round(t["hit_ratio"], 6) for t in r["turns"]]
           for m, r in results.items()}
    print(f"device_kind={dev.device_kind}")
    print(f"compile_s_total={sum(phases.compile_s.values()):.3f} "
          f"per_phase={json.dumps({k: round(v, 3) for k, v in phases.compile_s.items()})}")
    print(f"wall_s_per_phase="
          f"{json.dumps({k: round(v, 3) for k, v in phases.wall.items()})}")
    print(f"tokens_generated={tokens}")
    print(f"prefix_hit_ratio_per_turn={json.dumps(hit)}")
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    if phases.failed:
        print(f"chip_smoke: failed phases: {phases.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
