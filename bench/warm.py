"""Set-up: make every step program the cell's traffic can reach ready before
the window opens, so that nothing compiles inside it.

The engine keys its jitted steps on shapes: the fused decode step on (live
sequences, the widest lane, the block-table width bucket) and the prefill
chunk step on (chunks of one size, that size, the width bucket). The traffic
bounds all of them: lengths on its ``length_quantum`` grid give chunk sizes
that are multiples of it, its lengths give the width buckets, and its
``warm`` caps bound the batches. Each shape is run once on zeros through the
engine's own step objects, with every block table pointing at the pool's
sentinel page, which no request ever holds.

These objects are the program's own, not a public interface: the program
offers no call that warms its shapes. Where they are gone or take other
arguments, the warm-up traffic alone stands in, and the window's compile
counter shows what it missed; a program that buckets its shapes needs no
more than that traffic.
"""
from __future__ import annotations

import time

import numpy as np


def _pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def _buckets(lo_tokens: int, hi_tokens: int, page: int) -> list:
    lo, hi = _pow2(-(-lo_tokens // page)), _pow2(-(-hi_tokens // page))
    out, b = [], lo
    while b <= hi:
        out.append(b)
        b *= 2
    return out


def shapes(traffic: dict, engine_opts: dict, modules: int, page: int):
    """(decode shapes [(N, bmax, npages)], chunk shapes [(B, S, npages)])."""
    q = int(traffic.get("length_quantum", 1))
    chunk = int(engine_opts["chunk_size"])
    sys_ = traffic["system_prompt_tokens"]
    doc, obs, tail = (traffic["document_tokens"], traffic["observation_tokens"],
                      traffic["tail_tokens"])
    turns = traffic["turns"]
    lo_prompt = sys_ + doc[0] + tail[0]
    hi_prompt = sys_ + doc[1] + obs[1] * (turns - 1) + tail[1]
    hi_total = hi_prompt + traffic["reply_tokens"][1] + page
    caps = traffic["warm"]
    nmax, bmax_chunk = caps["decode_batch"], caps["chunk_batch"]
    decode = [(n, b, w)
              for w in _buckets(lo_prompt, hi_total, page)
              for n in range(1, nmax + 1)
              for b in range(-(-n // modules), n + 1)]
    sizes = sorted({min(chunk, s) for s in range(q, chunk + 1, q)})
    chunks = [(bb, s, w)
              for w in _buckets(min(sys_ + chunk, lo_prompt), hi_prompt, page)
              for s in sizes
              for bb in range(1, bmax_chunk + 1)]
    return decode, chunks


def run(engine, traffic: dict, engine_opts: dict, page: int, log) -> dict:
    """Run every shape once; returns counts and seconds per kind, or the
    reason it could not."""
    try:
        out = _run(engine, traffic, engine_opts, page)
    except Exception as e:  # noqa: BLE001 -- any change of private objects
        log(f"warm: step shapes not made ready ({e!r}); the warm-up traffic "
            f"stands in")
        return {"not_ready": repr(e)}
    log(f"warm: {out}")
    return out


def _run(engine, traffic: dict, engine_opts: dict, page: int) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core.prefillshare import base_prefill_chunk
    t0 = time.perf_counter()
    group = engine.decode_plane.groups[0]
    step, stacked, lanes = group._step, group.stacked, len(group.model_ids)
    decode, chunks = shapes(traffic, engine_opts, lanes, page)
    pool = engine.kvpool
    for n, b, w in decode:
        m = np.arange(n) % lanes
        bi = np.arange(n) // lanes
        zi = np.zeros((lanes, b), np.int32)
        nxt, state = step(
            stacked, pool.decode_state(), zi, zi,
            np.zeros((lanes, b, w), np.int32), m.astype(np.int32),
            bi.astype(np.int32), np.zeros(n, np.float32),
            np.zeros(n, np.int32), np.ones(n, np.float32),
            np.zeros(n, np.int32), True)
        pool.absorb_decode_state(state)
        np.asarray(nxt)
    # the engine hands each step's (live,) lane and row indices over as
    # Python lists: one small conversion program per count
    for n in range(1, traffic["warm"]["decode_batch"] + 1):
        jax.block_until_ready(jnp.asarray([0] * n, jnp.int32))
    t1 = time.perf_counter()
    for bb, s, w in chunks:
        out = base_prefill_chunk(engine.cfg, engine.base_params,
                                 np.zeros((bb, s), np.int32), pool=pool,
                                 block_tables=np.zeros((bb, w), np.int32),
                                 pos=np.zeros((bb,), np.int32))
        jax.block_until_ready(out)
    t2 = time.perf_counter()
    return {"decode_shapes": len(decode), "decode_s": t1 - t0,
            "chunk_shapes": len(chunks), "chunk_s": t2 - t1}
