"""Attention: GQA with RoPE variants, sliding windows, softcaps, caches.

Two execution paths share one mask semantics:
  - ``_direct``: materialized scores, for small shapes (CPU smoke tests, decode).
  - ``_flash``: chunked online-softmax (flash-style) in pure JAX ``lax.scan`` /
    ``lax.map`` — memory O(chunk), used for large prefill/train shapes. The
    Pallas TPU kernel (repro.kernels.flash_prefill) implements the same
    contract for real-TPU deployment; this is the XLA-lowerable twin used by
    the multi-pod dry-run.

Positions are explicit: ``q_pos`` (B, Sq) and ``k_pos`` (B, Tk) absolute token
positions; ``k_pos = -1`` marks invalid (unwritten) cache slots. Causality,
sliding windows, and cache validity all derive from these arrays, which makes
full prefill, *partial* prefill (PrefillShare's incremental extension), and
single-token decode the same code path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import LOCAL_ATTN
from repro.models.layers import dense_init, rmsnorm
from repro.models.rope import apply_rope
from repro.runtime import on_tpu

_NEG = -1e30

# Distributed policy hook (set by repro.launch.steps): PartitionSpec for the
# flash path's chunked K/V (nk, B, Ck, Hkv, D). Pinning these batch-sharded /
# head-replicated hoists the KV all-gather OUT of the q-chunk loop — GSPMD
# otherwise re-gathers model-sharded KV on every loop iteration (32x per
# layer at 32k prefill; EXPERIMENTS.md §Perf iteration 7).
FLASH_KV_SPEC = None


def _constrain_kv(x):
    if FLASH_KV_SPEC is not None:
        return jax.lax.with_sharding_constraint(x, FLASH_KV_SPEC)
    return x


def _pick_chunk(n: int, target: int) -> int:
    c = min(n, target)
    while n % c:
        c -= 1
    return c


def _block_mask(qp, kp, window):
    """qp: (B, Cq), kp: (B, Ck) -> bool (B, 1, 1, Cq, Ck)."""
    m = (kp[:, None, :] <= qp[:, :, None]) & (kp[:, None, :] >= 0)
    if window:
        m &= kp[:, None, :] > (qp[:, :, None] - window)
    return m[:, None, None, :, :]


def _softcap(s, cap):
    return jnp.tanh(s / cap) * cap if cap else s


def _direct(qg, k, v, q_pos, k_pos, window, softcap):
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32))
    s = _softcap(s, softcap)
    mask = _block_mask(q_pos, k_pos, window)
    s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.astype(v.dtype)


def _flash_fwd_impl(qg, k, v, q_pos, k_pos, window, softcap, q_chunk, kv_chunk):
    """Returns (o (B,Sq,Hkv,G,D), lse (B,Hkv,G,Sq))."""
    B, Sq, Hkv, G, D = qg.shape
    Tk = k.shape[1]
    Cq = _pick_chunk(Sq, q_chunk)
    Ck = _pick_chunk(Tk, kv_chunk)
    nq, nk = Sq // Cq, Tk // Ck

    kc = _constrain_kv(jnp.moveaxis(k.reshape(B, nk, Ck, Hkv, D), 1, 0))
    vc = _constrain_kv(jnp.moveaxis(v.reshape(B, nk, Ck, Hkv, D), 1, 0))
    kpc = jnp.moveaxis(k_pos.reshape(B, nk, Ck), 1, 0)

    def q_block(args):
        qb, qp = args  # (B, Cq, Hkv, G, D), (B, Cq)

        def kv_step(carry, xs):
            m, lden, acc = carry
            kb, vb, kpb = xs
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qb.astype(jnp.float32),
                           kb.astype(jnp.float32))
            s = _softcap(s, softcap)
            mask = _block_mask(qp, kpb, window)
            s = jnp.where(mask, s, _NEG)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None]) * mask  # mask kills fully-masked rows
            corr = jnp.exp(m - m_new)
            lden = lden * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vb.astype(jnp.float32))
            return (m_new, lden, acc), None

        m0 = jnp.full((B, Hkv, G, Cq), _NEG, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, Cq), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, Cq, D), jnp.float32)
        (m, lden, acc), _ = lax.scan(kv_step, (m0, l0, a0), (kc, vc, kpc))
        o = acc / jnp.maximum(lden, 1e-30)[..., None]
        lse = m + jnp.log(jnp.maximum(lden, 1e-30))           # (B,Hkv,G,Cq)
        return jnp.moveaxis(o, 3, 1).astype(v.dtype), lse

    if nq == 1:
        return q_block((qg, q_pos))
    qs = jnp.moveaxis(qg.reshape(B, nq, Cq, Hkv, G, D), 1, 0)
    qps = jnp.moveaxis(q_pos.reshape(B, nq, Cq), 1, 0)
    out, lses = lax.map(q_block, (qs, qps))     # (nq, B, Cq, Hkv, G, D)
    o = jnp.moveaxis(out, 0, 1).reshape(B, Sq, Hkv, G, D)
    lse = jnp.moveaxis(lses, 0, -2).reshape(B, Hkv, G, Sq)
    return o, lse


def _flash_bwd_impl(qg, k, v, q_pos, k_pos, o, lse, do,
                    window, softcap, q_chunk, kv_chunk):
    """Standard flash backward: recompute p per block from (q,k,lse); only
    (o, lse) were saved. Accumulates dk/dv across q blocks in a scan carry."""
    B, Sq, Hkv, G, D = qg.shape
    Tk = k.shape[1]
    Cq = _pick_chunk(Sq, q_chunk)
    Ck = _pick_chunk(Tk, kv_chunk)
    nq, nk = Sq // Cq, Tk // Ck

    kc = _constrain_kv(jnp.moveaxis(k.reshape(B, nk, Ck, Hkv, D), 1, 0))
    vc = _constrain_kv(jnp.moveaxis(v.reshape(B, nk, Ck, Hkv, D), 1, 0))
    kpc = jnp.moveaxis(k_pos.reshape(B, nk, Ck), 1, 0)

    qs = jnp.moveaxis(qg.reshape(B, nq, Cq, Hkv, G, D), 1, 0)
    qps = jnp.moveaxis(q_pos.reshape(B, nq, Cq), 1, 0)
    dos = jnp.moveaxis(do.reshape(B, nq, Cq, Hkv, G, D), 1, 0)
    os_ = jnp.moveaxis(o.reshape(B, nq, Cq, Hkv, G, D), 1, 0)
    lses = jnp.moveaxis(lse.reshape(B, Hkv, G, nq, Cq), 3, 0)  # (nq,B,H,G,Cq)

    def q_step(carry, xs):
        dk_acc, dv_acc = carry
        qb, qp, dob, ob, lseb = xs
        dof = dob.astype(jnp.float32)
        of = ob.astype(jnp.float32)
        Drow = jnp.einsum("bqhgd,bqhgd->bhgq", dof, of)        # (B,H,G,Cq)

        def kv_step(carry2, xs2):
            dq_b, dk_acc, dv_acc, j = carry2
            kb, vb, kpb = xs2
            s_raw = jnp.einsum("bqhgd,bkhd->bhgqk", qb.astype(jnp.float32),
                               kb.astype(jnp.float32))
            s = _softcap(s_raw, softcap)
            mask = _block_mask(qp, kpb, window)
            s = jnp.where(mask, s, _NEG)
            p = jnp.exp(s - lseb[..., None]) * mask            # (B,H,G,Cq,Ck)
            dv_blk = jnp.einsum("bhgqk,bqhgd->bkhd", p, dof)
            dp = jnp.einsum("bqhgd,bkhd->bhgqk", dof, vb.astype(jnp.float32))
            ds = p * (dp - Drow[..., None])
            if softcap:
                t = jnp.tanh(s_raw / softcap)
                ds = ds * (1.0 - t * t)
            dq_b = dq_b + jnp.einsum("bhgqk,bkhd->bqhgd", ds,
                                     kb.astype(jnp.float32))
            dk_blk = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qb.astype(jnp.float32))
            dk_acc = lax.dynamic_update_slice(
                dk_acc, lax.dynamic_slice(dk_acc, (0, j * Ck, 0, 0),
                                          (B, Ck, Hkv, D)) + dk_blk,
                (0, j * Ck, 0, 0))
            dv_acc = lax.dynamic_update_slice(
                dv_acc, lax.dynamic_slice(dv_acc, (0, j * Ck, 0, 0),
                                          (B, Ck, Hkv, D)) + dv_blk,
                (0, j * Ck, 0, 0))
            return (dq_b, dk_acc, dv_acc, j + 1), None

        dq0 = jnp.zeros((B, Cq, Hkv, G, D), jnp.float32)
        (dq_b, dk_acc, dv_acc, _), _ = lax.scan(
            kv_step, (dq0, dk_acc, dv_acc, 0), (kc, vc, kpc))
        return (dk_acc, dv_acc), dq_b

    dk0 = jnp.zeros((B, Tk, Hkv, D), jnp.float32)
    dv0 = jnp.zeros((B, Tk, Hkv, D), jnp.float32)
    (dk, dv), dqs = lax.scan(q_step, (dk0, dv0), (qs, qps, dos, os_, lses))
    dq = jnp.moveaxis(dqs, 0, 1).reshape(B, Sq, Hkv, G, D)
    return dq.astype(qg.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


import functools as _ft

import numpy as _np


@_ft.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _flash_vjp(window, softcap, q_chunk, kv_chunk, qg, k, v, q_pos, k_pos):
    o, _ = _flash_fwd_impl(qg, k, v, q_pos, k_pos, window, softcap,
                           q_chunk, kv_chunk)
    return o


def _flash_vjp_fwd(window, softcap, q_chunk, kv_chunk, qg, k, v, q_pos, k_pos):
    o, lse = _flash_fwd_impl(qg, k, v, q_pos, k_pos, window, softcap,
                             q_chunk, kv_chunk)
    return o, (qg, k, v, q_pos, k_pos, o, lse)


def _flash_vjp_bwd(window, softcap, q_chunk, kv_chunk, res, do):
    qg, k, v, q_pos, k_pos, o, lse = res
    dq, dk, dv = _flash_bwd_impl(qg, k, v, q_pos, k_pos, o, lse, do,
                                 window, softcap, q_chunk, kv_chunk)
    zq = _np.zeros(q_pos.shape, jax.dtypes.float0)   # int args: no cotangent
    zk = _np.zeros(k_pos.shape, jax.dtypes.float0)
    return dq, dk, dv, zq, zk


_flash_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _flash(qg, k, v, q_pos, k_pos, window, softcap, q_chunk, kv_chunk):
    """Differentiable flash attention: custom VJP stores only (o, lse)."""
    return _flash_vjp(window, softcap, q_chunk, kv_chunk, qg, k, v,
                      q_pos, k_pos)


def attention(q, k, v, q_pos, k_pos, *, window: int = 0, softcap=None,
              scale=None, q_chunk: int = 1024, kv_chunk: int = 2048,
              force_flash: bool | None = None):
    """q: (B,Sq,Hq,D); k/v: (B,Tk,Hkv,D); returns (B,Sq,Hq,D)."""
    B, Sq, Hq, D = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qg = (q * (scale if scale is not None else D ** -0.5)).reshape(B, Sq, Hkv, G, D)
    use_flash = force_flash if force_flash is not None else (Sq * Tk > 4096 * 2048)
    if use_flash:
        o = _flash(qg, k, v, q_pos.astype(jnp.int32), k_pos.astype(jnp.int32),
                   window, softcap, q_chunk, kv_chunk)
    else:
        o = _direct(qg, k, v, q_pos, k_pos, window, softcap)
    return o.reshape(B, Sq, Hq, D)


# ======================================================================
# Paged decode attention (shared-pool data plane)

PAGED_CACHE_KEYS = ("k_pages", "v_pages", "block_tables")


def _use_kernels() -> bool:
    """Mosaic kernels on TPU; the jnp twins in ``kernels.ref`` elsewhere
    (the CPU tests). A test compiling for a described TPU steers this."""
    return on_tpu()


def paged_attention_step(q, k_pages, v_pages, block_tables, lengths, *,
                         layer=None, softcap=0.0):
    """One decode step of attention over the paged KV pool.

    q: (B, Hq, D); k/v_pages: (P, page, Hkv*D), or layer-stacked (L, P, page,
    Hkv*D) read at ``layer``; block_tables: (B, npages); lengths: (B,).
    Mosaic kernel on TPU; elsewhere the pure-jnp gather twin
    (kernels.ref.ref_paged_decode) — same contract, XLA-lowerable, and
    bit-compatible with the ``_direct`` dense path so paged and dense engines
    produce identical greedy tokens on the CPU.
    """
    if _use_kernels():
        from repro.kernels.paged_decode import paged_decode_attention
        return paged_decode_attention(q, k_pages, v_pages, block_tables,
                                      lengths, layer=layer,
                                      softcap=softcap or 0.0)
    from repro.kernels.ref import ref_paged_decode
    return ref_paged_decode(q, k_pages, v_pages, block_tables, lengths,
                            layer=layer, softcap=softcap or 0.0)


def paged_prefill_attention(q, k_pages, v_pages, block_tables, start, *,
                            layer=None, softcap=0.0):
    """Chunk-prefill attention over the paged KV pool.

    q: (B, S, Hq, D) chunk queries at absolute positions ``start[b] + i``;
    the chunk's own K/V rows are already in their pages. Mosaic kernel on
    TPU; elsewhere the pure-jnp gather twin (kernels.ref.ref_paged_prefill)
    — same contract and bit-compatible with the ``_direct`` dense path, so
    chunked and unchunked prefill produce identical greedy tokens on the CPU.
    """
    if _use_kernels():
        from repro.kernels.flash_prefill_paged import flash_prefill_paged
        return flash_prefill_paged(q, k_pages, v_pages, block_tables, start,
                                   layer=layer, softcap=softcap or 0.0)
    from repro.kernels.ref import ref_paged_prefill
    return ref_paged_prefill(q, k_pages, v_pages, block_tables, start,
                             layer=layer, softcap=softcap or 0.0)


def _paged_apply(p, q, k, v, cache, pos, cfg):
    """Scatter the incoming tokens' K/V into their (private) pool pages,
    then attend over the block table. q/k/v: post-rope (B, S, H, D).

    The pool is one layer's ``(P, page, Hkv*D)`` pages, or the whole
    layer-stacked pool plus ``cache["layer"]`` (the layer scan carries the
    pool and updates it in place; the kernels read the layer by index, so no
    layer is ever sliced out). S == 1 is the decode step
    (paged_attention_step); S > 1 is a prefill chunk
    (paged_prefill_attention) — both read the prefix straight from the
    pages, no dense gather.
    """
    B, S = q.shape[0], q.shape[1]
    kp, vp, bt = (cache[key] for key in PAGED_CACHE_KEYS)
    layer = cache.get("layer")
    page = kp.shape[-2]
    positions = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None]   # (B, S)
    pg = jnp.take_along_axis(bt, positions // page, axis=1)           # (B, S)
    slot = positions % page
    at = (pg, slot) if layer is None else (layer, pg, slot)
    # vectorized scatter; written pages are private per sequence (fresh
    # chunk pages / copy-on-write at handoff), so (pg, slot) never collide.
    kp = kp.at[at].set(k.reshape(B, S, -1))
    vp = vp.at[at].set(v.reshape(B, S, -1))
    if S == 1:
        o = paged_attention_step(q[:, 0], kp, vp, bt, pos + 1, layer=layer,
                                 softcap=cfg.attn_softcap)[:, None]
    else:
        o = paged_prefill_attention(q, kp, vp, bt, pos, layer=layer,
                                    softcap=cfg.attn_softcap)
    out = jnp.einsum("bse,ed->bsd", o.reshape(B, S, -1), p["wo"])
    return out, {"k_pages": kp, "v_pages": vp, "block_tables": bt}


# ======================================================================
# Attention block: projections + rope + cache plumbing


def attn_init(key, cfg, *, cross: bool = False, dtype=jnp.float32):
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, hq * hd), dtype=dtype),
        "wk": dense_init(ks[1], (d, hkv * hd), dtype=dtype),
        "wv": dense_init(ks[2], (d, hkv * hd), dtype=dtype),
        "wo": dense_init(ks[3], (hq * hd, d), dtype=dtype),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def init_attn_cache(cfg, kind, batch, cache_len, dtype):
    """KV caches store a FLATTENED (kv_heads * head_dim) feature dim: a single
    named mesh axis can shard it 16-way even when kv_heads (8, 2, 1, ...)
    doesn't divide the axis — GSPMD then splits the reshape to (H, D) as
    (H-ways, D-ways) natively instead of involuntarily rematerializing
    (observed as a 2.2GB/step all-gather before this layout; EXPERIMENTS §Perf).
    """
    t = cache_len
    if kind == LOCAL_ATTN and cfg.sliding_window:
        t = min(cache_len, cfg.sliding_window)
    shape = (batch, t, cfg.n_kv_heads * cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "kpos": jnp.full((batch, t), -1, jnp.int32),
    }


def _update_global(cache, k, v, q_pos, pos):
    upd_kv = jax.vmap(lambda c, u, p: lax.dynamic_update_slice(c, u, (p, 0)))
    upd_p = jax.vmap(lambda c, u, p: lax.dynamic_update_slice(c, u, (p,)))
    return {
        "k": upd_kv(cache["k"], k, pos),
        "v": upd_kv(cache["v"], v, pos),
        "kpos": upd_p(cache["kpos"], q_pos, pos),
    }


def _update_window(cache, k, v, q_pos):
    t = cache["k"].shape[1]
    s = k.shape[1]
    if s >= t:
        return {"k": k[:, -t:], "v": v[:, -t:], "kpos": q_pos[:, -t:]}
    cat = lambda c, u: jnp.concatenate([c[:, s:], u], axis=1)
    return {"k": cat(cache["k"], k), "v": cat(cache["v"], v),
            "kpos": cat(cache["kpos"], q_pos)}


def attn_apply(p, x, cfg, kind, *, cache=None, pos=None, enc_out=None,
               cross: bool = False, causal: bool = True,
               flash: bool | None = None):
    """One attention layer.

    x: (B, S, D). pos: (B,) starting absolute position of x's first token.
    cache: attention cache dict or None (pure self-attention over x).
    Returns (out, new_cache).
    """
    B, S, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    window = cfg.sliding_window if kind == LOCAL_ATTN else 0

    q = jnp.einsum("bsd,de->bse", x, p["wq"]).reshape(B, S, hq, hd)
    if cross:
        # keys/values come from the encoder output; prefill (enc_out given)
        # computes and caches them, decode (enc_out=None) reuses the cache.
        if enc_out is not None:
            kf = jnp.einsum("bsd,de->bse", enc_out, p["wk"])
            vf = jnp.einsum("bsd,de->bse", enc_out, p["wv"])
            new_cache = {"k": kf, "v": vf}
        else:
            kf, vf = cache["k"], cache["v"]
            new_cache = cache
        k = kf.reshape(B, -1, hkv, hd)
        v = vf.reshape(B, -1, hkv, hd)
        tk = k.shape[1]
        q_pos = jnp.full((B, S), jnp.iinfo(jnp.int32).max, jnp.int32)
        k_pos = jnp.broadcast_to(jnp.arange(tk, dtype=jnp.int32)[None], (B, tk))
        o = attention(q, k, v, q_pos, k_pos, force_flash=flash)
        return jnp.einsum("bse,ed->bsd", o.reshape(B, S, hq * hd), p["wo"]), new_cache

    k = jnp.einsum("bsd,de->bse", x, p["wk"]).reshape(B, S, hkv, hd)
    v = jnp.einsum("bsd,de->bse", x, p["wv"]).reshape(B, S, hkv, hd)

    if "q_norm" in p:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)

    if pos is None:
        pos = jnp.zeros((B,), jnp.int32)
    q_pos = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    q = apply_rope(q, q_pos, style=cfg.rope_style, theta=cfg.rope_theta)
    k = apply_rope(k, q_pos, style=cfg.rope_style, theta=cfg.rope_theta)

    if cache is not None and "k_pages" in cache:
        if kind == LOCAL_ATTN:
            raise NotImplementedError("paged cache requires global attention")
        return _paged_apply(p, q, k, v, cache, pos, cfg)

    if cache is None:
        mask_qpos = q_pos if causal else jnp.full_like(
            q_pos, jnp.iinfo(jnp.int32).max)
        o = attention(q, k, v, mask_qpos, q_pos, window=window,
                      softcap=cfg.attn_softcap, force_flash=flash)
        new_cache = None
    else:
        kf = k.reshape(B, S, hkv * hd)
        vf = v.reshape(B, S, hkv * hd)
        if kind == LOCAL_ATTN and cfg.sliding_window:
            new_cache = _update_window(cache, kf, vf, q_pos)
        else:
            new_cache = _update_global(cache, kf, vf, q_pos, pos)
        t = new_cache["k"].shape[1]
        o = attention(q, new_cache["k"].reshape(B, t, hkv, hd),
                      new_cache["v"].reshape(B, t, hkv, hd),
                      q_pos, new_cache["kpos"],
                      window=window, softcap=cfg.attn_softcap, force_flash=flash)
    out = jnp.einsum("bse,ed->bsd", o.reshape(B, S, hq * hd), p["wo"])
    return out, new_cache
