"""Pure-jnp oracles for the Pallas kernels (tests assert_allclose against these)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG = -1e30


def ref_flash_prefill(q, k, v, *, causal: bool = True, window: int = 0,
                      softcap: float = 0.0, scale: float | None = None):
    """q: (B,Hq,S,D); k/v: (B,Hkv,T,D) -> (B,Hq,S,D). Full materialized softmax."""
    B, Hq, S, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    g = Hq // Hkv
    scale = D ** -0.5 if scale is None else scale
    qg = q.reshape(B, Hkv, g, S, D).astype(jnp.float32) * scale
    s = jnp.einsum("bhgsd,bhtd->bhgst", qg, k.astype(jnp.float32))
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgst,bhtd->bhgsd", p, v.astype(jnp.float32))
    return o.reshape(B, Hq, S, D).astype(q.dtype)


def _gather_pages(k_pages, v_pages, block_tables, layer, D):
    """Pool pages named by ``block_tables`` as contiguous (B, T, Hkv, D) K/V.
    Pools are (P, page, Hkv * D), or layer-stacked (L, P, page, Hkv * D)
    and read at ``layer`` (the kernels' contract)."""
    idx = block_tables if k_pages.ndim == 3 else (layer, block_tables)
    B, npages = block_tables.shape
    page, F = k_pages.shape[-2], k_pages.shape[-1]
    shape = (B, npages * page, F // D, D)
    return k_pages[idx].reshape(shape), v_pages[idx].reshape(shape)


def ref_paged_decode(q, k_pages, v_pages, block_tables, lengths, *,
                     layer=None, softcap: float = 0.0,
                     scale: float | None = None):
    """Gather pages into contiguous KV, then masked softmax attention."""
    B, Hq, D = q.shape
    k, v = _gather_pages(k_pages, v_pages, block_tables, layer, D)
    T, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    scale = D ** -0.5 if scale is None else scale

    qg = q.reshape(B, Hkv, g, D).astype(jnp.float32) * scale
    s = jnp.einsum("bhgd,bthd->bhgt", qg, k.astype(jnp.float32))
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    mask = jnp.arange(T)[None] < lengths[:, None]          # (B, T)
    s = jnp.where(mask[:, None, None, :], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgt,bthd->bhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Hq, D).astype(q.dtype)


def ref_paged_prefill(q, k_pages, v_pages, block_tables, start, *,
                      layer=None, softcap: float = 0.0,
                      scale: float | None = None):
    """Chunk-prefill attention over pages: gather the block table into
    contiguous KV, then a materialized causal softmax at absolute positions
    (q[b, i] sits at ``start[b] + i``). Mirrors ``_direct``'s op ordering so
    chunked and dense prefill agree token-for-token."""
    B, S, Hq, D = q.shape
    k, v = _gather_pages(k_pages, v_pages, block_tables, layer, D)
    T, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    scale = D ** -0.5 if scale is None else scale

    qg = (q.astype(jnp.float32) * scale).reshape(B, S, Hkv, g, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32))
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    qpos = start[:, None] + jnp.arange(S, dtype=jnp.int32)[None]     # (B, S)
    kpos = jnp.arange(T, dtype=jnp.int32)                            # (T,)
    mask = kpos[None, None, None, None, :] <= qpos[:, None, None, :, None]
    s = jnp.where(mask, s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, S, Hq, D).astype(q.dtype)


def ref_paged_write(new_k, new_v, k_pages, v_pages, block_tables, n_valid):
    """Scatter new KV rows into assigned pages (numpy-style oracle)."""
    import numpy as np
    B, S, F = new_k.shape
    page = k_pages.shape[1]
    npages = S // page
    ko = np.array(k_pages)
    vo = np.array(v_pages)
    nk = np.array(new_k).reshape(B, npages, page, F)
    nv = np.array(new_v).reshape(B, npages, page, F)
    bt = np.array(block_tables)
    for b in range(B):
        for j in range(int(n_valid[b])):
            ko[bt[b, j]] = nk[b, j]
            vo[bt[b, j]] = nv[b, j]
    return jnp.asarray(ko), jnp.asarray(vo)
