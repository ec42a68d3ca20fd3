"""Pallas TPU kernel: paged decode attention over the shared KV pool.

TPU-native adaptation of vLLM's PagedAttention: the decode worker's KV lives
in a paged pool; each sequence owns a block table mapping logical pages ->
physical pages. PrefillShare hands off *base-model* pages to every decode
worker, so the pool layout is the cross-model-shared artifact.

Pool layout: ``(P, page, Hkv * D)`` — the heads of one token row are
contiguous — optionally stacked on a leading layer axis ``(L, P, page,
Hkv * D)`` and read at ``layer``, so a layer-scanned model step hands the
kernel the whole stacked pool instead of slicing (copying) one layer out of
it. One kv head's rows of one page are the ``(page, D)`` block at lane
offset ``h * D``: a legal TPU block (``page`` a multiple of 8, ``D`` of 128)
that a strided DMA fetches straight from HBM.

The layer index, block table and sequence lengths ride in scalar-prefetch
(SMEM) via ``PrefetchScalarGridSpec``, so the K/V BlockSpec index maps
dereference the page table while the previous page streams HBM->VMEM. Grid
iterates (batch, kv_head, page); the full GQA query group for a kv head is
processed together (q block (group, D)), amortizing each K/V page fetch
across the group.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(layer_ref, block_tables_ref, lengths_ref, q_ref, k_ref, v_ref,
            o_ref, m_scr, l_scr, acc_scr, *, scale: float, softcap: float,
            page: int, npages: int, group: int):
    del layer_ref, block_tables_ref         # consumed by the index maps
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lengths_ref[b]
    live = j * page < length

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (group, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (page, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (group, page), 1)
        mask = kpos < length
        s = jnp.where(mask, s, NEG)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new) * mask
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == npages - 1)
    def _final():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def as_layer_stack(k_pages, v_pages, layer):
    """Normalize a pool to the kernels' layer-stacked form: a single layer's
    ``(P, page, F)`` pool becomes a one-layer stack read at layer 0; the
    layer index becomes the ``(1,)`` int32 array scalar prefetch takes."""
    if k_pages.ndim == 3:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    return k_pages, v_pages, jnp.asarray(layer, jnp.int32).reshape(1)


def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths, *,
                           layer=None, softcap: float = 0.0,
                           scale: float | None = None,
                           interpret: bool = False):
    """Single-token decode attention over a paged KV pool.

    q:            (B, Hq, D) current-step queries
    k_pages:      (P, page_size, Hkv * D) physical key pool, or the
                  layer-stacked (L, P, page_size, Hkv * D) pool
    v_pages:      same layout as ``k_pages``
    block_tables: (B, npages) int32 logical->physical page ids
    lengths:      (B,) int32 valid KV length per sequence
    layer:        int32 scalar: the layer a stacked pool is read at
    returns       (B, Hq, D)
    """
    B, Hq, D = q.shape
    k_pages, v_pages, layer = as_layer_stack(k_pages, v_pages, layer)
    page = k_pages.shape[2]
    Hkv = k_pages.shape[3] // D
    npages = block_tables.shape[1]
    group = Hq // Hkv
    scale = D ** -0.5 if scale is None else scale

    kernel = functools.partial(_kernel, scale=scale, softcap=softcap,
                               page=page, npages=npages, group=group)

    def q_map(b, h, j, ly, bt, ln):
        return (b, h, 0, 0)

    def page_map(b, h, j, ly, bt, ln):
        return (ly[0], bt[b, j], 0, h)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, npages),
        in_specs=[
            pl.BlockSpec((1, 1, group, D), q_map),   # whole group for (b, h)
            # head h's rows of the page the block table names
            pl.BlockSpec((1, 1, page, D), page_map),
            pl.BlockSpec((1, 1, page, D), page_map),
        ],
        out_specs=pl.BlockSpec((1, 1, group, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, D), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode",
    )(layer, block_tables, lengths, q.reshape(B, Hkv, group, D),
      k_pages, v_pages)
    return out.reshape(B, Hq, D)
