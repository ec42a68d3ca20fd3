"""Pallas TPU kernel: flash prefill attention straight over the paged pool.

The chunked-prefill data path: a chunk of S freshly-embedded tokens (absolute
positions ``start .. start+S``) attends to the WHOLE sequence so far — the
cached prefix AND the chunk itself — reading K/V directly from the physical
pool pages named by the sequence's block table. This removes the dense
gather that ``base_prefill_paged`` does before every prefill (O(prefix)
HBM traffic per call): the prefix never leaves the pool.

Contract: the chunk's own K/V rows have already been scattered into their
pages (the model layer writes them before attending, exactly like the decode
step), so every query finds at least its own key. Causality falls out of the
absolute positions: page j holds keys ``j*page .. (j+1)*page``, and a key is
visible iff ``kpos <= qpos``. Pages entirely beyond the chunk end are skipped
whole (the same block-skip trick as the dense flash kernel).

Pool layout as in ``paged_decode``: ``(P, page, Hkv * D)`` or the
layer-stacked ``(L, P, page, Hkv * D)`` read at ``layer``; one kv head's
rows of a page are the ``(page, D)`` block at lane offset ``h * D``.

Grid: (batch, kv_head, page) — the layer index, block table and
per-sequence start positions ride in scalar prefetch, so the K/V BlockSpec
dereferences the page table while the previous page streams HBM->VMEM. The
full GQA query group for a kv head — all S chunk positions at once — is
processed per page fetch, amortizing each page read across ``group * S``
query rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_decode import as_layer_stack

NEG = -1e30


def _kernel(layer_ref, bt_ref, start_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, softcap: float,
            page: int, npages: int, chunk: int, rows: int):
    del layer_ref, bt_ref                   # consumed by the index maps
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start = start_ref[b]
    # whole-block skip: pages entirely past the chunk's last position hold
    # nothing any query may see (kpos > qpos for every row)
    live = j * page < start + chunk

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (rows, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (page, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        # row r = g*chunk + i -> query at absolute position start + i
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, page), 0)
        qpos = start + r % chunk
        kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (rows, page), 1)
        mask = kpos <= qpos
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


def flash_prefill_paged(q, k_pages, v_pages, block_tables, start, *,
                        layer=None, softcap: float = 0.0,
                        scale: float | None = None, interpret: bool = False):
    """Chunk-prefill attention over a paged KV pool.

    q:            (B, S, Hq, D) chunk queries; q[b, i] sits at absolute
                  position ``start[b] + i``
    k_pages:      (P, page_size, Hkv * D) physical key pool, or the
                  layer-stacked (L, P, page_size, Hkv * D) pool
    v_pages:      same layout as ``k_pages``
    block_tables: (B, npages) int32 logical->physical page ids (rows may be
                  zero-padded past a sequence's last page — masked out)
    start:        (B,) int32 absolute position of each chunk's first token;
                  the chunk's own K/V rows must already be in their pages
    layer:        int32 scalar: the layer a stacked pool is read at
    returns       (B, S, Hq, D)
    """
    B, S, Hq, D = q.shape
    k_pages, v_pages, layer = as_layer_stack(k_pages, v_pages, layer)
    page = k_pages.shape[2]
    Hkv = k_pages.shape[3] // D
    npages = block_tables.shape[1]
    group = Hq // Hkv
    rows = group * S
    scale = D ** -0.5 if scale is None else scale

    # (B, Hkv, group*S, D): all of a kv head's query rows, chunk-major per
    # group member (row r = g*S + i)
    qg = (q.reshape(B, S, Hkv, group, D)
           .transpose(0, 2, 3, 1, 4).reshape(B, Hkv, rows, D))

    kernel = functools.partial(_kernel, scale=scale, softcap=softcap,
                               page=page, npages=npages, chunk=S, rows=rows)

    def q_map(b, h, j, ly, bt, st):
        return (b, h, 0, 0)

    def page_map(b, h, j, ly, bt, st):
        return (ly[0], bt[b, j], 0, h)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, npages),
        in_specs=[
            pl.BlockSpec((1, 1, rows, D), q_map),
            pl.BlockSpec((1, 1, page, D), page_map),
            pl.BlockSpec((1, 1, page, D), page_map),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, D), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rows, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_prefill_paged",
    )(layer, block_tables, start.astype(jnp.int32), qg, k_pages, v_pages)
    return (out.reshape(B, Hkv, group, S, D)
               .transpose(0, 3, 1, 2, 4).reshape(B, S, Hq, D))
