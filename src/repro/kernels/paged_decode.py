"""Pallas TPU kernel: paged decode attention over the shared KV pool.

TPU-native adaptation of vLLM's PagedAttention: the decode worker's KV lives
in a paged pool; each sequence owns a block table mapping logical pages ->
physical pages. PrefillShare hands off *base-model* pages to every decode
worker, so the pool layout is the cross-model-shared artifact.

Pool layout: ``(P, page, Hkv * D)`` — the heads of one token row are
contiguous — optionally stacked on a leading layer axis ``(L, P, page,
Hkv * D)`` and read at ``layer``, so a layer-scanned model step hands the
kernel the whole stacked pool instead of slicing (copying) one layer out of
it. One page of one layer is thus a contiguous ``(page, Hkv * D)`` slab,
every kv head at once.

The grid is one step per decode row. The pools stay in HBM; the step walks
the row's pages in blocks of ``pages_per_block`` whole pages, one async copy
per page slab into a double-buffered VMEM block, and starts the copies of
the next block (or of the next row's first block) before it computes the
current one. Each kv head reads its ``D`` lanes of the block against its
whole GQA query group, with the online softmax (f32 scores, max, sum and
accumulator) carried per head across blocks.

What it skips: a row walks ``cdiv(length, block)`` blocks, not the table's
(bucketed) width, and copies only its live pages, so table columns past the
row's length cost neither a copy nor a grid step; a padding row (length 1)
costs one page. Only the last block masks keys past the length, and zeroes
its value rows (the unfetched part of the block holds stale VMEM). The
layer index, block tables and lengths ride in scalar prefetch (SMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
#: most keys one block covers, and the VMEM its double-buffered K and V
#: slabs may take together
BLOCK_TOKENS = 512
BUFFER_BYTES = 4 << 20


def pages_per_block(page: int, width: int, itemsize: int,
                    npages: int) -> int:
    """Whole pages per block, from the static shapes: at most
    ``BLOCK_TOKENS`` keys, two slots of K and V within ``BUFFER_BYTES``, and
    no wider than the table."""
    by_tokens = BLOCK_TOKENS // page
    by_vmem = BUFFER_BYTES // (4 * page * width * itemsize)
    return max(1, min(by_tokens, by_vmem, npages))


def _kernel(layer_ref, tables_ref, lengths_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, slot_ref, *, scale: float, softcap: float,
            page: int, ppb: int, npages: int, hkv: int, head_dim: int,
            rows: int):
    b = pl.program_id(0)
    block = ppb * page
    layer = layer_ref[0]

    def copies(row, blk, slot, action):
        """Start or wait the copies of block ``blk`` of ``row``: each live
        page's K and V slab into ``slot``."""
        first = blk * ppb
        live = (lengths_ref[row] + page - 1) // page
        n = jnp.clip(live - first, 0, ppb)

        def one(i, carry):
            pg = tables_ref[row * npages + first + i]
            dst = pl.ds(pl.multiple_of(i * page, page), page)
            for s, (hbm, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                cp = pltpu.make_async_copy(hbm.at[layer, pg],
                                           buf.at[slot, dst], sems.at[s, slot])
                getattr(cp, action)()
            return carry
        jax.lax.fori_loop(0, n, one, 0)

    @pl.when(b == 0)
    def _prime():
        copies(0, 0, 0, "start")
        slot_ref[0] = 0

    length = lengths_ref[b]
    s0 = slot_ref[0]
    nblk = jnp.maximum((length + block - 1) // block, 1)

    def attend(slot, blk, carry, masked):
        first = blk * block
        live = first + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
        live_rows = first + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
        live, live_rows = live < length, live_rows < length
        out = []
        for h, (m, l, acc) in enumerate(carry):
            lanes = pl.ds(h * head_dim, head_dim)
            q = q_ref[0, h]                                   # (group, D)
            k = k_buf[slot, :, lanes]                         # (block, D)
            # bf16 x bf16 products are exact in f32: f32 scores as before
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            v = v_buf[slot, :, lanes]
            if masked:
                s = jnp.where(live, s, NEG)
                v = jnp.where(live_rows, v, 0)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1, keepdims=True)
            acc = acc * corr + jax.lax.dot_general(
                p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            out.append((m_new, l, acc))
        return out

    def body(blk, carry):
        slot = (s0 + blk) % 2
        copies(b, blk + 1, 1 - slot, "start")
        copies(b, blk, slot, "wait")
        return attend(slot, blk, carry, masked=False)

    group = q_ref.shape[2]
    init = [(jnp.full((group, 1), NEG, jnp.float32),
             jnp.zeros((group, 1), jnp.float32),
             jnp.zeros((group, head_dim), jnp.float32))] * hkv
    carry = jax.lax.fori_loop(0, nblk - 1, body, init)

    last = nblk - 1
    slot = (s0 + last) % 2

    @pl.when(b + 1 < rows)
    def _next_row():
        copies(b + 1, 0, 1 - slot, "start")
    slot_ref[0] = 1 - slot

    copies(b, last, slot, "wait")
    for h, (_, l, acc) in enumerate(attend(slot, last, carry, masked=True)):
        o_ref[0, h] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def as_layer_stack(k_pages, v_pages, layer):
    """Normalize a pool to the kernels' layer-stacked form: a single layer's
    ``(P, page, F)`` pool becomes a one-layer stack read at layer 0; the
    layer index becomes the ``(1,)`` int32 array scalar prefetch takes."""
    if k_pages.ndim == 3:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    return k_pages, v_pages, jnp.asarray(layer, jnp.int32).reshape(1)


# jitted, so each shape's kernel is traced once per process: a decode step
# program calls it once per lane, and set-up builds many such programs
@functools.partial(jax.jit, static_argnames=("softcap", "scale", "interpret"))
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
    lengths:      (B,) int32 valid KV length per sequence, at most
                  ``npages * page_size``
    layer:        int32 scalar: the layer a stacked pool is read at
    returns       (B, Hq, D)
    """
    B, Hq, D = q.shape
    k_pages, v_pages, layer = as_layer_stack(k_pages, v_pages, layer)
    page, width = k_pages.shape[2], k_pages.shape[3]
    hkv = width // D
    npages = block_tables.shape[1]
    group = Hq // hkv
    scale = D ** -0.5 if scale is None else scale
    ppb = pages_per_block(page, width, k_pages.dtype.itemsize, npages)

    kernel = functools.partial(_kernel, scale=scale, softcap=softcap,
                               page=page, ppb=ppb, npages=npages, hkv=hkv,
                               head_dim=D, rows=B)

    def row_map(b, *_):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, hkv, group, D), row_map),   # the row's heads
            pl.BlockSpec(memory_space=pl.ANY),            # pools stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hkv, group, D), row_map),
        scratch_shapes=[
            pltpu.VMEM((2, ppb * page, width), k_pages.dtype),
            pltpu.VMEM((2, ppb * page, width), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),              # (K/V, slot)
            pltpu.SMEM((1,), jnp.int32),                  # slot of row b's block 0
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, hkv, group, D), q.dtype),
        # the next row's first block is fetched by this step: sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode",
    )(layer, block_tables.reshape(-1).astype(jnp.int32),
      lengths.astype(jnp.int32), q.reshape(B, hkv, group, D),
      k_pages, v_pages)
    return out.reshape(B, Hq, D)
