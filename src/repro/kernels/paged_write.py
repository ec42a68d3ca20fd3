"""Pallas TPU kernel: scatter freshly-prefilled KV into the paged pool.

The bridge between PrefillShare's shared prefill stage and the paged decode
pool: after the base model prefills (or partially prefills) a prompt, the new
K/V rows for tokens [pos, pos+S) are written into the physical pages assigned
by the block table. Grid iterates (batch, page-span); the block table rides in
scalar prefetch so the OUTPUT BlockSpec's index map selects the physical page
while the previous page is still being written. The pool is updated in place
via input-output aliasing (no copy of the multi-GB pool).

Assumes page-aligned writes (pos % page_size == 0) — the engine always
extends caches at block granularity, padding partial tails (vLLM does the
same).
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(tables_ref, nvalid_ref, new_k_ref, new_v_ref, kpool_ref,
            vpool_ref, kout_ref, vout_ref, *, page: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j < nvalid_ref[b])
    def _write():
        kout_ref[...] = new_k_ref[...]
        vout_ref[...] = new_v_ref[...]

    @pl.when(j >= nvalid_ref[b])
    def _keep():
        # page not owned by this request: preserve pool contents
        kout_ref[...] = kpool_ref[...]
        vout_ref[...] = vpool_ref[...]


def paged_write(new_k, new_v, k_pages, v_pages, block_tables, n_valid, *,
                interpret: bool = False):
    """Write per-request new KV rows into their assigned physical pages.

    new_k/new_v:  (B, S, F) freshly computed KV rows, F = Hkv * D
                  (S = n_pages * page)
    k/v_pages:    (P, page, F) physical pools (updated in place)
    block_tables: (B, npages) int32 physical page per logical page
    n_valid:      (B,) int32 number of valid pages per request
    returns updated (k_pages, v_pages)
    """
    B, S, F = new_k.shape
    page = k_pages.shape[1]
    npages = S // page
    assert npages == block_tables.shape[1], (npages, block_tables.shape)

    kernel = functools.partial(_kernel, page=page)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, npages),
        in_specs=[
            pl.BlockSpec((1, page, F), lambda b, j, bt, nv: (b, j, 0)),
            pl.BlockSpec((1, page, F), lambda b, j, bt, nv: (b, j, 0)),
            pl.BlockSpec((1, page, F),
                         lambda b, j, bt, nv: (bt[b, j], 0, 0)),
            pl.BlockSpec((1, page, F),
                         lambda b, j, bt, nv: (bt[b, j], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, page, F),
                         lambda b, j, bt, nv: (bt[b, j], 0, 0)),
            pl.BlockSpec((1, page, F),
                         lambda b, j, bt, nv: (bt[b, j], 0, 0)),
        ],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)],
        input_output_aliases={4: 0, 5: 1},   # pools updated in place
        # grid points may alias pool revisions (bt is data-dependent): keep
        # the page axis sequential; requests are independent.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_write",
    )(block_tables, n_valid, new_k, new_v, k_pages, v_pages)
