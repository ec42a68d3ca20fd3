"""jit'd public wrappers for the Pallas kernels.

On a real TPU runtime these dispatch to the Mosaic-compiled kernels; on CPU
``interpret=True`` executes the kernel bodies in Python for correctness
validation, and the model stack's pure-JAX flash path
(repro.models.attention) is the XLA-lowerable twin used by the dry-run.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.flash_prefill_paged import flash_prefill_paged
from repro.kernels.paged_decode import paged_decode_attention
from repro.runtime import on_tpu


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "scale", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale=None, interpret: bool | None = None):
    """Model-layout wrapper: q (B,S,Hq,D), k/v (B,T,Hkv,D) -> (B,S,Hq,D)."""
    interp = (not on_tpu()) if interpret is None else interpret
    o = flash_prefill(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                      v.transpose(0, 2, 1, 3), causal=causal, window=window,
                      softcap=softcap, scale=scale, interpret=interp)
    return o.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("softcap", "scale", "interpret"))
def paged_prefill(q, k_pages, v_pages, block_tables, start, *,
                  softcap: float = 0.0, scale=None,
                  interpret: bool | None = None):
    """Chunk-prefill attention over the paged pool: q (B, S, Hq, D) at
    absolute positions ``start[b] + i`` attends to prefix + chunk straight
    from the pages (no dense gather of the prefix)."""
    interp = (not on_tpu()) if interpret is None else interpret
    return flash_prefill_paged(q, k_pages, v_pages, block_tables, start,
                               softcap=softcap, scale=scale, interpret=interp)


@functools.partial(jax.jit, static_argnames=("softcap", "scale", "interpret"))
def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    softcap: float = 0.0, scale=None,
                    interpret: bool | None = None):
    interp = (not on_tpu()) if interpret is None else interpret
    return paged_decode_attention(q, k_pages, v_pages, block_tables, lengths,
                                  softcap=softcap, scale=scale,
                                  interpret=interp)
