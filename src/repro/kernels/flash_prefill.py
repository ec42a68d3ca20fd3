"""Pallas TPU kernel: fused flash attention for the (shared) prefill stage.

The prefill stage is PrefillShare's hot spot — the whole point of the paper is
to run it ONCE per shared prompt — so it must hit the MXU roofline. Blocked
online-softmax flash attention with:
  - GQA (the kv-head index map folds the q→kv group mapping, so K/V blocks are
    fetched once per kv head, not per q head),
  - causal + sliding-window masking with whole-block skipping (fully-masked
    K blocks are never computed, halving causal FLOPs),
  - Gemma-2-style attention logit softcap,
  - fp32 accumulation in VMEM scratch, bf16/f32 I/O.

Layout: q (B, Hq, S, D), k/v (B, Hkv, T, D) — head-major so a (block, D) tile
is contiguous in HBM and lands VMEM-aligned (D is a multiple of 128 for all
assigned archs except head_dim=64 archs, where the MXU tile is still fine with
lane padding).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, softcap: float,
            seq_k: int, bq: int, bk: int, nk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * bq
    k_start = ik * bk
    # whole-block skip: causal (K block entirely in the future) or window
    # (K block entirely before the window of every query in the Q block)
    live = k_start < seq_k
    if causal:
        live &= k_start <= q_start + bq - 1
    if window:
        live &= (k_start + bk - 1) > (q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < seq_k
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG)

        m_prev = m_scr[...]                                  # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new) * mask
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bk, D)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _final():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_prefill(q, k, v, *, causal: bool = True, window: int = 0,
                  softcap: float = 0.0, scale: float | None = None,
                  block_q: int = 512, block_k: int = 512,
                  interpret: bool = False):
    """q: (B, Hq, S, D); k, v: (B, Hkv, T, D) -> (B, Hq, S, D)."""
    B, Hq, S, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    group = Hq // Hkv
    scale = D ** -0.5 if scale is None else scale
    bq = min(block_q, S)
    while S % bq:
        bq //= 2
    bk = min(block_k, T)
    while T % bk:
        bk //= 2
    nq, nk = S // bq, T // bk

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        seq_k=T, bq=bq, bk=bk, nk=nk)

    return pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
