"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret=True executes the kernel bodies in Python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import flash_attention, paged_attention
from repro.kernels.paged_decode import paged_decode_attention
from repro.kernels.ref import ref_flash_prefill, ref_paged_decode

KEY = jax.random.PRNGKey(42)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


FLASH_CASES = [
    # B, Hq, Hkv, S, T, D, window, softcap
    (2, 4, 2, 128, 128, 64, 0, 0.0),
    (1, 8, 8, 256, 256, 128, 0, 0.0),       # MHA
    (1, 8, 1, 192, 192, 64, 0, 0.0),        # MQA, non-pow2 seq
    (2, 4, 2, 128, 128, 64, 64, 0.0),       # sliding window
    (1, 4, 2, 256, 256, 128, 0, 50.0),      # softcap (gemma2)
    (1, 2, 1, 64, 320, 64, 0, 0.0),         # cross-len (cache prefix)
    (1, 4, 4, 96, 96, 32, 32, 30.0),        # window + softcap, odd sizes
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_prefill_vs_ref(case, dtype):
    B, Hq, Hkv, S, T, D, win, cap = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), dtype)
    o = flash_attention(q, k, v, window=win, softcap=cap, interpret=True)
    r = ref_flash_prefill(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), window=win,
                          softcap=cap).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


PAGED_CASES = [
    # B, Hq, Hkv, D, page, npages, pool, lengths (None: random), layers
    # (None: one layer's pool; else a stack read at its last layer)
    (3, 8, 2, 64, 16, 8, 40, None, None),
    (1, 4, 4, 128, 32, 4, 16, None, None),
    (2, 8, 1, 64, 16, 16, 64, None, None),    # MQA long table
    (4, 2, 2, 32, 8, 4, 20, None, None),
    # blocks of 32 pages over a 48-page table (not a multiple of the
    # block): a row ending mid-block, one ending on the block boundary, a
    # padding row of length 1, one filling the whole table
    (4, 8, 2, 64, 16, 48, 120, (600, 512, 1, 768), None),
    # a bucketed table far wider than the live pages (a dead tail)
    (2, 4, 2, 64, 8, 256, 40, (20, 70), None),
    # a layer-stacked pool read at layer 2
    (3, 8, 2, 64, 16, 40, 64, (33, 513, 640), 3),
    # qwen3-1.7b and qwen3-8b heads: (Hq, Hkv, D, page) = (16|32, 8, 128, 16)
    (3, 16, 8, 128, 16, 64, 80, (1, 512, 1000), 2),
    (3, 32, 8, 128, 16, 64, 80, (17, 700, 1024), None),
]


@pytest.mark.parametrize("case", PAGED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_vs_ref(case, dtype):
    B, Hq, Hkv, D, page, npages, P, lengths, layers = case
    ks = jax.random.split(KEY, 5)
    pool = (P, page, Hkv * D) if layers is None else (layers, P, page, Hkv * D)
    layer = None if layers is None else layers - 1
    q = jax.random.normal(ks[0], (B, Hq, D), dtype)
    kp = jax.random.normal(ks[1], pool, dtype)
    vp = jax.random.normal(ks[2], pool, dtype)
    bt = jax.random.randint(ks[3], (B, npages), 0, P)
    if lengths is None:
        ln = jax.random.randint(ks[4], (B,), 1, page * npages + 1)
    else:
        ln = jnp.asarray(lengths)
    ln = ln.astype(jnp.int32)
    o = paged_decode_attention(q, kp, vp, bt, ln, layer=layer,
                               interpret=True)
    r = ref_paged_decode(q, kp, vp, bt, ln, layer=layer)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_paged_softcap():
    B, Hq, Hkv, D, page, npages, P = 2, 4, 2, 64, 16, 4, 12
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, Hq, D))
    kp = jax.random.normal(ks[1], (P, page, Hkv * D))
    vp = jax.random.normal(ks[2], (P, page, Hkv * D))
    bt = jax.random.randint(ks[3], (B, npages), 0, P)
    ln = jnp.array([30, 64], jnp.int32)
    o = paged_attention(q, kp, vp, bt, ln, softcap=30.0, interpret=True)
    r = ref_paged_decode(q, kp, vp, bt, ln, softcap=30.0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5,
                               rtol=2e-5)


def test_flash_block_skipping_correct():
    """Whole-block skips (causal/window) must not change results."""
    B, Hq, Hkv, S, D = 1, 2, 1, 512, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    # small blocks -> many fully-masked blocks exercised
    from repro.kernels.flash_prefill import flash_prefill
    o = flash_prefill(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                      v.transpose(0, 2, 1, 3), window=128, block_q=64,
                      block_k=64, interpret=True)
    r = ref_flash_prefill(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), window=128)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5,
                               rtol=2e-5)


# ----------------------------------------------------------------------
# flash_prefill_paged (chunked prefill straight over the pool)

from repro.kernels.flash_prefill_paged import flash_prefill_paged
from repro.kernels.ops import paged_prefill
from repro.kernels.ref import ref_paged_prefill

PAGED_PREFILL_CASES = [
    # B, Hq, Hkv, D, page, npages, pool, S
    (2, 4, 2, 64, 8, 4, 16, 5),      # chunk boundary mid-page
    (1, 8, 1, 32, 16, 3, 8, 16),     # MQA, chunk == page
    (3, 2, 2, 64, 8, 6, 32, 7),      # MHA, ragged starts
    (1, 4, 2, 128, 16, 2, 8, 1),     # degenerate single-token chunk
]


@pytest.mark.parametrize("case", PAGED_PREFILL_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_prefill_paged_vs_ref(case, dtype):
    """Kernel vs the dense (materialized-softmax) reference over gathered
    pages, at per-sequence chunk start positions landing anywhere in a
    page."""
    B, Hq, Hkv, D, page, npages, P, S = case
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, S, Hq, D), dtype)
    kp = jax.random.normal(ks[1], (P, page, Hkv * D), dtype)
    vp = jax.random.normal(ks[2], (P, page, Hkv * D), dtype)
    bt = jax.random.randint(ks[3], (B, npages), 0, P)
    max_start = npages * page - S
    st = jax.random.randint(ks[4], (B,), 0, max_start + 1).astype(jnp.int32)
    o = paged_prefill(q, kp, vp, bt, st, interpret=True)   # jit'd wrapper
    r = ref_paged_prefill(q, kp, vp, bt, st)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_flash_prefill_paged_softcap():
    B, Hq, Hkv, D, page, npages, P, S = 2, 4, 2, 64, 8, 4, 12, 6
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, S, Hq, D))
    kp = jax.random.normal(ks[1], (P, page, Hkv * D))
    vp = jax.random.normal(ks[2], (P, page, Hkv * D))
    bt = jax.random.randint(ks[3], (B, npages), 0, P)
    st = jnp.array([3, 20], jnp.int32)
    o = flash_prefill_paged(q, kp, vp, bt, st, softcap=30.0, interpret=True)
    r = ref_paged_prefill(q, kp, vp, bt, st, softcap=30.0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5,
                               rtol=2e-5)


def test_flash_prefill_paged_matches_contiguous_flash():
    """Position logic end-to-end vs the DENSE flash reference: contiguous
    KV laid into identity-mapped pages, chunk = the last S positions of a
    causal sequence -> rows S.. of the full dense result."""
    B, Hq, Hkv, D, page, T, S = 1, 4, 2, 8, 8, 64, 24
    ks = jax.random.split(KEY, 3)
    k = jax.random.normal(ks[0], (B, T, Hkv, D))
    v = jax.random.normal(ks[1], (B, T, Hkv, D))
    q_full = jax.random.normal(ks[2], (B, T, Hq, D))
    kp = k.reshape(T // page, page, Hkv * D)
    vp = v.reshape(T // page, page, Hkv * D)
    bt = jnp.arange(T // page, dtype=jnp.int32)[None]
    st = jnp.array([T - S], jnp.int32)
    o = flash_prefill_paged(q_full[:, T - S:], kp, vp, bt, st,
                            interpret=True)
    full = ref_flash_prefill(q_full.transpose(0, 2, 1, 3),
                             k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(o), np.asarray(full[:, T - S:]),
                               atol=2e-5, rtol=2e-5)


# ----------------------------------------------------------------------
# paged_write (prefill -> paged pool bridge)

from repro.kernels.paged_write import paged_write
from repro.kernels.ref import ref_paged_write


@pytest.mark.parametrize("case", [
    # B, S, Hkv, D, page, pool
    (3, 64, 2, 32, 16, 24),
    (1, 32, 4, 64, 8, 12),
    (2, 128, 1, 128, 32, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_write_vs_ref(case, dtype):
    B, S, H, D, page, P = case
    npages = S // page
    ks = jax.random.split(KEY, 4)
    nk = jax.random.normal(ks[0], (B, S, H * D), dtype)
    nv = jax.random.normal(ks[1], (B, S, H * D), dtype)
    kp = jax.random.normal(ks[2], (P, page, H * D), dtype)
    vp = jax.random.normal(ks[3], (P, page, H * D), dtype)
    # disjoint page assignment across requests
    perm = np.random.RandomState(0).permutation(P)[: B * npages]
    bt = jnp.asarray(perm.reshape(B, npages), jnp.int32)
    nvalid = jnp.asarray(np.random.RandomState(1).randint(1, npages + 1, B),
                         jnp.int32)
    ko, vo = paged_write(nk, nv, kp, vp, bt, nvalid, interpret=True)
    rk, rv = ref_paged_write(nk, nv, kp, vp, bt, nvalid)
    np.testing.assert_array_equal(np.asarray(ko, np.float32),
                                  np.asarray(rk, np.float32))
    np.testing.assert_array_equal(np.asarray(vo, np.float32),
                                  np.asarray(rv, np.float32))


def test_paged_roundtrip_write_then_read():
    """Pages written by paged_write are read back by paged_decode_attention."""
    B, S, H, D, page, P = 2, 64, 2, 64, 16, 16
    npages = S // page
    ks = jax.random.split(KEY, 3)
    nk = jax.random.normal(ks[0], (B, S, H * D))
    nv = jax.random.normal(ks[1], (B, S, H * D))
    kp = jnp.zeros((P, page, H * D))
    vp = jnp.zeros((P, page, H * D))
    bt = jnp.arange(B * npages, dtype=jnp.int32).reshape(B, npages)
    nvalid = jnp.full((B,), npages, jnp.int32)
    kp, vp = paged_write(nk, nv, kp, vp, bt, nvalid, interpret=True)
    q = jax.random.normal(ks[2], (B, 4, D))
    ln = jnp.full((B,), S, jnp.int32)
    o = paged_attention(q, kp, vp, bt, ln, interpret=True)
    # reference: direct attention against the contiguous new KV
    r = ref_paged_decode(q, kp, vp, bt, ln)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-5)
