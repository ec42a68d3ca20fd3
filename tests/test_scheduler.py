"""Chunked-prefill scheduler: bit-identity vs the unchunked paged path
across chunk sizes, chunk boundaries mid-page, zero-length tails on full
prefix hits, admission/backpressure under PoolExhausted, and priority
ordering."""
import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.kvcache.blocks import PoolExhausted
from repro.models import init_params
from repro.serving.api import SamplingParams
from repro.serving.engine import LocalDisaggEngine

CFG = ModelConfig(name="sched-eng", arch_type="dense", n_layers=2, d_model=32,
                  n_heads=2, n_kv_heads=1, d_ff=64, vocab_size=64,
                  dtype="float32")
PAGE = 8


def _params():
    base = init_params(CFG, jax.random.PRNGKey(0))
    decs = {f"m{i}": init_params(CFG, jax.random.PRNGKey(10 + i))
            for i in range(2)}
    return base, decs


def _engine(base, decs, **kw):
    kw.setdefault("num_pages", 64)
    kw.setdefault("page_size", PAGE)
    return LocalDisaggEngine(CFG, base, decs, **kw)


def _reference_run(base, decs):
    """Greedy outputs from the unchunked paged path (today's behaviour)."""
    eng = _engine(base, decs)
    rng = np.random.default_rng(0)
    ctx = list(rng.integers(4, 60, size=19))
    outs = []
    for mid in ("m0", "m1"):
        ctx += list(rng.integers(4, 60, size=5))
        out = eng.invoke(0, ctx, mid, gen_tokens=4)
        outs.append(out)
        ctx += list(out)
    return outs, eng.stats


@pytest.mark.parametrize("chunk", [3, 5, 8, 64])
def test_chunked_bit_identical_across_chunk_sizes(chunk):
    """Greedy tokens are token-for-token equal to the unchunked paged path
    for every chunk size — including chunk >= prompt length, which
    degenerates to today's whole-tail prefill — across multi-turn context
    growth and two decode models."""
    base, decs = _params()
    ref, ref_stats = _reference_run(base, decs)

    eng = _engine(base, decs, chunked=True, chunk_size=chunk, token_budget=16)
    rng = np.random.default_rng(0)
    ctx = list(rng.integers(4, 60, size=19))
    for mid, want in zip(("m0", "m1"), ref):
        ctx += list(rng.integers(4, 60, size=5))
        got = eng.invoke(0, ctx, mid, gen_tokens=4)
        np.testing.assert_array_equal(got, want)
        ctx += list(got)
    # same accounting as the eager path: chunking changes the schedule,
    # never the amount of compute or reuse
    assert eng.stats.prefill_tokens_computed == ref_stats.prefill_tokens_computed
    assert eng.stats.prefill_tokens_reused == ref_stats.prefill_tokens_reused
    if chunk < 19:
        assert eng.scheduler.stats.chunks > 1
    eng.end_session(0)
    eng.block_pool.check_invariants()


def test_chunk_boundary_mid_page():
    """Chunk boundaries landing mid-page (chunk % page != 0): the next chunk
    keeps appending into the same physical page via the unaligned scatter."""
    base, decs = _params()
    ref = _engine(base, decs)
    rng = np.random.default_rng(3)
    ctx = list(rng.integers(4, 60, size=19))        # pages: 2 full + partial
    want = ref.invoke(0, ctx, "m0", gen_tokens=5)

    eng = _engine(base, decs, chunked=True, chunk_size=6, token_budget=32)
    got = eng.invoke(0, ctx, "m0", gen_tokens=5)    # boundaries at 6,12,18
    np.testing.assert_array_equal(got, want)
    # 19 tokens in 6-token chunks: 6+6+6+1 -> 4 chunks, but only 3 pages
    assert eng.scheduler.stats.chunks == 4
    sess = eng.prefill_workers[0].sessions[0]
    assert len(sess.block_table) == 3
    eng.end_session(0)
    eng.block_pool.check_invariants()
    assert eng.block_pool.active_count == 0


def test_zero_length_tail_after_full_prefix_hit():
    """A prompt fully covered by cached pages (page-aligned length) needs
    ZERO prefill chunks: the request goes straight from admission to the
    decode handoff."""
    base, decs = _params()
    ref = _engine(base, decs)
    rng = np.random.default_rng(4)
    ctx = list(rng.integers(4, 60, size=2 * PAGE))  # exactly 2 full pages
    want0 = ref.invoke(0, ctx, "m0", gen_tokens=4)
    want1 = ref.invoke(1, ctx, "m1", gen_tokens=4)

    eng = _engine(base, decs, chunked=True, chunk_size=4, token_budget=32)
    got0 = eng.invoke(0, ctx, "m0", gen_tokens=4)
    np.testing.assert_array_equal(got0, want0)
    computed = eng.stats.prefill_tokens_computed
    chunks = eng.scheduler.stats.chunks

    got1 = eng.invoke(1, ctx, "m1", gen_tokens=4)   # radix full-prefix hit
    np.testing.assert_array_equal(got1, want1)
    assert eng.stats.prefill_tokens_computed == computed   # nothing computed
    assert eng.scheduler.stats.chunks == chunks            # zero-length tail
    assert eng.stats.prefill_tokens_reused >= 2 * PAGE
    eng.end_session(0)
    eng.end_session(1)
    eng.block_pool.check_invariants()


def test_sibling_submit_chunked_fast_path():
    """Two decode models fanning out over one identical context: the second
    request is held until the first commits, then served from the live
    session's pages without recomputing."""
    base, decs = _params()
    ref = _engine(base, decs)
    rng = np.random.default_rng(5)
    ctx = list(rng.integers(4, 60, size=20))
    w0 = ref.invoke(0, ctx, "m0", gen_tokens=3)
    w1 = ref.invoke(0, ctx, "m1", gen_tokens=3)

    eng = _engine(base, decs, chunked=True, chunk_size=8, token_budget=32)
    r0 = eng.submit(0, ctx, "m0", gen_tokens=3)
    r1 = eng.submit(0, ctx, "m1", gen_tokens=3)
    eng.run()
    np.testing.assert_array_equal(eng.result(r0), w0)
    np.testing.assert_array_equal(eng.result(r1), w1)
    assert eng.stats.prefill_tokens_computed == 20         # computed ONCE
    assert eng.stats.prefill_tokens_reused == 20           # sibling reuse
    assert eng.stats.cow_page_copies == 2                  # one clone each
    eng.end_session(0)
    eng.block_pool.check_invariants()


def test_sibling_pages_pinned_across_leader_session_end():
    """The sibling fast path pins the leader session's pages at ADMISSION:
    if the leader session ends before the (possibly deferred) promotion,
    the pages must stay active — not drop to CACHED where another request
    could evict and reuse them."""
    base, decs = _params()
    ref = _engine(base, decs)
    rng = np.random.default_rng(10)
    ctx = list(rng.integers(4, 60, size=2 * PAGE))  # aligned: no CoW clone
    ref.invoke(0, ctx, "m0", gen_tokens=3)
    want = ref.invoke(0, ctx, "m1", gen_tokens=3)

    eng = _engine(base, decs, chunked=True, chunk_size=8, token_budget=32)
    eng.invoke(0, ctx, "m0", gen_tokens=3)          # leader session resident
    rid = eng.submit(0, ctx, "m1", gen_tokens=3)
    eng.scheduler._admit()                          # sibling captured + pinned
    eng.end_session(0)                              # leader lets go
    sib_bt = eng.scheduler.prefilling[0].sibling_bt
    for p in sib_bt:
        assert eng.block_pool.refcount(p) >= 1      # pin holds pages active
    eng.run()
    np.testing.assert_array_equal(eng.result(rid), want)
    eng.block_pool.check_invariants()
    assert eng.block_pool.active_count == 0


def test_admission_hard_pool_exhaustion_raises():
    """A prompt the pool can never host fails loudly (no silent spin): the
    scheduler raises PoolExhausted once no step can make progress."""
    base, decs = _params()
    eng = _engine(base, decs, num_pages=2, chunked=True, chunk_size=4,
                  token_budget=32)
    ctx = list(np.random.default_rng(6).integers(4, 60, size=40))  # 5 pages
    eng.submit(0, ctx, "m0", gen_tokens=2)
    with pytest.raises(PoolExhausted):
        eng.run()


def test_backpressure_holds_request_until_decode_frees_pages():
    """Admission under PoolExhausted: a request whose chunk cannot obtain
    pages is HELD (its computed pages stay put) and completes once the
    running decode finishes and releases its private pages."""
    base, decs = _params()
    ref = _engine(base, decs)
    rng = np.random.default_rng(7)
    ctx_a = list(rng.integers(4, 60, size=18))
    ctx_b = list(rng.integers(4, 60, size=18))
    want_a = ref.invoke(0, ctx_a, "m0", gen_tokens=10)
    want_b = ref.invoke(1, ctx_b, "m1", gen_tokens=10)

    # pool sized so both sessions fit resident, but NOT both prefills plus
    # the first request's decode growth at once -> request B must stall
    eng = _engine(base, decs, num_pages=9, chunked=True, chunk_size=6,
                  token_budget=8)
    ra = eng.submit(0, ctx_a, "m0", gen_tokens=10)
    rb = eng.submit(1, ctx_b, "m1", gen_tokens=10)
    eng.run()
    np.testing.assert_array_equal(eng.result(ra), want_a)
    np.testing.assert_array_equal(eng.result(rb), want_b)
    assert eng.scheduler.stats.stalls > 0
    eng.end_session(0)
    eng.end_session(1)
    eng.block_pool.check_invariants()


def test_priority_policy_schedules_high_priority_first():
    """Under the priority policy a late-arriving high-priority request
    finishes prefill before an earlier low-priority long prompt."""
    base, decs = _params()
    eng = _engine(base, decs, chunked=True, chunk_size=8, token_budget=8,
                  sched_policy="priority")
    rng = np.random.default_rng(8)
    long_ctx = list(rng.integers(4, 60, size=48))
    short_ctx = list(rng.integers(4, 60, size=16))
    r_low = eng.submit(0, long_ctx, "m0", gen_tokens=2, priority=0)
    r_high = eng.submit(1, short_ctx, "m1", gen_tokens=2, priority=5)
    eng.run()
    assert eng.scheduler.promoted.index(r_high) < \
        eng.scheduler.promoted.index(r_low)
    eng.result(r_low), eng.result(r_high)


def test_equal_length_chunks_batch_into_one_forward():
    """Chunks of the same length from different requests run as ONE batched
    base-model forward (max_prefill_batch > 1), with outputs unchanged."""
    base, decs = _params()
    ref = _engine(base, decs)
    rng = np.random.default_rng(9)
    ctxs = [list(rng.integers(4, 60, size=24)) for _ in range(3)]
    wants = [ref.invoke(sid, c, "m0", gen_tokens=3)
             for sid, c in enumerate(ctxs)]

    eng = _engine(base, decs, chunked=True, chunk_size=8, token_budget=64)
    rids = [eng.submit(sid, c, "m0", gen_tokens=3)
            for sid, c in enumerate(ctxs)]
    eng.run()
    for rid, want in zip(rids, wants):
        np.testing.assert_array_equal(eng.result(rid), want)
    assert eng.scheduler.stats.max_prefill_batch >= 2


def test_duplicate_prefill_counted_once_per_shared_document():
    """Two fan-out requests over one 1,000-token document, admitted in the
    same step: neither has committed, so the radix tree serves the second
    nothing and it computes the document again. The document's tokens count
    once as duplicated; a third request admitted after both committed
    counts none."""
    base, decs = _params()
    eng = _engine(base, decs, num_pages=512, chunked=True, chunk_size=64,
                  token_budget=256)
    rng = np.random.default_rng(11)
    doc = list(rng.integers(4, 60, size=1000))
    a = eng.generate("m0", doc + [61, 62], SamplingParams(max_tokens=2))
    b = eng.generate("m1", doc + [62, 61], SamplingParams(max_tokens=2))
    eng.step()
    assert eng.stats.prefill_tokens_reused == 0
    assert eng.stats.prefill_tokens_duplicated == 1000
    eng.run()
    assert a.finished and b.finished
    assert eng.stats.prefill_tokens_computed == 2 * 1002
    c = eng.generate("m0", doc + [63], SamplingParams(max_tokens=2))
    eng.run()
    assert c.finished
    assert eng.stats.prefill_tokens_duplicated == 1000
    assert eng.stats()["prefill_tokens_duplicated"] == 1000
