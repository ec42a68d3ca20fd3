"""Request-centric serving API (repro.serving.api): SamplingParams executed
in the decode planes, streaming RequestOutputs with finish reasons, abort
page-accounting at every lifecycle stage, SharedContext sessions, the
deprecated legacy shim, and chunk block-table bucketing."""
import warnings

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import init_params
from repro.runtime import program_traces
from repro.serving.api import (FINISH_ABORT, FINISH_EOS, FINISH_LENGTH,
                               FINISH_STOP, SamplingParams)
from repro.serving.engine import LocalDisaggEngine

CFG = ModelConfig(name="api-eng", arch_type="dense", n_layers=2, d_model=32,
                  n_heads=2, n_kv_heads=1, d_ff=64, vocab_size=64,
                  dtype="float32")
PAGE = 8


@pytest.fixture(scope="module")
def params():
    base = init_params(CFG, jax.random.PRNGKey(0))
    decs = {f"m{i}": init_params(CFG, jax.random.PRNGKey(10 + i))
            for i in range(2)}
    return base, decs


def _engine(params, **kw):
    base, decs = params
    kw.setdefault("num_pages", 64)
    kw.setdefault("page_size", PAGE)
    return LocalDisaggEngine(CFG, base, decs, **kw)


def _legacy_invoke(eng, sid, ctx, mid, gen):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return eng.invoke(sid, ctx, mid, gen_tokens=gen)


def _ctx(seed=0, n=19):
    return list(np.random.default_rng(seed).integers(4, 60, size=n))


# ======================================================================
# SamplingParams semantics


def test_temperature_zero_bit_identical_to_legacy_greedy(params):
    """generate(temperature=0) reproduces the pre-redesign greedy path
    token-for-token — fused and per-model, eager and chunked."""
    ctx = _ctx(0)
    ref = _legacy_invoke(_engine(params), 0, ctx, "m0", 6)
    for kw in (dict(),                                     # fused, eager
               dict(fused=False),                          # per-model, eager
               dict(chunked=True, chunk_size=5, token_budget=16)):  # chunked
        eng = _engine(params, **kw)
        out = eng.generate("m0", ctx, SamplingParams(max_tokens=6))
        np.testing.assert_array_equal(out.result(), ref, err_msg=str(kw))
        assert out.finish_reason == FINISH_LENGTH


def test_seeded_sampling_reproducible_regardless_of_batch_packing(params):
    """A seeded sampled stream depends only on (request, seed): running the
    same request alone, alongside other traffic, and under the chunked
    scheduler yields the SAME tokens (keys fold from (seed, position))."""
    ctx = _ctx(1)
    sp = SamplingParams(max_tokens=6, temperature=0.8, top_k=12, seed=7)

    solo = _engine(params).generate("m0", ctx, sp).result()
    assert len(set(solo.tolist())) > 1 or True        # stream materialized

    busy = _engine(params)
    busy.generate("m1", _ctx(2, 13), SamplingParams(max_tokens=9,
                                                    temperature=1.3, seed=3))
    busy.generate("m0", _ctx(3, 27), SamplingParams(max_tokens=4))
    got = busy.generate("m0", ctx, sp)
    busy.run()
    np.testing.assert_array_equal(solo, got.tokens)

    chunked = _engine(params, chunked=True, chunk_size=5, token_budget=16)
    chunked.generate("m1", _ctx(2, 13), SamplingParams(max_tokens=9,
                                                       temperature=1.3, seed=3))
    got2 = chunked.generate("m0", ctx, sp)
    chunked.run()
    np.testing.assert_array_equal(solo, got2.tokens)


def test_default_seed_gives_independent_fanout_draws(params):
    """seed=None (the default) means engine-assigned per-request seeds: N
    sampled generations over the SAME prompt and model are N different
    draws, not N copies of one stream."""
    eng = _engine(params)
    ctx = _ctx(30)
    sp = SamplingParams(max_tokens=6, temperature=1.0)
    assert sp.seed is None
    outs = [eng.generate("m0", ctx, sp) for _ in range(3)]
    eng.run()
    streams = [tuple(o.tokens) for o in outs]
    assert len(set(streams)) > 1, streams
    seeds = [o.params.seed for o in outs]       # resolved, visible, distinct
    assert len(set(seeds)) == 3 and None not in seeds


def test_abort_from_stream_callback_does_not_corrupt_other_streams(params):
    """RequestOutput.abort() invoked from INSIDE a stream callback (the
    'first agent answered, cancel the rest' pattern) fires mid decode-step:
    the step must finish with its original token/sequence alignment, so the
    surviving streams are unaffected."""
    ctxs = [_ctx(31 + i) for i in range(3)]
    refs = [_engine(params).generate(
        "m0", c, SamplingParams(max_tokens=6)).result() for c in ctxs]

    eng = _engine(params)
    outs = {}

    def killer(ro, tok):
        if len(ro.tokens) == 2:
            outs["b"].abort()                   # re-enters the engine

    outs["a"] = eng.generate("m0", ctxs[0], SamplingParams(max_tokens=6),
                             stream_callback=killer)
    outs["b"] = eng.generate("m0", ctxs[1], SamplingParams(max_tokens=6))
    outs["c"] = eng.generate("m0", ctxs[2], SamplingParams(max_tokens=6))
    eng.run()
    np.testing.assert_array_equal(outs["a"].result(), refs[0])
    np.testing.assert_array_equal(outs["c"].result(), refs[2])
    assert outs["b"].finish_reason == FINISH_ABORT
    # the abort fired during A's token-2 bookkeeping, BEFORE B's token-2 was
    # delivered: B keeps the delivered prefix of its reference stream (the
    # in-flight token is dropped, not mis-delivered)
    n = len(outs["b"].tokens)
    assert 1 <= n < 6
    np.testing.assert_array_equal(outs["b"].tokens, refs[1][:n])
    eng.block_pool.check_invariants()


def test_top_k_one_is_greedy_even_at_high_temperature(params):
    ctx = _ctx(4)
    greedy = _engine(params).generate(
        "m0", ctx, SamplingParams(max_tokens=5)).result()
    forced = _engine(params).generate(
        "m0", ctx, SamplingParams(max_tokens=5, temperature=5.0,
                                  top_k=1, seed=11)).result()
    np.testing.assert_array_equal(greedy, forced)


def test_top_p_renormalizes_over_top_k_survivors():
    """Nucleus filtering operates on the distribution AFTER top-k, not the
    raw one: with probs (.4,.3,.2,.1), top_k=2 renormalizes to (4/7, 3/7),
    so top_p=0.55 keeps only the argmax (exclusive mass of the runner-up is
    4/7 > 0.55) — the unrenormalized cut (0.4 < 0.55) would keep both."""
    import jax.numpy as jnp
    from repro.serving.sampling import fold_keys, sample_logits
    lg = jnp.log(jnp.array([[0.4, 0.3, 0.2, 0.1]], jnp.float32))
    keys = fold_keys(jnp.arange(1, dtype=jnp.int32),
                     jnp.arange(1, dtype=jnp.int32))
    for seed_pos in range(20):
        keys = fold_keys(jnp.array([seed_pos], jnp.int32),
                         jnp.array([seed_pos], jnp.int32))
        tok = sample_logits(lg, jnp.array([1.0], jnp.float32),
                            jnp.array([2], jnp.int32),
                            jnp.array([0.55], jnp.float32), keys)
        assert int(tok[0]) == 0, seed_pos
    # sanity: without the top-k squeeze, top_p=0.55 keeps tokens {0, 1}
    seen = set()
    for seed_pos in range(40):
        keys = fold_keys(jnp.array([seed_pos], jnp.int32),
                         jnp.array([seed_pos], jnp.int32))
        tok = sample_logits(lg, jnp.array([1.0], jnp.float32),
                            jnp.array([0], jnp.int32),
                            jnp.array([0.55], jnp.float32), keys)
        seen.add(int(tok[0]))
    assert seen == {0, 1}


def test_abort_after_final_token_before_reap_is_not_an_abort(params):
    """A sequence that already produced its last token but has not been
    reaped yet (reaping happens at the next step's top) is COMPLETE: abort
    must refuse, and the result must still materialize."""
    eng = _engine(params)
    out = eng.generate("m0", _ctx(22), SamplingParams(max_tokens=3))
    for _ in range(3):
        eng.step()
    assert len(out.tokens) == 3 and not out.finished   # generated, unreaped
    assert eng.abort(out) is False
    np.testing.assert_array_equal(out.result(), out.tokens)
    assert out.finish_reason == FINISH_LENGTH
    eng.block_pool.check_invariants()


def test_dense_fallback_generate_streams_to_callback(params):
    """paged=False (the SSM/hybrid fallback path) honours stream_callback
    and the RequestOutput contract even though generation is synchronous."""
    eng = _engine(params, paged=False, capacity=64)
    seen = []
    out = eng.generate("m0", _ctx(23), SamplingParams(max_tokens=4),
                       stream_callback=lambda ro, t: seen.append(t))
    assert out.finished and out.finish_reason == FINISH_LENGTH
    assert seen == out.tokens and len(seen) == 4


def test_sampling_params_validation():
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError, match="max_tokens"):
        SamplingParams(max_tokens=-1)
    assert SamplingParams(stop_token_ids=[3, 5]).stop_token_ids == (3, 5)


# ======================================================================
# finish reasons + early termination


def test_stop_and_eos_finish_reasons_truncate_the_stream(params):
    """stop_token_ids / eos_token_id end generation mid-flight: the stream
    is cut at (and includes) the terminating token, the finish reason names
    the cause, and the retired sequence's pages return to the pool."""
    ctx = _ctx(5)
    full = _engine(params).generate(
        "m0", ctx, SamplingParams(max_tokens=6)).result()

    stop_tok = int(full[2])
    cut = full.tolist().index(stop_tok) + 1    # first occurrence, inclusive
    assert cut < len(full)

    eng = _engine(params)
    baseline = eng.block_pool.free_count
    stop = eng.generate("m0", ctx, SamplingParams(
        max_tokens=6, stop_token_ids=[stop_tok]))
    np.testing.assert_array_equal(stop.result(), full[:cut])
    assert stop.finish_reason == FINISH_STOP
    eng.block_pool.check_invariants()
    assert eng.block_pool.free_count == baseline   # ephemeral session ended

    eos = _engine(params).generate("m0", ctx, SamplingParams(
        max_tokens=6, eos_token_id=stop_tok))
    np.testing.assert_array_equal(eos.result(), full[:cut])
    assert eos.finish_reason == FINISH_EOS


def test_early_finish_frees_budget_mid_flight(params):
    """An EOS-terminated sequence stops consuming decode steps: the engine
    advances only the surviving sequence afterwards (budget freed), and both
    requests' outputs are unaffected."""
    ctx_a, ctx_b = _ctx(6), _ctx(7)
    ref_b = _engine(params).generate(
        "m1", ctx_b, SamplingParams(max_tokens=8)).result()
    probe = _engine(params).generate(
        "m0", ctx_a, SamplingParams(max_tokens=8)).result()

    eos_tok = int(probe[1])
    cut = probe.tolist().index(eos_tok) + 1    # steps until ra dies
    assert cut < 8

    eng = _engine(params)
    ra = eng.generate("m0", ctx_a, SamplingParams(
        max_tokens=8, eos_token_id=eos_tok))
    rb = eng.generate("m1", ctx_b, SamplingParams(max_tokens=8))
    eng.run()
    assert ra.finish_reason == FINISH_EOS and len(ra.tokens) == cut
    np.testing.assert_array_equal(rb.result(), ref_b)
    # `cut` joint steps + (8 - cut) solo steps: the dead sequence stopped
    # consuming budget/batch slots the step after its EOS
    assert eng.stats.decode_steps == 8
    assert eng.stats.decode_tokens == 2 * cut + (8 - cut)


# ======================================================================
# streaming


def test_streaming_iterator_callback_and_latency_capture(params):
    eng = _engine(params)
    seen = []
    out = eng.generate("m0", _ctx(8), SamplingParams(max_tokens=5),
                       stream_callback=lambda ro, t: seen.append(t))
    assert out.tokens == [] and out.ttft is None
    streamed = list(out)                       # iterator drives the engine
    assert out.finished and out.finish_reason == FINISH_LENGTH
    assert streamed == out.tokens == seen and len(streamed) == 5
    assert out.ttft is not None and out.ttft >= 0
    assert len(out.token_times) == 5
    assert len(out.inter_token_latencies()) == 4
    # late callback replays the already-streamed prefix
    replay = []
    out.add_callback(lambda ro, t: replay.append(t))
    assert replay == streamed
    np.testing.assert_array_equal(out.result(), streamed)


# ======================================================================
# abort: page accounting at every lifecycle stage


def _free_baseline(eng):
    eng.block_pool.check_invariants()
    return eng.block_pool.free_count


def test_abort_queued_request(params):
    eng = _engine(params, chunked=True, chunk_size=5, token_budget=16)
    base = _free_baseline(eng)
    out = eng.generate("m0", _ctx(9), SamplingParams(max_tokens=4))
    assert eng.abort(out) is True
    assert out.finished and out.finish_reason == FINISH_ABORT
    assert not eng.scheduler.has_work()
    assert eng.block_pool.free_count == base
    eng.block_pool.check_invariants()
    assert eng.abort(out) is False             # idempotent
    with pytest.raises(KeyError, match="aborted"):
        eng.result(out.request_id)


def test_abort_mid_chunk_prefill(params):
    """Abort while the prompt is partially prefilled: computed tail pages
    are dropped, the cached-prefix refs return, pool to baseline."""
    eng = _engine(params, chunked=True, chunk_size=5, token_budget=8)
    base = _free_baseline(eng)
    victim = eng.generate("m0", _ctx(10, 40), SamplingParams(max_tokens=4))
    other = eng.generate("m1", _ctx(11), SamplingParams(max_tokens=4))
    eng.step()
    eng.step()                                  # victim mid-prefill
    assert any(r.rid == victim.request_id and 0 < r.done < r.n
               for r in eng.scheduler.prefilling)
    assert victim.abort() is True
    ref = _engine(params).generate(
        "m1", _ctx(11), SamplingParams(max_tokens=4)).result()
    np.testing.assert_array_equal(other.result(), ref)   # survivor unharmed
    eng.end_session(other.session_id)
    assert eng.block_pool.free_count == base
    eng.block_pool.check_invariants()
    assert eng.block_pool.active_count == 0


def test_abort_held_under_pool_exhaustion(params):
    """A request HELD by backpressure (its chunk cannot obtain pages) can be
    aborted; its partial pages free, unblocking nothing less than the pool's
    baseline, while the running request completes."""
    eng = _engine(params, num_pages=9, chunked=True, chunk_size=6,
                  token_budget=8)
    base = _free_baseline(eng)
    ra = eng.generate("m0", _ctx(12, 18), SamplingParams(max_tokens=10))
    rb = eng.generate("m1", _ctx(13, 18), SamplingParams(max_tokens=10))
    stalled = None
    for _ in range(40):
        eng.step()
        if eng.scheduler.stats.stalls and any(
                r.rid == rb.request_id for r in eng.scheduler.prefilling):
            stalled = rb
            break
        if not eng.scheduler.has_work():
            break
    assert stalled is not None, "workload never hit backpressure"
    assert stalled.abort() is True
    eng.run()
    assert ra.finished and ra.finish_reason == FINISH_LENGTH
    eng.end_session(ra.session_id)
    assert eng.block_pool.free_count == base
    eng.block_pool.check_invariants()


def test_abort_while_decoding(params):
    eng = _engine(params)
    base = _free_baseline(eng)
    out = eng.generate("m0", _ctx(14), SamplingParams(max_tokens=12))
    eng.step()
    eng.step()
    assert 0 < len(out.tokens) < 12
    partial = list(out.tokens)
    assert out.abort() is True
    assert out.finish_reason == FINISH_ABORT
    assert out.tokens == partial               # stream frozen at abort point
    np.testing.assert_array_equal(out.result(), partial)   # partial, no hang
    assert not eng.scheduler.has_work()
    assert eng.block_pool.free_count == base
    eng.block_pool.check_invariants()
    assert eng.block_pool.active_count == 0


def test_abort_cached_prefix_request_returns_baseline(params):
    """Abort page accounting with the GLOBAL radix tree holding references:
    a request whose prompt hit the automatic prefix cache refs shared cached
    pages; aborting it mid-chunk or mid-decode returns the free-page count
    exactly to the post-warm baseline, and the cached prefix stays servable."""
    eng = _engine(params, chunked=True, chunk_size=5, token_budget=16)
    prefix = _ctx(40, 4 * PAGE)
    # warm: a PLAIN generate (no SharedContext) publishes the prefix in the
    # engine-global tree; its ephemeral session auto-releases on finish
    eng.generate("m0", prefix, SamplingParams(max_tokens=2)).result()
    base = _free_baseline(eng)
    assert eng.stats()["prefix_nodes"] >= 4

    # (a) abort mid-chunk: cached-prefix refs return to the LRU cache, the
    # partially-computed tail pages are dropped
    victim = eng.generate("m0", prefix + _ctx(41, 12),
                          SamplingParams(max_tokens=4))
    eng.step()
    r = next(r for r in eng.scheduler.prefilling
             if r.rid == victim.request_id)
    assert r.alloc.cached_tokens == 4 * PAGE   # hit with NO shared session
    assert victim.abort() is True
    assert eng.block_pool.free_count == base
    eng.block_pool.check_invariants()

    # (b) abort while decoding: handoff refs on the cached prefix unwind too
    out = eng.generate("m0", prefix + _ctx(42, 5),
                       SamplingParams(max_tokens=12))
    while not out.tokens:
        eng.step()
    assert out.abort() is True
    eng.run()
    assert eng.block_pool.free_count == base
    eng.block_pool.check_invariants()
    assert eng.block_pool.active_count == 0

    # (c) the aborts did not poison the tree: a fresh request still hits
    out2 = eng.generate("m0", prefix + _ctx(43, 7),
                        SamplingParams(max_tokens=3))
    out2.result()
    s = eng.stats()
    assert s["prefix_hit_tokens"] >= 3 * 4 * PAGE    # (a), (b) and (c) hit
    assert eng.block_pool.free_count == base
    eng.block_pool.check_invariants()


def test_abort_cached_prefix_eager_returns_baseline(params):
    """Same baseline guarantee on the eager path: a decoding request whose
    prefill fully reused the published prefix aborts back to baseline."""
    eng = _engine(params)
    prefix = _ctx(44, 3 * PAGE)
    eng.generate("m0", prefix, SamplingParams(max_tokens=2)).result()
    base = _free_baseline(eng)
    out = eng.generate("m1", prefix, SamplingParams(max_tokens=12))
    eng.step()
    assert out.abort() is True
    assert eng.block_pool.free_count == base
    eng.block_pool.check_invariants()
    assert eng.stats()["prefix_hit_tokens"] >= 3 * PAGE


# ======================================================================
# shared contexts


def test_shared_context_end_to_end(params):
    """One prefilled prefix, many models: the prefix is computed ONCE, every
    generate reuses it (the paper's execution pattern), extend grows it
    across turns, close releases the pages."""
    eng = _engine(params, num_pages=128)
    prefix = _ctx(15, 2 * PAGE)
    refs = {}
    for mid in ("m0", "m1"):
        refs[mid] = _legacy_invoke(_engine(params), 0, prefix, mid, 4)

    with eng.shared_context(prefix) as ctx:
        assert eng.stats.prefill_tokens_computed == len(prefix)  # warmed
        outs = {mid: ctx.generate(mid, params=SamplingParams(max_tokens=4))
                for mid in ("m0", "m1")}
        eng.run()
        for mid, out in outs.items():
            np.testing.assert_array_equal(out.result(), refs[mid])
        # prefix computed once; both generates fully reused it
        assert eng.stats.prefill_tokens_computed == len(prefix)
        assert eng.stats.prefill_tokens_reused >= 2 * len(prefix)

        ctx.extend(outs["m0"].tokens)
        out2 = ctx.generate("m1", params=SamplingParams(max_tokens=3))
        ref2 = _legacy_invoke(_engine(params), 0,
                              prefix + outs["m0"].tokens, "m1", 3)
        np.testing.assert_array_equal(out2.result(), ref2)
    eng.block_pool.check_invariants()
    assert eng.block_pool.active_count == 0    # close released the session


def test_shared_context_chunked_with_tails(params):
    """SharedContext on the chunked scheduler, with request-private tails:
    tails never join the shared prefix, prefix pages are shared page-
    granularly."""
    eng = _engine(params, chunked=True, chunk_size=6, token_budget=16,
                  num_pages=128)
    prefix = _ctx(16, 3 * PAGE)
    tails = {"m0": _ctx(17, 5), "m1": _ctx(18, 7)}
    refs = {mid: _legacy_invoke(_engine(params), 0, prefix + t, mid, 3)
            for mid, t in tails.items()}
    with eng.shared_context(prefix) as ctx:
        outs = {mid: ctx.generate(mid, t, SamplingParams(max_tokens=3))
                for mid, t in tails.items()}
        eng.run()
        for mid, out in outs.items():
            np.testing.assert_array_equal(out.result(), refs[mid])
        assert ctx.tokens == prefix            # tails stayed private
    assert eng.stats.prefill_tokens_reused >= 2 * len(prefix)
    eng.block_pool.check_invariants()


def test_ephemeral_session_cleanup(params):
    """generate() without a session runs in an engine-owned one-shot
    session, released automatically on finish — no caller end_session."""
    eng = _engine(params)
    eng.generate("m0", _ctx(19), SamplingParams(max_tokens=3)).result()
    assert all(not w.sessions for w in eng.prefill_workers)
    eng.block_pool.check_invariants()
    assert eng.block_pool.active_count == 0


# ======================================================================
# legacy shim


def test_legacy_surface_warns_and_stays_token_identical(params):
    new = _engine(params).generate(
        "m0", _ctx(20), SamplingParams(max_tokens=5)).result()
    eng = _engine(params)
    with pytest.warns(DeprecationWarning, match="submit.*deprecated"):
        rid = eng.submit(0, _ctx(20), "m0", gen_tokens=5)
    eng.run()
    np.testing.assert_array_equal(eng.result(rid), new)
    eng2 = _engine(params)
    with pytest.warns(DeprecationWarning, match="invoke.*deprecated"):
        old = eng2.invoke(0, _ctx(20), "m0", gen_tokens=5)
    np.testing.assert_array_equal(old, new)


# ======================================================================
# chunk block-table bucketing (ROADMAP open item)


def test_chunk_block_table_bucketing_bounds_retraces():
    """CHUNK block tables are padded to the next power of two, so the jitted
    chunk step retraces O(log pages) times over a long prefill instead of
    once per page of table growth."""
    cfg = ModelConfig(name="api-bucket", arch_type="dense", n_layers=2,
                      d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
                      vocab_size=64, dtype="float32")
    base = init_params(cfg, jax.random.PRNGKey(0))
    decs = {"m0": init_params(cfg, jax.random.PRNGKey(10))}
    eng = LocalDisaggEngine(cfg, base, decs, num_pages=64, page_size=4,
                            chunked=True, chunk_size=8, token_budget=8)
    before = program_traces().get("chunk_step", 0)
    out = eng.generate("m0", _ctx(21, 96), SamplingParams(max_tokens=2))
    out.result()                               # 96 tokens -> 24 pages
    chunks = eng.scheduler.stats.chunks
    traces = program_traces().get("chunk_step", 0) - before
    assert chunks >= 12                        # really was chunked
    # buckets hit: npages in {2,4,8,16,32} (+ the final ragged chunk S) —
    # far fewer traces than chunks; unbucketed tables would retrace ~every
    # chunk that grows the table
    assert traces <= 7, (traces, chunks)
