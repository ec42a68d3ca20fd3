"""Fused cross-model decode plane (serving/decode.py): one jitted step per
engine step for ALL decode models, bit-identical greedy tokens vs
the per-model dispatch loop, donation-aware pool updates, power-of-two
block-table bucketing, and the page-0 padding sentinel."""
import math
from collections import Counter

import jax
import numpy as np
import pytest

from repro.configs.base import ATTN, ModelConfig
from repro.kvcache.blocks import BlockPool
from repro.models import init_params
from repro.runtime import program_traces
from repro.serving.decode import StackedDecoders, next_pow2
from repro.serving.engine import LocalDisaggEngine
from repro.serving.metrics import lint_prometheus

CFG = ModelConfig(name="fused-eng", arch_type="dense", n_layers=2, d_model=32,
                  n_heads=2, n_kv_heads=1, d_ff=64, vocab_size=64,
                  dtype="float32")
# 3 layers over a 2-layer pattern: 1 scanned group + 1 unrolled tail layer,
# so the fused step's row merge covers BOTH pool layouts.
CFG_TAIL = ModelConfig(name="fused-tail", arch_type="dense", n_layers=3,
                       d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
                       vocab_size=64, dtype="float32",
                       layer_pattern=(ATTN, ATTN))
PAGE = 8


def _params(cfg, n_models):
    base = init_params(cfg, jax.random.PRNGKey(0))
    decs = {f"m{i}": init_params(cfg, jax.random.PRNGKey(10 + i))
            for i in range(n_models)}
    return base, decs


def _engine(cfg, base, decs, **kw):
    kw.setdefault("num_pages", 64)
    kw.setdefault("page_size", PAGE)
    return LocalDisaggEngine(cfg, base, decs, **kw)


def _mixed_workload(rng, n_models):
    """Ragged contexts, staggered gen lengths: model 0's sequences finish
    first, so later steps run with a model at ZERO active sequences."""
    jobs = []
    for i in range(2 * n_models):
        mid = f"m{i % n_models}"
        ctx = list(rng.integers(4, 60, size=11 + 5 * i))
        gen = 3 if mid == "m0" else 6 + (i % 2)
        jobs.append((i, ctx, mid, gen))
    return jobs


@pytest.mark.parametrize("cfg", [CFG, CFG_TAIL], ids=["grouped", "with-tail"])
def test_fused_matches_per_model_loop_bitwise(cfg):
    """Greedy tokens from the fused multi-model step == the per-model
    dispatch loop, across mixed-model ragged batches, including steps where
    one model has no active sequences left."""
    base, decs = _params(cfg, 3)
    fused = _engine(cfg, base, decs)                 # fused default on paged
    legacy = _engine(cfg, base, decs, fused=False)
    assert fused.decode_plane is not None and legacy.decode_plane is None

    jobs = _mixed_workload(np.random.default_rng(0), 3)
    f_rids = [fused.submit(sid, ctx, mid, gen) for sid, ctx, mid, gen in jobs]
    l_rids = [legacy.submit(sid, ctx, mid, gen) for sid, ctx, mid, gen in jobs]
    fused.run()
    legacy.run()
    for fr, lr in zip(f_rids, l_rids):
        np.testing.assert_array_equal(fused.result(fr), legacy.result(lr))
    # sanity: the workload really did mix models within single steps
    assert fused.stats.decode_tokens == legacy.stats.decode_tokens
    assert fused.stats.decode_batch_mean > 1.0


def test_one_dispatch_per_step_across_models():
    """The acceptance bar: every engine decode step issues exactly ONE jitted
    forward for all active sequences across all decode models (legacy pays
    one per model per step)."""
    base, decs = _params(CFG, 3)
    rng = np.random.default_rng(1)
    ctxs = [list(rng.integers(4, 60, size=12 + i)) for i in range(3)]

    fused = _engine(CFG, base, decs)
    for sid, ctx in enumerate(ctxs):
        fused.submit(sid, ctx, f"m{sid}", gen_tokens=5)
    fused.run()
    assert fused.stats.decode_dispatches == fused.stats.decode_steps
    assert fused.decode_plane.dispatches == fused.stats.decode_steps

    legacy = _engine(CFG, base, decs, fused=False)
    for sid, ctx in enumerate(ctxs):
        legacy.submit(sid, ctx, f"m{sid}", gen_tokens=5)
    legacy.run()
    # all three models active on every engine step -> 3x the dispatches the
    # fused plane issued for the same schedule
    assert legacy.stats.decode_dispatches == 3 * fused.stats.decode_steps


def test_npages_bucketing_stops_per_page_retraces():
    """Block-table width is bucketed to the next power of two: growing by one
    page WITHIN a bucket reuses the jit trace; only crossing a bucket
    boundary (4 -> 5 pages => bucket 4 -> 8) retraces."""
    assert [next_pow2(n) for n in (1, 2, 3, 4, 5, 8, 9)] == [1, 2, 4, 4, 8, 8, 16]
    base, decs = _params(CFG, 1)
    eng = _engine(CFG, base, decs)
    before = program_traces().get("decode_step", 0)
    # 23-token prompt -> 3 pages (bucket 4); 9 generated tokens end at
    # pos 32 -> 4 pages, still bucket 4: table growth must not retrace.
    eng.invoke(0, list(range(4, 4 + 23)), "m0", gen_tokens=9)
    assert program_traces()["decode_step"] - before == 1
    # push past 32 tokens -> 5 pages -> bucket 8: exactly one more trace
    eng.submit(0, list(range(4, 4 + 23)) + [5] * 6, "m0", gen_tokens=6)
    eng.run()
    assert program_traces()["decode_step"] - before == 2


def test_pool_donation_pair_is_functional_off_tpu():
    """Off-TPU the fused step's donation is a no-op: the pre-step page
    buffers stay valid and unchanged (pure functional update), while the pool
    absorbs the step's returned buffers."""
    base, decs = _params(CFG, 2)
    eng = _engine(CFG, base, decs)
    r0 = eng.submit(0, list(range(4, 24)), "m0", gen_tokens=1)
    r1 = eng.submit(1, list(range(24, 44)), "m1", gen_tokens=1)
    pre = jax.tree.map(lambda x: np.asarray(x).copy(),
                       eng.kvpool.decode_state())
    pre_refs = eng.kvpool.decode_state()            # live pre-step buffers
    eng.run()
    post = eng.kvpool.decode_state()
    changed = False
    for g in pre["groups"]:
        # the old buffers were not mutated in place...
        np.testing.assert_array_equal(
            np.asarray(pre_refs["groups"][g]["k"]), pre["groups"][g]["k"])
        # ...and the pool now holds freshly-appended rows
        changed |= not np.array_equal(np.asarray(post["groups"][g]["k"]),
                                      pre["groups"][g]["k"])
    assert changed, "decode step appended no KV to the pool"
    assert eng.result(r0).shape == (1,) and eng.result(r1).shape == (1,)


def test_sentinel_page_zero_never_holds_live_kv():
    """Regression for the ragged block-table padding alias: page id 0 is a
    never-allocated sentinel, so zero-padded table slots (shorter sequences
    in a wider batch, fused fake rows) cannot alias live KV. Before the fix,
    the FIRST page the pool handed out was id 0 — exactly the page every
    padded slot pointed at."""
    pool = BlockPool(4, PAGE)
    got = pool.alloc(4)                              # drain the whole pool
    assert 0 not in got and min(got) == 1
    with pytest.raises(ValueError, match="sentinel"):
        pool.ref([0])
    with pytest.raises(ValueError, match="sentinel"):
        pool.drop([0])
    pool.check_invariants()

    base, decs = _params(CFG, 2)
    eng = _engine(CFG, base, decs)
    rng = np.random.default_rng(3)
    # long + short sequences decode in one batch: the short row's table is
    # zero-padded to the long row's (bucketed) width every step
    jobs = [(0, list(rng.integers(4, 60, size=37)), "m0", 5),
            (1, list(rng.integers(4, 60, size=9)), "m1", 5)]
    rids = [eng.submit(*j) for j in jobs]
    eng.run()
    used = set()
    for w in eng.prefill_workers:
        for sc in w.sessions.values():
            used.update(sc.block_table)
    assert 0 not in used
    # physical sentinel row 0 never received a write, on any layer
    for g, a in eng.kvpool.k_groups.items():
        assert not np.asarray(a)[:, 0].any(), f"group {g} wrote sentinel row"
    for i, a in enumerate(eng.kvpool.k_tail):
        assert not np.asarray(a)[0].any(), f"tail layer {i} wrote sentinel row"
    # and the mixed-width batch still decodes exactly like isolated runs
    ref = _engine(CFG, base, decs)
    for rid, job in zip(rids, jobs):
        np.testing.assert_array_equal(eng.result(rid),
                                      ref.invoke(*job[:3], gen_tokens=job[3]))


def test_result_fetch_states():
    """result() keeps the entry (repeat reads OK); pop_result() releases it;
    errors name the rid and its fetch state instead of a bare KeyError."""
    base, decs = _params(CFG, 1)
    eng = _engine(CFG, base, decs)
    rid = eng.submit(0, list(range(4, 20)), "m0", gen_tokens=3)
    with pytest.raises(KeyError, match=f"request {rid}: submitted but not"):
        eng.result(rid)
    eng.run()
    first = eng.result(rid)
    np.testing.assert_array_equal(first, eng.result(rid))   # non-consuming
    np.testing.assert_array_equal(first, eng.pop_result(rid))
    with pytest.raises(KeyError, match="already fetched"):
        eng.result(rid)
    with pytest.raises(KeyError, match="already fetched"):
        eng.pop_result(rid)
    with pytest.raises(KeyError, match="unknown request id"):
        eng.result(999)


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "per-model"])
def test_decode_page_counters_match_block_tables(fused, monkeypatch):
    """decode_table_pages counts what each decode dispatch hands the
    attention kernel (lanes x rows x bucketed table width), and
    decode_live_pages the pages its real rows attend to: both equal a hand
    count from the block tables, and both are exported."""
    base, decs = _params(CFG, 2)
    eng = _engine(CFG, base, decs, fused=fused)
    calls = []          # per dispatch: (lanes, [(model, pos, table pages)])

    def record(lanes, seqs):
        calls.append((lanes, [(s.model_id, s.pos, len(s.block_table))
                              for s in seqs]))

    if fused:
        step = StackedDecoders.step

        def spy(self, seqs):
            record(len(self.model_ids), seqs)
            return step(self, seqs)
        monkeypatch.setattr(StackedDecoders, "step", spy)
    else:
        batched = eng._batched_step

        def spy(mid, seqs):
            record(1, seqs)
            return batched(mid, seqs)
        monkeypatch.setattr(eng, "_batched_step", spy)

    rng = np.random.default_rng(3)
    for sid, n in enumerate((13, 37)):       # unequal prompts, one a module
        eng.submit(sid, list(rng.integers(4, 60, size=n)), f"m{sid}",
                   gen_tokens=6)
    eng.run()

    table = live = 0
    for lanes, rows in calls:
        rows_per_lane = max(Counter(m for m, _, _ in rows).values())
        width = 1
        while width < max(n for _, _, n in rows):
            width *= 2
        table += lanes * rows_per_lane * width
        live += sum(math.ceil((pos + 1) / PAGE) for _, pos, _ in rows)
    stats = eng.stats()
    assert calls and 0 < live < table
    assert stats["decode_table_pages"] == table
    assert stats["decode_live_pages"] == live
    text = eng.render_prometheus()
    assert lint_prometheus(text) == []
    assert f"engine_decode_table_pages_total {table}" in text
    assert f"engine_decode_live_pages_total {live}" in text
