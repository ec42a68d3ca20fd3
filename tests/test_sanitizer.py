"""PoolSanitizer: sanitize=True is token-bit-identical to sanitize=False,
and every seeded corruption trips with a precise diagnostic."""
import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.kvcache.blocks import BlockPool
from repro.kvcache.radix import Node, PrefixIndex
from repro.kvcache.sanitize import (SanitizedKVPool, SanitizerError,
                                    check_index, check_pool)
from repro.models import init_params
from repro.serving.api import SamplingParams
from repro.serving.engine import LocalDisaggEngine

CFG = ModelConfig(name="sanitize-eng", arch_type="dense", n_layers=2,
                  d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
                  vocab_size=64, dtype="float32")
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


def _start_decode(eng, tokens=None, max_tokens=6):
    """Admit one request and step until it reaches the decode plane."""
    h = eng.generate("m0", tokens or list(range(1, 12)),
                     SamplingParams(max_tokens=max_tokens))
    for _ in range(32):
        eng.scheduler.step()
        if eng.scheduler.active:
            return h
    raise AssertionError("request never reached decode")


# ======================================================================
# bit-identity
# ======================================================================

def test_sanitize_run_is_token_bit_identical():
    base, decs = _params()
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(4, 60, size=n)) for n in (9, 21, 9, 5)]

    def run(sanitize):
        eng = _engine(base, decs, chunked=True, chunk_size=PAGE,
                      token_budget=32, sanitize=sanitize)
        hs = [eng.generate(f"m{i % 2}", p, SamplingParams(max_tokens=5))
              for i, p in enumerate(prompts)]
        eng.scheduler.run()
        return [h.result().tolist() for h in hs], eng

    ref, _ = run(False)
    got, eng = run(True)
    assert got == ref
    assert eng.sanitizer is not None and eng.sanitizer.checks > 0
    assert isinstance(eng.kvpool, SanitizedKVPool)


def test_sanitize_requires_paged_plane():
    base, decs = _params()
    with pytest.raises(ValueError, match="paged"):
        LocalDisaggEngine(CFG, base, decs, paged=False, sanitize=True)


# ======================================================================
# seeded corruptions -> precise diagnostics
# ======================================================================

def test_refcount_corruption_trips():
    base, decs = _params()
    eng = _engine(base, decs, chunked=True, sanitize=True)
    _start_decode(eng)
    s = eng.scheduler.active[0]
    bid = s.shared_blocks[0]
    eng.block_pool._refcount[bid] += 1           # phantom reference
    with pytest.raises(SanitizerError, match=f"refcount mismatch on page "
                                             f"{bid}"):
        eng.scheduler.step()


def test_leaked_reference_trips():
    base, decs = _params()
    eng = _engine(base, decs, chunked=True, sanitize=True)
    _start_decode(eng)
    leaked = eng.block_pool.alloc(1)[0]          # held by NO engine structure
    with pytest.raises(SanitizerError,
                       match=f"page {leaked} is ACTIVE .* NO engine "
                             f"structure"):
        eng.scheduler.step()


def test_sentinel_in_live_table_trips():
    base, decs = _params()
    eng = _engine(base, decs, chunked=True, sanitize=True)
    _start_decode(eng)
    eng.scheduler.active[0].block_table[0] = BlockPool.SENTINEL
    with pytest.raises(SanitizerError, match="sentinel page 0 appears in "
                                             "the live block table"):
        eng.scheduler.step()


def test_stale_index_entry_trips():
    base, decs = _params()
    eng = _engine(base, decs, chunked=True, sanitize=True)
    _start_decode(eng)
    idx = eng.prefix_index
    free_bid = eng.block_pool._free[-1]
    node = Node(key=(99,) * PAGE, block_id=free_bid, parent=idx.root)
    idx.root.children[node.key] = node
    idx._by_block[free_bid] = node               # index points at a FREE page
    with pytest.raises(SanitizerError,
                       match=f"block {free_bid} but the pool has it FREE"):
        eng.scheduler.step()


def test_leaked_relay_page_trips_with_relay_naming():
    """Relay-published pages are first-class in the step census: a published
    page seeded ACTIVE with NO engine holder must trip with a diagnostic
    NAMING relay publication as the holder class (not the generic leak
    message) — the PR 8 ROADMAP instruction for new page owners."""
    base, _ = _params()
    eng = LocalDisaggEngine(CFG, base, num_pages=64, page_size=PAGE,
                            chunked=True, sanitize=True)
    eng.models.register("m_base", base)       # KV path == base: may publish
    prompt = list(range(1, 1 + 2 * PAGE))
    eng.generate("m_base", prompt,
                 SamplingParams(max_tokens=PAGE + 2)).result()
    assert eng.stats()["relay_pages_published"] > 0
    bid = next(bid for bid, nd in eng.prefix_index._by_block.items()
               if nd.provenance == "relay")
    eng.scheduler.step()                      # clean census first
    eng.block_pool.ref([bid])                 # seeded leak: ACTIVE, no holder
    with pytest.raises(SanitizerError, match=f"page {bid} is ACTIVE .* "
                                             f"holder: relay publication"):
        eng.scheduler.step()


def test_relay_refcount_mismatch_tags_relay_page():
    """A refcount corruption on a page that happens to be relay-published is
    tagged as such in the mismatch diagnostic."""
    base, _ = _params()
    eng = LocalDisaggEngine(CFG, base, num_pages=64, page_size=PAGE,
                            chunked=True, sanitize=True)
    eng.models.register("m_base", base)
    prompt = list(range(1, 1 + 2 * PAGE))
    out = eng.generate("m_base", prompt,
                       SamplingParams(max_tokens=PAGE + 2)).result()
    relay_bid = next(bid for bid, nd in eng.prefix_index._by_block.items()
                     if nd.provenance == "relay")
    # a follower whose prompt EXTENDS the published stream holds the relay
    # page as cached prefix while decoding; corrupt its refcount mid-flight
    eng.generate("m_base", prompt + [2] + [int(t) for t in out],
                 SamplingParams(max_tokens=4))
    for _ in range(32):
        eng.scheduler.step()
        if eng.scheduler.active:
            break
    assert any(relay_bid in s.shared_blocks for s in eng.scheduler.active)
    eng.block_pool._refcount[relay_bid] += 1
    with pytest.raises(SanitizerError, match="relay-published page"):
        eng.scheduler.step()


# ======================================================================
# donation poisoning
# ======================================================================

def test_use_after_donation_trips():
    base, decs = _params()
    eng = _engine(base, decs, sanitize=True)
    stale = eng.kvpool.decode_state()
    g = next(iter(stale["groups"]))
    eng.kvpool.absorb_decode_state(eng.kvpool.decode_state())
    with pytest.raises(SanitizerError, match="use-after-donation"):
        _ = stale["groups"][g]["k"].shape
    with pytest.raises(SanitizerError, match="use-after-donation"):
        np.asarray(stale["groups"][g]["v"])


def test_stale_decode_cache_trips():
    """A pool handle taken before a chunked-prefill step (which donates the
    pool on TPU) is dead after it."""
    base, decs = _params()
    eng = _engine(base, decs, sanitize=True, chunked=True, chunk_size=PAGE)
    stale = eng.kvpool.decode_state()
    g = next(iter(stale["groups"]))
    eng.generate("m0", list(range(1, 12)), SamplingParams(max_tokens=1))
    eng.scheduler.step()                      # one prefill chunk
    assert eng.scheduler.stats.chunks >= 1
    with pytest.raises(SanitizerError, match="use-after-donation"):
        _ = stale["groups"][g]["k"][0]


def test_absorbed_tree_itself_is_never_poisoned():
    """Round-tripping the handed-out dict through absorb (legal off-TPU
    no-op) must keep the pool's buffers real arrays."""
    base, decs = _params()
    eng = _engine(base, decs, sanitize=True)
    state = eng.kvpool.decode_state()
    eng.kvpool.absorb_decode_state(state)
    for g, arr in eng.kvpool.k_groups.items():
        assert hasattr(arr, "shape")             # a real array, not a trap


def test_copy_page_retires_outstanding_state():
    base, decs = _params()
    eng = _engine(base, decs, sanitize=True)
    stale = eng.kvpool.decode_state()
    g = next(iter(stale["groups"]))
    (bid,) = eng.block_pool.alloc(1)
    (dst,) = eng.block_pool.alloc(1)
    eng.kvpool.copy_page(bid, dst)               # donated pool update on TPU
    with pytest.raises(SanitizerError, match="copy_page"):
        _ = stale["groups"][g]["k"].shape


# ======================================================================
# standalone checkers (no engine)
# ======================================================================

def test_check_pool_diagnoses_direct_corruption():
    p = BlockPool(8, 4)
    check_pool(p)                                # fresh pool is clean
    blocks = p.alloc(2)
    check_pool(p)
    p._refcount[blocks[0]] = -1
    with pytest.raises(SanitizerError, match="negative"):
        check_pool(p)
    p._refcount[blocks[0]] = 1
    p._free.append(blocks[1])                    # active AND free
    with pytest.raises(SanitizerError, match="also in the free"):
        check_pool(p)
    p._free.pop()
    p._refcount[BlockPool.SENTINEL] = 1
    with pytest.raises(SanitizerError, match="sentinel page 0"):
        check_pool(p)


def test_check_index_structural_and_residency():
    pool = BlockPool(8, 2)
    idx = PrefixIndex(2)
    pool.add_evict_callback(idx.remove_block)
    blocks = pool.alloc(2)
    idx.insert([1, 2, 3, 4], blocks)
    check_index(idx, pool)
    pool.unref(blocks)                           # CACHED: still resident
    check_index(idx, pool)
    pool.drop(list(blocks))                      # FREE without eviction cb
    with pytest.raises(SanitizerError, match="FREE"):
        check_index(idx, pool)
