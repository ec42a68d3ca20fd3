"""Model-lifecycle registry (serving/registry.py): hot (un)register decode
models while the engine serves — duplicate/unknown-id errors, drain vs abort
retirement, page refcounts back to baseline after churn, bit-identical
surviving streams across fused-plane lane remaps, and LoRA-spec'd models
(one base copy + stacked adapters, merged inside the jitted decode step)
asserted bit-identical to pre-merged ``lora_apply`` decoders."""
import warnings

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.lora import LoRAPair, lora_apply, lora_init
from repro.models import init_params
from repro.serving.api import SamplingParams, UnknownModelError
from repro.serving.engine import LocalDisaggEngine
from repro.serving.registry import (DecodeModelSpec, LoRAAdapter,
                                    ModelRegistry, as_spec)

CFG = ModelConfig(name="reg-eng", arch_type="dense", n_layers=2, d_model=32,
                  n_heads=2, n_kv_heads=1, d_ff=64, vocab_size=64,
                  dtype="float32")
PAGE = 8


@pytest.fixture(scope="module")
def params():
    base = init_params(CFG, jax.random.PRNGKey(0))
    decs = {f"m{i}": init_params(CFG, jax.random.PRNGKey(10 + i))
            for i in range(3)}
    return base, decs


def _engine(params, models=("m0", "m1", "m2"), **kw):
    base, decs = params
    kw.setdefault("num_pages", 64)
    kw.setdefault("page_size", PAGE)
    eng = LocalDisaggEngine(CFG, base, **kw)
    for mid in models:
        eng.models.register(mid, DecodeModelSpec(full=decs[mid]))
    return eng


def _ctx(seed, n=19):
    return list(np.random.default_rng(seed).integers(4, 60, size=n))


def _adapter(key, base, rank=4, alpha=8.0) -> LoRAAdapter:
    """lora_init with nonzero B so the merge is a real perturbation."""
    tree = lora_init(key, base, rank=rank)
    flat, td = jax.tree_util.tree_flatten(
        tree, is_leaf=lambda x: x is None or isinstance(x, LoRAPair))
    out = [None if p is None else
           LoRAPair(p.A, 0.05 * jax.random.normal(
               jax.random.fold_in(key, 77 + i), p.B.shape, p.B.dtype))
           for i, p in enumerate(flat)]
    return LoRAAdapter(jax.tree_util.tree_unflatten(td, out),
                       alpha=alpha, rank=rank)


# ======================================================================
# registration errors / spec validation


def test_register_duplicate_raises(params):
    eng = _engine(params, models=("m0",))
    with pytest.raises(ValueError, match="already registered"):
        eng.models.register("m0", DecodeModelSpec(full=params[1]["m1"]))
    assert eng.models.list() == ["m0"]          # registry unchanged


def test_unregister_unknown_raises(params):
    eng = _engine(params, models=("m0",))
    with pytest.raises(UnknownModelError, match="'ghost' is not registered"):
        eng.models.unregister("ghost")
    with pytest.raises(UnknownModelError, match="not registered"):
        eng.models.get("ghost")


def test_generate_unknown_model_is_first_class(params):
    """Unknown-model submissions fail with UnknownModelError BEFORE any rid
    or pages exist — on generate, on SharedContext.generate, and on the
    legacy submit shim."""
    eng = _engine(params, models=("m0",))
    free0 = eng.block_pool.free_count
    with pytest.raises(UnknownModelError, match="'nope' is not registered"):
        eng.generate("nope", _ctx(0))
    with pytest.raises(UnknownModelError):
        with eng.shared_context(_ctx(1)) as ctx:
            ctx.generate("nope")
    with pytest.raises(UnknownModelError):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            eng.submit(9, _ctx(0), "nope", 4)
    eng.run()
    assert eng.block_pool.free_count == free0
    # the failed submissions issued no rids: the next request works normally
    out = eng.generate("m0", _ctx(0), SamplingParams(max_tokens=3))
    assert out.result().shape == (3,)


def test_spec_validation():
    with pytest.raises(ValueError, match="exactly one"):
        DecodeModelSpec()
    with pytest.raises(ValueError, match="exactly one"):
        DecodeModelSpec(full={"w": 1}, lora=LoRAAdapter(params=None))
    with pytest.raises(TypeError, match="LoRAAdapter"):
        DecodeModelSpec(lora={"A": 1, "B": 2})
    assert as_spec({"w": 1}).kind == "full"
    assert as_spec(LoRAAdapter(params=None)).kind == "lora"


def test_constructor_dict_is_deprecated_shim(params):
    """The construction-time decoders dict still works — it registers each
    entry (token-identical to explicit registration) and warns."""
    base, decs = params
    with pytest.warns(DeprecationWarning, match="decoders"):
        old = LocalDisaggEngine(CFG, base, dict(decs), num_pages=64,
                                page_size=PAGE)
    assert old.models.list() == sorted(decs)
    assert old.stats.model_churn_events == 0     # construction is not churn
    new = _engine(params)
    ctx = _ctx(5)
    np.testing.assert_array_equal(
        old.generate("m1", ctx, SamplingParams(max_tokens=5)).result(),
        new.generate("m1", ctx, SamplingParams(max_tokens=5)).result())


# ======================================================================
# churn while serving


def test_hot_register_mid_run_preserves_surviving_outputs(params):
    """Registering a model while requests are decoding relayouts the fused
    plane at a step boundary; surviving requests' greedy outputs are
    bit-identical to a churn-free run, and the new model serves."""
    base, decs = params
    ref = _engine(params, models=("m0", "m1"))
    jobs = [( _ctx(10), "m0", 8), (_ctx(11, 13), "m1", 8)]
    refs = [ref.generate(m, c, SamplingParams(max_tokens=g))
            for c, m, g in jobs]
    ref.run()

    eng = _engine(params, models=("m0", "m1"))
    outs = [eng.generate(m, c, SamplingParams(max_tokens=g))
            for c, m, g in jobs]
    for _ in range(3):
        eng.step()                                # mid-generation...
    assert all(len(o.tokens) == 3 for o in outs)
    eng.models.register("m2", DecodeModelSpec(full=decs["m2"]))  # ...churn
    late = eng.generate("m2", _ctx(12, 17), SamplingParams(max_tokens=4))
    eng.run()
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o.tokens, r.tokens)
    m2_ref = _engine(params).generate("m2", _ctx(12, 17),
                                      SamplingParams(max_tokens=4))
    np.testing.assert_array_equal(late.result(), m2_ref.result())
    assert eng.stats.plane_rebuilds >= 1


def test_unregister_drain_finishes_inflight_then_retires(params):
    eng = _engine(params, models=("m0", "m1"))
    ref = _engine(params, models=("m0", "m1"))
    out = eng.generate("m0", _ctx(20), SamplingParams(max_tokens=8))
    eng.step()
    done_now = eng.models.unregister("m0", drain=True)
    assert done_now is False and "m0" in eng.models
    assert "m0" in eng.models.draining
    with pytest.raises(UnknownModelError, match="draining"):
        eng.generate("m0", _ctx(21))             # no NEW work while draining
    with pytest.raises(ValueError, match="already draining"):
        eng.models.unregister("m0")
    eng.run()                                    # in-flight request finishes
    assert out.finished and out.finish_reason == "length"
    np.testing.assert_array_equal(
        out.tokens,
        ref.generate("m0", _ctx(20), SamplingParams(max_tokens=8)).result())
    assert "m0" not in eng.models and eng.models.list() == ["m1"]
    with pytest.raises(UnknownModelError):
        eng.generate("m0", _ctx(21))


def test_unregister_abort_releases_pages_to_baseline(params):
    """drain=False aborts the model's in-flight work through the engine's
    abort path: aborted handles finish with reason 'abort', survivors are
    bit-identical, and the pool's free-page count returns to baseline."""
    eng = _engine(params, models=("m0", "m1"))
    free0 = eng.block_pool.free_count
    victim = eng.generate("m0", _ctx(30), SamplingParams(max_tokens=10))
    keeper = eng.generate("m1", _ctx(31, 13), SamplingParams(max_tokens=6))
    for _ in range(2):
        eng.step()
    assert eng.models.unregister("m0", drain=False) is True
    assert victim.finished and victim.finish_reason == "abort"
    assert len(victim.tokens) == 2               # streamed prefix survives
    assert "m0" not in eng.models
    eng.run()
    np.testing.assert_array_equal(
        keeper.tokens,
        _engine(params).generate("m1", _ctx(31, 13),
                                 SamplingParams(max_tokens=6)).result())
    assert eng.block_pool.free_count == free0
    eng.block_pool.check_invariants()


def test_churn_storm_page_accounting_and_plane_counters(params):
    """Interleaved register/unregister under traffic: free pages return to
    baseline once everything finishes, and the rebuilt plane's trace/
    dispatch counters stay cumulative (monotonic across relayouts)."""
    base, decs = params
    eng = _engine(params, models=("m0",))
    free0 = eng.block_pool.free_count
    a = eng.generate("m0", _ctx(40), SamplingParams(max_tokens=6))
    eng.step()
    d0 = eng.decode_plane.dispatches
    eng.models.register("m1", DecodeModelSpec(full=decs["m1"]))
    b = eng.generate("m1", _ctx(41, 11), SamplingParams(max_tokens=5))
    eng.step()
    eng.models.register("m2", DecodeModelSpec(full=decs["m2"]))
    eng.models.unregister("m1", drain=False)     # aborts b
    eng.models.unregister("m2")                  # never had traffic: gone now
    assert b.finish_reason == "abort"
    eng.run()
    assert a.finished and a.finish_reason == "length"
    assert eng.models.list() == ["m0"]
    assert eng.block_pool.free_count == free0
    assert eng.decode_plane.dispatches >= d0 + 1     # counters carried over
    assert eng.stats.plane_rebuilds >= 2
    eng.block_pool.check_invariants()


def test_seeded_stream_unchanged_across_lane_remap(params):
    """A seeded SAMPLED stream (keys fold from (seed, position)) is
    reproducible across a mid-stream churn event that remaps its fused-plane
    lane index."""
    sp = SamplingParams(max_tokens=8, temperature=0.9, top_k=12, seed=13)
    solo = _engine(params, models=("m1",)).generate(
        "m1", _ctx(50), sp).result()

    base, decs = params
    eng = _engine(params, models=("m0", "m1"))
    got = eng.generate("m1", _ctx(50), sp)
    eng.generate("m0", _ctx(51, 12), SamplingParams(max_tokens=3))
    for _ in range(2):
        eng.step()
    # churn both ways: m1's lane index changes (m0 retires below it, m2
    # arrives), while its pages / positions / sampling keys do not
    eng.models.register("m2", DecodeModelSpec(full=decs["m2"]))
    eng.run()
    eng.models.unregister("m0")
    got2 = eng.generate("m1", _ctx(50), sp)      # fresh run, remapped lane
    eng.run()
    np.testing.assert_array_equal(solo, got.tokens)
    np.testing.assert_array_equal(solo, got2.result())


def test_chunked_mode_churn_drain_and_abort(params):
    """Churn under the chunked scheduler: drain lets a still-PREFILLING
    request finish bit-identically; drain=False aborts it mid-chunk with
    pages back to baseline."""
    kw = dict(chunked=True, chunk_size=5, token_budget=16)
    ref = _engine(params, models=("m0",), **kw)
    r = ref.generate("m0", _ctx(60, 33), SamplingParams(max_tokens=5)).result()

    eng = _engine(params, models=("m0", "m1"), **kw)
    out = eng.generate("m0", _ctx(60, 33), SamplingParams(max_tokens=5))
    eng.step()                                   # first chunk only
    assert eng.models.unregister("m0", drain=True) is False
    eng.run()
    np.testing.assert_array_equal(out.tokens, r)
    assert "m0" not in eng.models

    eng2 = _engine(params, models=("m0", "m1"), **kw)
    free0 = eng2.block_pool.free_count
    out2 = eng2.generate("m0", _ctx(61, 33), SamplingParams(max_tokens=5))
    eng2.step()                                  # mid-prefill
    eng2.models.unregister("m0", drain=False)
    assert out2.finished and out2.finish_reason == "abort"
    eng2.run()
    assert eng2.block_pool.free_count == free0
    eng2.block_pool.check_invariants()


# ======================================================================
# LoRA specs: adapter-factored fused plane


def test_lora_spec_bit_identical_to_materialized(params):
    """LoRA-registered models (stacked A/B factors, merged inside the jitted
    vmapped step) decode bit-identically — greedy AND seeded sampling — to
    the same adapters pre-merged into full ``lora_apply`` decoders, while
    the fused plane stores one base copy + N adapter sets."""
    base, _ = params
    ads = {f"a{i}": _adapter(jax.random.PRNGKey(100 + i), base)
           for i in range(2)}
    lora_eng = LocalDisaggEngine(CFG, base, num_pages=64, page_size=PAGE)
    full_eng = LocalDisaggEngine(CFG, base, num_pages=64, page_size=PAGE)
    for mid, ad in ads.items():
        lora_eng.models.register(mid, DecodeModelSpec(lora=ad))
        full_eng.models.register(mid, DecodeModelSpec(full=lora_apply(
            base, ad.params, alpha=ad.alpha, rank=ad.rank)))
    jobs = [(_ctx(70), "a0", SamplingParams(max_tokens=6)),
            (_ctx(71, 13), "a1", SamplingParams(max_tokens=6)),
            (_ctx(72, 11), "a0",
             SamplingParams(max_tokens=6, temperature=0.8, top_k=10, seed=3))]
    louts = [lora_eng.generate(m, c, sp) for c, m, sp in jobs]
    fouts = [full_eng.generate(m, c, sp) for c, m, sp in jobs]
    lora_eng.run()
    full_eng.run()
    for lo, fo in zip(louts, fouts):
        np.testing.assert_array_equal(lo.tokens, fo.tokens)
    # weight-side Eq. 9: the lora plane stores exactly the stacked adapter
    # factors beyond the shared base; the full plane stores N full models
    ad_bytes = sum(x.nbytes for x in jax.tree.leaves(ads["a0"].params))
    full_bytes = sum(x.nbytes for x in jax.tree.leaves(
        full_eng.models.get("a0").full))
    assert lora_eng.decode_plane.param_bytes() == 2 * ad_bytes
    assert full_eng.decode_plane.param_bytes() == 2 * full_bytes
    assert lora_eng.decode_plane.param_bytes() \
        < full_eng.decode_plane.param_bytes() / 4


def test_mixed_full_and_lora_groups(params):
    """Full specs and LoRA specs coexist: they stack into separate fusable
    groups (one dispatch each per step) and both decode correctly alongside
    each other, including across a churn of either kind."""
    base, decs = params
    ad = _adapter(jax.random.PRNGKey(200), base)
    eng = LocalDisaggEngine(CFG, base, num_pages=64, page_size=PAGE)
    eng.models.register("full0", DecodeModelSpec(full=decs["m0"]))
    eng.models.register("lora0", DecodeModelSpec(lora=ad))
    o1 = eng.generate("full0", _ctx(80), SamplingParams(max_tokens=5))
    o2 = eng.generate("lora0", _ctx(80), SamplingParams(max_tokens=5))
    eng.run()
    assert len(eng.decode_plane.groups) == 2

    ref_full = LocalDisaggEngine(CFG, base, num_pages=64, page_size=PAGE)
    ref_full.models.register("full0", decs["m0"])
    np.testing.assert_array_equal(
        o1.tokens, ref_full.generate("full0", _ctx(80),
                                     SamplingParams(max_tokens=5)).result())
    ref_lora = LocalDisaggEngine(CFG, base, num_pages=64, page_size=PAGE)
    ref_lora.models.register("lora0", DecodeModelSpec(full=lora_apply(
        base, ad.params, alpha=ad.alpha, rank=ad.rank)))
    np.testing.assert_array_equal(
        o2.tokens, ref_lora.generate("lora0", _ctx(80),
                                     SamplingParams(max_tokens=5)).result())


def test_lora_spec_per_model_loop_and_lazy_materialization(params):
    """fused=False exercises the DecodeWorker path: the LoRA spec
    materializes ``lora_apply`` params lazily there, and outputs match the
    fused in-step merge bit-for-bit. In fused mode the worker copy is never
    materialized — the plane reads the factors directly."""
    base, _ = params
    ad = _adapter(jax.random.PRNGKey(300), base)
    fused_eng = LocalDisaggEngine(CFG, base, num_pages=64, page_size=PAGE)
    loop_eng = LocalDisaggEngine(CFG, base, num_pages=64, page_size=PAGE,
                                 fused=False)
    for eng in (fused_eng, loop_eng):
        eng.models.register("lm", DecodeModelSpec(lora=ad))
    a = fused_eng.generate("lm", _ctx(90), SamplingParams(max_tokens=6))
    b = loop_eng.generate("lm", _ctx(90), SamplingParams(max_tokens=6))
    fused_eng.run()
    loop_eng.run()
    np.testing.assert_array_equal(a.tokens, b.tokens)
    assert fused_eng.decoders["lm"]._dec_params is None     # never paid
    assert loop_eng.decoders["lm"]._dec_params is not None  # lazily paid


def test_registry_repr_and_queries(params):
    eng = _engine(params, models=("m0", "m1"))
    assert isinstance(eng.models, ModelRegistry)
    assert len(eng.models) == 2 and list(eng.models) == ["m0", "m1"]
    assert "m0" in eng.models and "zzz" not in eng.models
    assert eng.models.get("m0").kind == "full"
    assert "m0" in repr(eng.models)
