"""The benchmark's weights are in the layout the program takes, its
configurations are the program's, its plain reference computes what the
program's own dense forward pass computes, and a configuration's
``model_type`` names the family that knows its layer."""
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model as bmodel

HERE = os.path.dirname(__file__)
CONFIGS = sorted(glob.glob(os.path.join(HERE, "..", "configs", "*.json")))
TINY = os.path.join(HERE, "data", "tiny.json")


@pytest.mark.parametrize("path", CONFIGS + [TINY])
def test_weights_match_program_layout(path):
    from repro.models import init_params
    conf = bmodel.load(path)
    fam = bmodel.family(conf)
    cfg = fam.program_config(conf)
    want = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    got = jax.eval_shape(lambda: fam.init_module(
        conf, 0, conf["deployment"]["prefill_module"]))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)


def test_seed_over_32_bits_gives_distinct_weights():
    conf = bmodel.load(TINY)
    init = bmodel.family(conf).init_module
    a = init(conf, 2**31 + 5, "base")["embed"]
    b = init(conf, 2**32 + 2**31 + 5, "base")["embed"]
    c = init(conf, 2**31 + 5, "base")["embed"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(a), np.asarray(c))


def test_reference_matches_program_forward():
    """Base prefill of a prompt, then a decode module fed the first token
    and its own greedy tokens: the program's dense path in float32 against
    the reference, logits within float32 rounding."""
    from repro.core.prefillshare import base_prefill
    from repro.models import forward
    conf = bmodel.load(TINY)
    fam = bmodel.family(conf)
    cfg = dataclasses.replace(fam.program_config(conf), dtype="float32")
    s = fam.shapes(conf)
    base = fam.init_module(conf, 11, "base")
    dec = fam.init_module(conf, 11, "agent_a")
    prompt = np.random.default_rng(0).integers(4, s.vocab, 37).tolist()
    served = [int(t) for t in np.random.default_rng(1).integers(4, s.vocab, 6)]
    f32 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)  # noqa
    with jax.default_matmul_precision("highest"):
        _, cache = base_prefill(cfg, f32(base), jnp.asarray([prompt]),
                                cache_len=64)
        toks = jnp.asarray([[2] + served[:-1]])
        logits, _, _ = forward(cfg, f32(dec), toks, cache=cache,
                               pos=jnp.asarray([len(prompt)]), logits="all")
        ref = fam.served_logits(s, base, dec, prompt, served, 2)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(logits[0]),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("model_type,error", [
    (None, "names no model_type"), ("", "names no model_type"),
    ("no_such_family", "no family 'no_such_family'")])
def test_family_must_be_named_and_exist(model_type, error):
    conf = {**bmodel.load(TINY), "model_type": model_type}
    with pytest.raises(ValueError, match=error):
        bmodel.family(conf)


def test_qwen3_refuses_a_program_without_qk_norm():
    conf = bmodel.load(TINY)
    fam = bmodel.family(conf)
    assert fam.program_config(conf).qk_norm
    conf["repo_overrides"] = {**conf["repo_overrides"], "qk_norm": False}
    with pytest.raises(ValueError, match="differs"):
        fam.program_config(conf)
