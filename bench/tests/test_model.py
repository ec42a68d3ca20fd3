"""The benchmark's weights are in the layout the program takes, its
configurations are the program's, and its plain reference computes what
the program's own dense forward pass computes."""
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model as bmodel
from bench.reference import served_logits

HERE = os.path.dirname(__file__)
CONFIGS = sorted(glob.glob(os.path.join(HERE, "..", "configs", "*.json")))
TINY = os.path.join(HERE, "data", "tiny.json")


@pytest.mark.parametrize("path", CONFIGS + [TINY])
def test_weights_match_program_layout(path):
    from repro.models import init_params
    conf = bmodel.load(path)
    cfg = bmodel.program_config(conf)
    dep = conf["deployment"]
    want = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    got = jax.eval_shape(lambda: bmodel._init(
        bmodel.shapes(conf), jax.random.PRNGKey(0), float(dep["embed_scale"]),
        0.1, 0.02))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)


def test_seed_over_32_bits_gives_distinct_weights():
    conf = bmodel.load(TINY)
    a = bmodel.init_module(conf, 2**31 + 5, "base")["embed"]
    b = bmodel.init_module(conf, 2**32 + 2**31 + 5, "base")["embed"]
    c = bmodel.init_module(conf, 2**31 + 5, "base")["embed"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(a), np.asarray(c))


def test_reference_matches_program_forward():
    """Base prefill of a prompt, then a decode module fed the first token
    and its own greedy tokens: the program's dense path in float32 against
    the reference, logits within float32 rounding."""
    from repro.core.prefillshare import base_prefill
    from repro.models import forward
    conf = bmodel.load(TINY)
    cfg = dataclasses.replace(bmodel.program_config(conf), dtype="float32")
    s = bmodel.shapes(conf)
    base = bmodel.init_module(conf, 11, "base")
    dec = bmodel.init_module(conf, 11, "agent_a")
    prompt = np.random.default_rng(0).integers(4, s.vocab, 37).tolist()
    served = [int(t) for t in np.random.default_rng(1).integers(4, s.vocab, 6)]
    f32 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)  # noqa
    with jax.default_matmul_precision("highest"):
        _, cache = base_prefill(cfg, f32(base), jnp.asarray([prompt]),
                                cache_len=64)
        toks = jnp.asarray([[2] + served[:-1]])
        logits, _, _ = forward(cfg, f32(dec), toks, cache=cache,
                               pos=jnp.asarray([len(prompt)]), logits="all")
        ref = served_logits(s, base, dec, prompt, served, 2)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(logits[0]),
                               atol=2e-4, rtol=2e-4)
