"""End-to-end behaviour tests for the PrefillShare system.

The heavyweight claims (Fig-2 curve, engine bit-equivalence) have dedicated
test modules; this file asserts the cross-cutting system invariants that tie
the layers together.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.configs import ASSIGNED, INPUT_SHAPES, get_config

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_all_assigned_archs_registered_with_exact_dims():
    expect = {
        "granite-moe-3b-a800m": (32, 1536, 24, 8, 512, 49155),
        "gemma2-27b": (46, 4608, 32, 16, 36864, 256000),
        "seamless-m4t-medium": (12, 1024, 16, 16, 4096, 256206),
        "chatglm3-6b": (28, 4096, 32, 2, 13696, 65024),
        "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256000),
        "granite-8b": (36, 4096, 32, 8, 14336, 49152),
        "internlm2-1.8b": (24, 2048, 16, 8, 8192, 92544),
        "grok-1-314b": (64, 6144, 48, 8, 32768, 131072),
        "internvl2-76b": (80, 8192, 64, 8, 28672, 128256),
        "mamba2-780m": (48, 1536, 0, 0, 0, 50280),
    }
    assert set(ASSIGNED) == set(expect)
    for name, (L, d, h, kv, ff, V) in expect.items():
        c = get_config(name)
        assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
                c.vocab_size) == (L, d, h, kv, ff, V), name
        assert c.source, f"{name} missing citation"


def test_moe_configs():
    g = get_config("granite-moe-3b-a800m")
    assert g.n_experts == 40 and g.top_k == 8
    k = get_config("grok-1-314b")
    assert k.n_experts == 8 and k.top_k == 2


def test_long_context_eligibility():
    ok = {a for a in ASSIGNED if get_config(a).long_context_ok}
    assert ok == {"mamba2-780m", "recurrentgemma-2b", "gemma2-27b"}


def test_input_shapes_assigned():
    s = INPUT_SHAPES
    assert (s["train_4k"].seq_len, s["train_4k"].global_batch) == (4096, 256)
    assert (s["prefill_32k"].seq_len, s["prefill_32k"].global_batch) == (32768, 32)
    assert (s["decode_32k"].seq_len, s["decode_32k"].global_batch) == (32768, 128)
    assert (s["long_500k"].seq_len, s["long_500k"].global_batch) == (524288, 1)


def test_sharding_policy_divisibility():
    """No rule may ever produce an uneven shard on the production mesh."""
    import itertools

    from repro.launch.sharding import param_pspec
    shapes = [(49155, 1536), (1536, 6448), (40, 1536, 512), (8, 6144, 32768),
              (4096, 4096), (14336, 4096), (2, 46, 128), (256000, 4608)]
    for shape in shapes:
        for name in ("x/wo", "x/wi", "embed"):
            spec = param_pspec(name, shape, 16, 16)
            for dim, ax in itertools.zip_longest(shape, spec, fillvalue=None):
                if ax in ("model", "data"):
                    assert dim is not None and dim % 16 == 0, (name, shape, spec)


def test_mesh_shapes_subprocess():
    """make_production_mesh builds 16x16 and 2x16x16 (512 fake devices)."""
    code = (
        "import os; os.environ['XLA_FLAGS']="
        "'--xla_force_host_platform_device_count=512'\n"
        "import sys; sys.path.insert(0, %r)\n"
        "from repro.launch.mesh import make_production_mesh\n"
        "m1 = make_production_mesh(); m2 = make_production_mesh(multi_pod=True)\n"
        "assert m1.devices.shape == (16, 16) and m1.axis_names == ('data', 'model')\n"
        "assert m2.devices.shape == (2, 16, 16)\n"
        "assert m2.axis_names == ('pod', 'data', 'model')\n"
        "print('ok')\n" % SRC)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=240)
    assert r.returncode == 0 and "ok" in r.stdout, r.stderr[-800:]


def test_tiny_sharded_execution_subprocess():
    """Actually EXECUTE a sharded serve_step on an 8-device host mesh."""
    code = (
        "import os; os.environ['XLA_FLAGS']="
        "'--xla_force_host_platform_device_count=8'\n"
        "import sys; sys.path.insert(0, %r)\n"
        "import jax, jax.numpy as jnp, dataclasses\n"
        "from jax.sharding import AxisType\n"
        "from repro.configs import get_config\n"
        "from repro.configs.base import INPUT_SHAPES, InputShape\n"
        "from repro.launch.steps import build\n"
        "cfg = get_config('internlm2-1.8b').reduced()\n"
        "cfg = dataclasses.replace(cfg, name='t', vocab_size=512)\n"
        "mesh = jax.make_mesh((2, 4), ('data', 'model'),\n"
        "                     axis_types=(AxisType.Auto,) * 2)\n"
        "INPUT_SHAPES['tiny_dec'] = InputShape('tiny_dec', 64, 4, 'decode')\n"
        "b = build(cfg, 'tiny_dec', mesh)\n"
        "args = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), b['args'])\n"
        "with jax.set_mesh(mesh):\n"
        "    f = jax.jit(b['fn'], in_shardings=b['in_shardings'])\n"
        "    logits, cache = f(*args)\n"
        "    assert logits.shape == (4, cfg.vocab_size)\n"
        "    assert not bool(jnp.isnan(logits).any())\n"
        "print('ok')\n" % SRC)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0 and "ok" in r.stdout, (r.stderr[-1500:])


def test_dryrun_results_if_present():
    """If the dry-run sweep has been run, every non-skipped combo must have
    compiled (this is the deliverable-e gate)."""
    path = os.path.join(os.path.dirname(__file__), "..", "results",
                        "dryrun.jsonl")
    if not os.path.exists(path):
        pytest.skip("dry-run not executed yet")
    bad = []
    with open(path) as f:
        for line in f:
            r = json.loads(line)
            if "error" in r:
                bad.append((r["arch"], r["shape"], r.get("mesh")))
    assert not bad, f"dry-run failures: {bad}"


def test_cache_pspec_properties():
    """Decode caches shard seq on model; long-context shards seq on both."""
    pytest.importorskip("hypothesis", reason="optional dep")
    import hypothesis.strategies as st
    from hypothesis import given, settings

    from repro.launch.sharding import cache_pspec

    @settings(max_examples=200, deadline=None)
    @given(st.sampled_from(["k", "v", "kpos"]),
           st.sampled_from([1, 2, 8, 32, 128, 256]),
           st.sampled_from([2048, 4096, 32768, 524288]),
           st.sampled_from([64, 256, 1024, 2048]),
           st.booleans(), st.booleans())
    def check(leaf, B, T, F, stacked, decode):
        shape = ((4,) if stacked else ()) + ((B, T) if leaf == "kpos"
                                             else (B, T, F))
        name = ("groups/pos0/" if stacked else "tail/0/") + leaf
        spec = cache_pspec(name, shape, 16, 16, stacked=stacked,
                           decode=decode)
        # every sharded dim divides evenly
        for dim, ax in zip(shape, tuple(spec) + (None,) * len(shape)):
            if ax is None:
                continue
            ways = 1
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                ways *= 16
            assert dim % ways == 0, (shape, spec)
        # stacked leading dim never sharded
        if stacked:
            assert len(spec) == 0 or spec[0] is None

    check()
