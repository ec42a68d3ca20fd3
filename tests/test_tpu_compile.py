"""Compile the serving main path for a described TPU v5e chip, at real widths.

Nothing runs: each test lowers and compiles for a chip that is described,
not attached, which is what the TPU's compiler refuses or accepts (block
shapes, fast-memory use, programs that do not fit in HBM). Shapes come from
``jax.eval_shape``, so no weights are ever built.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.core import prefillshare
from repro.kernels.flash_prefill_paged import flash_prefill_paged
from repro.kernels.paged_decode import paged_decode_attention
from repro.kernels.paged_write import paged_write
from repro.models import attention, init_params
from repro.serving.decode import StackedDecoders
from repro.serving.registry import DecodeModelSpec

QWEN3 = "qwen3-1.7b"
PAGES, PAGE = 1024, 16          # the chip smoke's pool: 16,384 tokens
NPAGES = 256                    # table width bucket of a 2k-4k context
HBM_BUDGET = 14.75e9            # v5e's 15.75 GB usable, less 1 GB spare


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache; keep the cache out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def chip(one_chip, no_compile_cache, monkeypatch):
    """ShapeDtypeStruct factory on the described chip; the model's attention
    takes its TPU (Mosaic kernel) branch."""
    monkeypatch.setattr(attention, "_use_kernels", lambda: True)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return sds


def _pool_state(sds, cfg):
    shape = (cfg.n_layers, PAGES + 1, PAGE, cfg.n_kv_heads * cfg.head_dim)
    return {"groups": {"pos0": {"k": sds(shape, jnp.bfloat16),
                                "v": sds(shape, jnp.bfloat16)}},
            "tail": []}


def _param_shapes(sds, cfg):
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    return jax.tree.map(lambda s: sds(s.shape, s.dtype), shapes)


def _nbytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _kernel_call(name, cfg, sds, npages=NPAGES):
    hq, d = cfg.n_heads, cfg.head_dim
    f = cfg.n_kv_heads * d
    i32, bf16 = jnp.int32, jnp.bfloat16
    pool = sds((cfg.n_layers, PAGES + 1, PAGE, f), bf16)
    if name == "paged_decode":
        return jax.jit(paged_decode_attention).lower(
            sds((4, hq, d), bf16), pool, pool, sds((4, npages), i32),
            sds((4,), i32), layer=sds((), i32))
    if name == "flash_prefill_paged":
        return jax.jit(flash_prefill_paged).lower(
            sds((1, 256, hq, d), bf16), pool, pool, sds((1, NPAGES), i32),
            sds((1,), i32), layer=sds((), i32))
    flat = sds((cfg.n_layers * (PAGES + 1), PAGE, f), bf16)
    return jax.jit(paged_write, donate_argnums=(2, 3)).lower(
        sds((cfg.n_layers, 8 * PAGE, f), bf16),
        sds((cfg.n_layers, 8 * PAGE, f), bf16), flat, flat,
        sds((cfg.n_layers, 8), i32), sds((cfg.n_layers,), i32))


@pytest.mark.parametrize("name, model", [
    ("paged_decode", QWEN3), ("paged_decode", "qwen3-8b"),
    ("flash_prefill_paged", QWEN3), ("paged_write", QWEN3)])
def test_kernel_compiles_for_v5e(chip, name, model):
    """The main-path kernels' block shapes are legal on the chip at the
    config's head widths, and each lowers to a Mosaic custom call."""
    compiled = _kernel_call(name, get_config(model), chip).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_decode_compiles_for_v5e_at_8b_bucket(chip):
    """The 8b cell's table bucket, 1,024 pages over four rows: the kernel's
    double-buffered page blocks fit the chip's fast memory, and it lowers
    to a Mosaic custom call."""
    compiled = _kernel_call("paged_decode", get_config("qwen3-8b"), chip,
                            npages=1024).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_decode_step_fits_v5e(chip):
    """Two full-weight qwen3-1.7b decode modules over a 1024-page pool: the
    fused step's arguments + temporaries + the engine's base copy fit in
    HBM with 1 GB to spare; the pool is updated in place (aliased, not
    copied per lane), and no weights are transposed or copied per step."""
    cfg = get_config(QWEN3)
    params = _param_shapes(chip, cfg)
    plane = StackedDecoders(cfg, {"a": DecodeModelSpec(full=params),
                                  "b": DecodeModelSpec(full=params)}, None)
    step = jax.jit(plane._step.__wrapped__, donate_argnums=(1,),
                   static_argnums=(11,))
    i32, f32 = jnp.int32, jnp.float32
    m, b = 2, 2
    state = _pool_state(chip, cfg)
    compiled = step.lower(
        [params, params], state, chip((m, b), i32), chip((m, b), i32),
        chip((m, b, NPAGES), i32), chip((m * b,), i32), chip((m * b,), i32),
        chip((m * b,), f32), chip((m * b,), i32), chip((m * b,), f32),
        chip((m * b,), i32), True).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + _nbytes(params))
    assert total <= HBM_BUDGET, total
    assert mem.alias_size_in_bytes >= _nbytes(state)
    assert mem.temp_size_in_bytes < 64e6, mem.temp_size_in_bytes
    assert "vmap()/transpose" not in text
    assert "tpu_custom_call" in text


def test_chunk_step_updates_pool_in_place_v5e(chip):
    """The chunked-prefill step over the donated pool: no second pool."""
    cfg = get_config(QWEN3)
    params = _param_shapes(chip, cfg)
    state = _pool_state(chip, cfg)
    step = jax.jit(prefillshare._make_chunk_step(cfg).__wrapped__,
                   donate_argnums=(3,))
    compiled = step.lower(params, chip((1, 256), jnp.int32),
                          chip((1,), jnp.int32), state,
                          chip((1, NPAGES), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _nbytes(state)
    assert mem.temp_size_in_bytes < 256e6, mem.temp_size_in_bytes
    assert "tpu_custom_call" in compiled.as_text()
