"""Each configuration's deployment compiles for a described TPU v5e and fits
its 16 GB: the base module, two decode modules and the pool, with the fused
decode step and the prefill chunk step at the widest table the cell's
traffic reaches. Nothing runs; shapes come from ``jax.eval_shape``."""
import glob
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import model as bmodel

HERE = os.path.dirname(__file__)
CONFIGS = sorted(glob.glob(os.path.join(HERE, "..", "configs", "*.json")))
HBM = 15.75e9                  # what a v5e chip gives a program
SPARE = 0.5e9


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
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def sds(one_chip, no_compile_cache, monkeypatch):
    from repro.models import attention
    monkeypatch.setattr(attention, "_use_kernels", lambda: True)
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)


def _nbytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _setup(path, sds):
    conf = bmodel.load(path)
    fam = bmodel.family(conf)
    s = fam.shapes(conf)
    params = jax.eval_shape(lambda: fam.init_module(
        conf, 0, conf["deployment"]["prefill_module"]))
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), params)
    rows = (s.layers, conf["pool_pages"] + 1, conf["page_size"], s.kv_width)
    pool = {"groups": {"pos0": {"k": sds(rows, jnp.bfloat16),
                                "v": sds(rows, jnp.bfloat16)}}, "tail": []}
    return conf, fam.program_config(conf), params, pool


def _widest_table(conf):
    from bench.spec import benchmark, traffic_path
    widest = 1
    for w in benchmark()["workloads"]:
        if w["config"] != conf["name"]:
            continue
        t = bmodel.load(traffic_path(w["traffic"]))
        tokens = (t["system_prompt_tokens"] + t["document_tokens"][1]
                  + t["observation_tokens"][1] * (t["turns"] - 1)
                  + t["tail_tokens"][1] + t["reply_tokens"][1])
        pages = -(-tokens // conf["page_size"]) + 1
        widest = max(widest, 1 << (pages - 1).bit_length())
    return widest


@pytest.mark.parametrize("path", CONFIGS, ids=os.path.basename)
def test_deployment_fits_v5e(path, sds):
    from repro.core import prefillshare
    from repro.serving.decode import StackedDecoders
    from repro.serving.registry import DecodeModelSpec
    conf, cfg, params, pool = _setup(path, sds)
    npages = _widest_table(conf)
    plane = StackedDecoders(cfg, {"a": DecodeModelSpec(full=params),
                                  "b": DecodeModelSpec(full=params)}, None)
    step = jax.jit(plane._step.__wrapped__, donate_argnums=(1,),
                   static_argnums=(11,))
    m, b = 2, 8
    i32, f32 = jnp.int32, jnp.float32
    fused = step.lower(
        [params, params], pool, sds((m, b), i32), sds((m, b), i32),
        sds((m, b, npages), i32), sds((m * b,), i32), sds((m * b,), i32),
        sds((m * b,), f32), sds((m * b,), i32), sds((m * b,), f32),
        sds((m * b,), i32), True).compile().memory_analysis()
    chunk = jax.jit(prefillshare._make_chunk_step(cfg).__wrapped__,
                    donate_argnums=(3,)).lower(
        params, sds((3, 256), i32), sds((3,), i32), pool,
        sds((3, npages), i32)).compile().memory_analysis()
    base = _nbytes(params)
    for mem in (fused, chunk):
        assert mem.alias_size_in_bytes >= _nbytes(pool)
    resident = 3 * base + _nbytes(pool)
    peak = resident + max(fused.temp_size_in_bytes, chunk.temp_size_in_bytes)
    assert peak <= HBM - SPARE, (resident, fused.temp_size_in_bytes,
                                 chunk.temp_size_in_bytes)
