"""A configuration file and its model family.

A configuration's ``model_type`` names its family, the file
``bench/families/<model_type>.py``: the only benchmark code that knows the
model's layer. A family gives

- ``shapes(conf)``: a frozen, hashable object with at least ``layers``,
  ``d``, ``hq``, ``hkv``, ``head_dim``, ``vocab``, ``tied``, ``eps``,
  ``kv_width`` and ``q_width``, and whatever else its own code reads;
- ``program_config(conf)``: the program's model config with the file's
  overrides, refusing one that disagrees with the file;
- ``init_module(conf, seed, module)``: one module's seeded weights in the
  layout the program takes, made on the device in one jitted call;
- ``served_logits(s, base, dec, prompt, served, first_token,
  control=False)``: the float32 reference's logits at the served positions
  of one request (see ``bench/reference.py``);
- ``decode_step(s, lengths)``, ``chunk_step(s, chunks)``: the model
  operations of the served work, and ``paged_decode(s, lengths)``,
  ``flash_prefill_paged(s, chunks)``: (operations, bytes) of the attention
  kernels' calls, each summed over the model's layers.

The weights are the benchmark's own: the same seed gives the same weights,
to the program in the timed run and to the reference after it.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

FAMILIES = Path(__file__).resolve().parent / "families"


def load(path) -> dict:
    with open(path) as f:
        return json.load(f)


def family(conf: dict):
    """The module of ``bench/families/<model_type>.py``."""
    kind = conf.get("model_type")
    if not kind:
        raise ValueError(f"{conf.get('name')}: the configuration names no "
                         f"model_type")
    name = f"bench.families.{kind}"
    if name not in sys.modules:
        path = FAMILIES / f"{kind}.py"
        if not path.is_file():
            known = sorted(p.stem for p in FAMILIES.glob("*.py"))
            raise ValueError(f"{conf.get('name')}: no family {kind!r} "
                             f"(have {known})")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        # a dataclass looks up its module while it is made
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def module_key(seed: int, offset: int):
    """A module's key from the run seed, which may exceed 32 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, offset)


def _leaf(key, shape, std, dtype):
    return (jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype))
