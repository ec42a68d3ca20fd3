"""A configuration file: its sizes, the program's model config for it, and
seeded weights made on the device in the layout the program takes.

The weights are the benchmark's own: the same seed gives the same weights,
to the program in the timed run and to the reference after it.
"""
from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class Shapes:
    """The sizes the FLOP counts and the reference need, from the file."""
    d: int
    d_ff: int
    layers: int
    hq: int
    hkv: int
    head_dim: int
    vocab: int
    tied: bool
    rope_theta: float
    eps: float

    @property
    def kv_width(self) -> int:
        return self.hkv * self.head_dim

    @property
    def q_width(self) -> int:
        return self.hq * self.head_dim


def load(path) -> dict:
    with open(path) as f:
        return json.load(f)


def shapes(conf: dict) -> Shapes:
    return Shapes(
        d=conf["hidden_size"], d_ff=conf["intermediate_size"],
        layers=conf["num_hidden_layers"], hq=conf["num_attention_heads"],
        hkv=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        vocab=conf["vocab_size"], tied=conf["tie_word_embeddings"],
        rope_theta=float(conf["rope_theta"]), eps=float(conf["rms_norm_eps"]))


def program_config(conf: dict):
    """The program's registered config with the file's overrides; refuses
    one whose sizes differ from the file's."""
    from repro.configs.base import get_config
    cfg = dataclasses.replace(get_config(conf["repo_config"]),
                              **conf.get("repo_overrides", {}))
    s = shapes(conf)
    got = Shapes(d=cfg.d_model, d_ff=cfg.d_ff, layers=cfg.n_layers,
                 hq=cfg.n_heads, hkv=cfg.n_kv_heads, head_dim=cfg.head_dim,
                 vocab=cfg.vocab_size, tied=cfg.tie_embeddings,
                 rope_theta=float(cfg.rope_theta), eps=float(cfg.norm_eps))
    if got != s or cfg.dtype != conf["torch_dtype"] or not cfg.qk_norm:
        raise ValueError(f"{conf['name']}: program config {got} differs "
                         f"from the file's {s}")
    return cfg


def module_key(seed: int, offset: int):
    """A module's key from the run seed, which may exceed 32 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, offset)


def _leaf(key, shape, std, dtype):
    return (jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype))


@partial(jax.jit, static_argnums=(0, 2, 3, 4))
def _init(s: Shapes, key, embed_scale: float, norm_std: float,
          embed_std: float):
    dt = jnp.bfloat16
    L, d, f = s.layers, s.d, s.d_ff
    ks = iter(jax.random.split(key, 16))

    def dense(shape):
        return _leaf(next(ks), shape, shape[-2] ** -0.5, dt)

    def norm(shape):
        # the program keeps a norm's scale as (scale - 1)
        return _leaf(next(ks), shape, norm_std, dt)

    layer = {
        "norm1": norm((L, d)),
        "attn": {"wq": dense((L, d, s.q_width)),
                 "wk": dense((L, d, s.kv_width)),
                 "wv": dense((L, d, s.kv_width)),
                 "wo": dense((L, s.q_width, d)),
                 "q_norm": norm((L, s.head_dim)),
                 "k_norm": norm((L, s.head_dim))},
        "norm2": norm((L, d)),
        "mlp": {"wi": dense((L, d, f)), "wu": dense((L, d, f)),
                "wo": dense((L, f, d))},
    }
    params = {"embed": _leaf(next(ks), (s.vocab, d), embed_std * embed_scale,
                             dt),
              "final_norm": norm((d,)),
              "groups": {"pos0": layer},
              "tail": []}
    if not s.tied:
        params["unembed"] = _leaf(next(ks), (s.vocab, d), embed_std, dt)
    return params


def init_module(conf: dict, seed: int, module: str):
    """One module's weights, made on the device in one jitted call."""
    dep = conf["deployment"]
    std = dep["init_std"]
    p = _init(shapes(conf), module_key(seed, dep["modules"][module]),
              float(dep["embed_scale"]), float(std["norm"]),
              float(std["embed"]))
    return jax.block_until_ready(p)
