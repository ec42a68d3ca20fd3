"""Dense Qwen3: GQA attention with per-head q/k RMSNorm and NeoX rope, then a
SwiGLU MLP, in every layer.

Its weights in the program's layout, its float32 reference layer, and the
operations its served work needs.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops
from bench.model import _leaf, module_key
from bench.reference import (PROMPT_PAD, REPLY_PAD, _attend, _embed,
                             _layer_weights, _logits, _mm, _pad, _q8, _rms,
                             _rope)


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


def shapes(conf: dict) -> Shapes:
    return Shapes(
        d=conf["hidden_size"], d_ff=conf["intermediate_size"],
        layers=conf["num_hidden_layers"], hq=conf["num_attention_heads"],
        hkv=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        vocab=conf["vocab_size"], tied=conf["tie_word_embeddings"],
        rope_theta=float(conf["rope_theta"]), eps=float(conf["rms_norm_eps"]))


def program_config(conf: dict):
    """The program's registered config with the file's overrides; refuses
    one whose sizes differ from the file's, or that has no q/k norm."""
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


@partial(jax.jit, static_argnums=(0, 1))
def _layer(s: Shapes, control: bool, w, x, qpos, kvalid, k_prev, v_prev,
           kpos_prev):
    """One decoder layer over rows x (S, d) at qpos; rows with kvalid False
    are padding and give no keys. Returns (x, k, v) of these rows."""
    S = x.shape[0]
    a = w["attn"]
    h = _rms(x, w["norm1"], s.eps)
    q = _mm(h, a["wq"], control).reshape(S, s.hq, s.head_dim)
    k = _mm(h, a["wk"], control).reshape(S, s.hkv, s.head_dim)
    v = _mm(h, a["wv"], control).reshape(S, s.hkv, s.head_dim)
    q = _rope(_rms(q, a["q_norm"], s.eps), qpos, s.rope_theta)
    k = _rope(_rms(k, a["k_norm"], s.eps), qpos, s.rope_theta)
    if control:
        k, v = _q8(k, -1), _q8(v, -1)
    kk = jnp.concatenate([k_prev, k])
    vv = jnp.concatenate([v_prev, v])
    kpos = jnp.concatenate([kpos_prev, jnp.where(kvalid, qpos, -1)])
    x = x + _mm(_attend(q, kk, vv, qpos, kpos), a["wo"], control)
    h = _rms(x, w["norm2"], s.eps)
    m = w["mlp"]
    x = x + _mm(jax.nn.silu(_mm(h, m["wi"], control)) * _mm(h, m["wu"],
                                                              control),
                m["wo"], control)
    return x, k, v


def served_logits(s: Shapes, base, dec, prompt, served, first_token,
                  control=False):
    """Float32 logits (L, V) at each served position of one request: the
    base module prefills ``prompt``; the decode module is fed
    ``first_token`` and then each served token but the last."""
    n, L = len(prompt), len(served)
    Np, Lp = _pad(n, PROMPT_PAD), _pad(L, REPLY_PAD)
    toks = np.zeros(Np, np.int32)
    toks[:n] = prompt
    pos = jnp.arange(Np, dtype=jnp.int32)
    valid = pos < n
    empty = jnp.zeros((0, s.hkv, s.head_dim), jnp.float32)
    nokeys = jnp.zeros((0,), jnp.int32)
    x = _embed(s, base["embed"], jnp.asarray(toks))
    cache = []
    for layer in range(s.layers):
        x, k, v = _layer(s, control, _layer_weights(base, layer), x, pos,
                         valid, empty, empty, nokeys)
        cache.append((k, v))
    del x
    dtoks = np.zeros(Lp, np.int32)
    dtoks[:L] = [first_token] + list(served[:-1])
    dpos = n + jnp.arange(Lp, dtype=jnp.int32)
    dvalid = jnp.arange(Lp) < L
    kpos_prev = jnp.where(valid, pos, -1)
    y = _embed(s, dec["embed"], jnp.asarray(dtoks))
    for layer in range(s.layers):
        k, v = cache[layer]
        y, _, _ = _layer(s, control, _layer_weights(dec, layer), y, dpos,
                         dvalid, k, v, kpos_prev)
    table = dec["embed"] if s.tied else dec["unembed"]
    return _logits(s, control, y, dec["final_norm"], table)[:L]


def _layer_matmul_flops(s: Shapes) -> int:
    """One token through one layer's projections and MLP."""
    d = s.d
    return 2 * (d * s.q_width + 2 * d * s.kv_width + s.q_width * d
                + 3 * d * s.d_ff)


def decode_step(s: Shapes, lengths) -> int:
    """Model operations of decode steps that produce one token for each of
    ``lengths`` (keys attended, the token's own included): every layer and
    the output head."""
    per_token = s.layers * _layer_matmul_flops(s) + 2 * s.d * s.vocab
    return (len(lengths) * per_token
            + s.layers * sum(flops._attn_flops(s, n) for n in lengths))


def chunk_step(s: Shapes, chunks) -> int:
    """Model operations of prefill chunks (start, size): every layer, no
    output head (a chunk step returns no logits)."""
    tokens = sum(n for _, n in chunks)
    return s.layers * (tokens * _layer_matmul_flops(s)
                       + sum(flops._attn_flops(s, flops._chunk_keys(st, n))
                             for st, n in chunks))


def paged_decode(s: Shapes, lengths) -> tuple[int, int]:
    """Every layer's ``paged_decode`` call over ``lengths``."""
    f, b = flops.paged_decode(s, lengths)
    return f * s.layers, b * s.layers


def flash_prefill_paged(s: Shapes, chunks) -> tuple[int, int]:
    """Every layer's ``flash_prefill_paged`` call over ``chunks``."""
    f, b = flops.flash_prefill_paged(s, chunks)
    return f * s.layers, b * s.layers
