"""A toy mixture of experts, for tests: GQA attention with NeoX rope and no
q/k norm, then in every layer a softmax router that sends each token to its
top-k experts, each a SwiGLU MLP, their outputs weighted by the renormalized
router probabilities. The program serves it with ``repro/models/moe.py``.

A test copies this file to ``bench/families/toy_moe.py`` in a checkout: a
model family that the harness has never seen, added as a file alone.

The program drops a token that finds its expert full; with
``capacity_factor * top_k >= experts`` every expert takes a whole row, so no
token is ever dropped and the dropless reference here is the same function.
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
    d: int
    d_expert: int
    experts: int
    top_k: int
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
        d=conf["hidden_size"], d_expert=conf["moe_intermediate_size"],
        experts=conf["num_experts"], top_k=conf["num_experts_per_tok"],
        layers=conf["num_hidden_layers"], hq=conf["num_attention_heads"],
        hkv=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        vocab=conf["vocab_size"], tied=conf["tie_word_embeddings"],
        rope_theta=float(conf["rope_theta"]), eps=float(conf["rms_norm_eps"]))


def program_config(conf: dict):
    """The program's config with the file's overrides; refuses one whose
    sizes differ, that has q/k norm or shared experts, or that can drop a
    token."""
    from repro.configs.base import get_config
    cfg = dataclasses.replace(get_config(conf["repo_config"]),
                              **conf.get("repo_overrides", {}))
    s = shapes(conf)
    got = Shapes(d=cfg.d_model, d_expert=cfg.d_ff, experts=cfg.n_experts,
                 top_k=cfg.top_k, layers=cfg.n_layers, hq=cfg.n_heads,
                 hkv=cfg.n_kv_heads, head_dim=cfg.head_dim,
                 vocab=cfg.vocab_size, tied=cfg.tie_embeddings,
                 rope_theta=float(cfg.rope_theta), eps=float(cfg.norm_eps))
    if (got != s or cfg.dtype != conf["torch_dtype"] or cfg.qk_norm
            or cfg.n_shared_experts
            or cfg.capacity_factor * cfg.top_k < cfg.n_experts):
        raise ValueError(f"{conf['name']}: program config {cfg} differs "
                         f"from the file's {s}")
    return cfg


@partial(jax.jit, static_argnums=(0, 2, 3, 4, 5))
def _init(s: Shapes, key, embed_scale: float, norm_std: float,
          embed_std: float, router_std: float):
    dt = jnp.bfloat16
    L, d, f, E = s.layers, s.d, s.d_expert, s.experts
    ks = iter(jax.random.split(key, 16))

    def dense(shape):
        return _leaf(next(ks), shape, shape[-2] ** -0.5, dt)

    def norm(shape):
        return _leaf(next(ks), shape, norm_std, dt)

    layer = {
        "norm1": norm((L, d)),
        "attn": {"wq": dense((L, d, s.q_width)),
                 "wk": dense((L, d, s.kv_width)),
                 "wv": dense((L, d, s.kv_width)),
                 "wo": dense((L, s.q_width, d))},
        "norm2": norm((L, d)),
        # the program keeps the router in float32
        "moe": {"router": _leaf(next(ks), (L, d, E), router_std, jnp.float32),
                "wi": dense((L, E, d, f)), "wu": dense((L, E, d, f)),
                "wo": dense((L, E, f, d))},
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
    dep = conf["deployment"]
    std = dep["init_std"]
    p = _init(shapes(conf), module_key(seed, dep["modules"][module]),
              float(dep["embed_scale"]), float(std["norm"]),
              float(std["embed"]), float(std["router"]))
    return jax.block_until_ready(p)


def _experts(s: Shapes, control: bool, m, h):
    """Each row through its top-k experts, weighted by the renormalized
    router probabilities; every expert runs over every row and the weights
    of the experts a row does not choose are 0."""
    probs = jax.nn.softmax(_mm(h, m["router"], control), axis=-1)
    top, idx = jax.lax.top_k(probs, s.top_k)
    top = top / top.sum(-1, keepdims=True)
    weight = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top)
    out = jnp.zeros_like(h)
    for e in range(s.experts):
        y = _mm(jax.nn.silu(_mm(h, m["wi"][e], control))
                * _mm(h, m["wu"][e], control), m["wo"][e], control)
        out = out + weight[:, e:e + 1] * y
    return out


@partial(jax.jit, static_argnums=(0, 1))
def _layer(s: Shapes, control: bool, w, x, qpos, kvalid, k_prev, v_prev,
           kpos_prev):
    S = x.shape[0]
    a = w["attn"]
    h = _rms(x, w["norm1"], s.eps)
    q = _rope(_mm(h, a["wq"], control).reshape(S, s.hq, s.head_dim), qpos,
              s.rope_theta)
    k = _rope(_mm(h, a["wk"], control).reshape(S, s.hkv, s.head_dim), qpos,
              s.rope_theta)
    v = _mm(h, a["wv"], control).reshape(S, s.hkv, s.head_dim)
    if control:
        k, v = _q8(k, -1), _q8(v, -1)
    kk = jnp.concatenate([k_prev, k])
    vv = jnp.concatenate([v_prev, v])
    kpos = jnp.concatenate([kpos_prev, jnp.where(kvalid, qpos, -1)])
    x = x + _mm(_attend(q, kk, vv, qpos, kpos), a["wo"], control)
    return x + _experts(s, control, w["moe"], _rms(x, w["norm2"], s.eps)), k, v


def served_logits(s: Shapes, base, dec, prompt, served, first_token,
                  control=False):
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


def _token_flops(s: Shapes) -> int:
    """One token through one layer's projections, router and the top-k
    experts it is sent to."""
    d = s.d
    return 2 * (d * s.q_width + 2 * d * s.kv_width + s.q_width * d
                + d * s.experts + s.top_k * 3 * d * s.d_expert)


def decode_step(s: Shapes, lengths) -> int:
    per_token = s.layers * _token_flops(s) + 2 * s.d * s.vocab
    return (len(lengths) * per_token
            + s.layers * sum(flops._attn_flops(s, n) for n in lengths))


def chunk_step(s: Shapes, chunks) -> int:
    tokens = sum(n for _, n in chunks)
    return s.layers * (tokens * _token_flops(s)
                       + sum(flops._attn_flops(s, flops._chunk_keys(st, n))
                             for st, n in chunks))


def paged_decode(s: Shapes, lengths) -> tuple[int, int]:
    f, b = flops.paged_decode(s, lengths)
    return f * s.layers, b * s.layers


def flash_prefill_paged(s: Shapes, chunks) -> tuple[int, int]:
    f, b = flops.flash_prefill_paged(s, chunks)
    return f * s.layers, b * s.layers
