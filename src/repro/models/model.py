"""Composable model: one stack covering dense / MoE / SSM / hybrid / enc-dec / VLM.

Layers are organized as repetitions of ``cfg.layer_pattern`` ("groups"). All
full groups are stacked on a leading axis and executed with ``lax.scan`` so HLO
size is O(1) in depth (an 80-layer model compiles in seconds); a remainder
"tail" (e.g. recurrentgemma's 26 = 8*3 + 2) runs unrolled. Caches mirror the
same structure, which makes the whole sequence state a single pytree — exactly
the object PrefillShare hands off between prefill and decode workers.

The unified ``forward(params, tokens, cache, pos)`` covers:
  - training forward (cache=None),
  - full prefill (pos=0, empty cache),
  - PARTIAL prefill (pos>0: extend an existing cache with appended tokens),
  - decode (S=1),
which is the paper's execution pipeline (§3.3) expressed as one function.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ATTN, LOCAL_ATTN, RGLRU, SSD, ModelConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssd as ssd_mod
from repro.models.layers import (embed_init, embed_lookup, mlp_apply, mlp_init,
                                 rmsnorm, rmsnorm_init, unembed)

Params = Any
Cache = Any

# Distributed activation policy, set by repro.launch.steps before tracing:
# a PartitionSpec applied to the (B, S, D) residual stream at block
# boundaries via with_sharding_constraint (pins GSPMD propagation; see
# EXPERIMENTS.md §Perf). None = single-host, no constraint.
ACTIVATION_SPEC = None


def _constrain(x):
    if ACTIVATION_SPEC is not None and x.ndim == 3:
        return jax.lax.with_sharding_constraint(x, ACTIVATION_SPEC)
    return x


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


def _group_structure(cfg: ModelConfig):
    pat = cfg.layer_pattern
    n_full = cfg.n_layers // len(pat)
    tail = tuple(pat[: cfg.n_layers % len(pat)])
    return pat, n_full, tail


# ======================================================================
# init


def _layer_init(key, cfg, kind, *, cross: bool, dtype):
    ks = jax.random.split(key, 8)
    p = {"norm1": rmsnorm_init(cfg.d_model, dtype)}
    if kind in (ATTN, LOCAL_ATTN):
        p["attn"] = attn_mod.attn_init(ks[0], cfg, dtype=dtype)
    elif kind == RGLRU:
        p["rglru"] = rglru_mod.rglru_init(ks[0], cfg, dtype=dtype)
    elif kind == SSD:
        p["ssd"] = ssd_mod.ssd_init(ks[0], cfg, dtype=dtype)
    if cross:
        p["norm_x"] = rmsnorm_init(cfg.d_model, dtype)
        p["cross"] = attn_mod.attn_init(ks[1], cfg, cross=True, dtype=dtype)
    if cfg.d_ff > 0 and kind != SSD:
        p["norm2"] = rmsnorm_init(cfg.d_model, dtype)
        if cfg.is_moe and kind in (ATTN, LOCAL_ATTN):
            p["moe"] = moe_mod.moe_init(ks[2], cfg, dtype=dtype)
        else:
            p["mlp"] = mlp_init(ks[2], cfg.d_model, cfg.d_ff, dtype=dtype)
    return p


def init_params(cfg: ModelConfig, key) -> Params:
    dtype = _dtype(cfg)
    pat, n_full, tail = _group_structure(cfg)
    keys = jax.random.split(key, 8)
    cross = cfg.is_encdec

    def stacked(kf, kind, pos):
        def one(k):
            return _layer_init(k, cfg, kind, cross=cross, dtype=dtype)
        return jax.vmap(one)(jax.random.split(jax.random.fold_in(kf, pos), n_full))

    params = {
        "embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
        "groups": {f"pos{i}": stacked(keys[1], kind, i) for i, kind in enumerate(pat)}
        if n_full else {},
        "tail": [
            _layer_init(jax.random.fold_in(keys[2], i), cfg, kind,
                        cross=cross, dtype=dtype)
            for i, kind in enumerate(tail)
        ],
    }
    if not cfg.tie_embeddings:
        params["unembed"] = embed_init(keys[3], cfg.vocab_size, cfg.d_model, dtype)
    if cfg.is_encdec:
        def enc_stack(k):
            def one(kk):
                return _layer_init(kk, cfg, ATTN, cross=False, dtype=dtype)
            return jax.vmap(one)(jax.random.split(k, cfg.encoder_layers))
        params["encoder"] = {"groups": enc_stack(keys[4]),
                             "norm": rmsnorm_init(cfg.d_model, dtype)}
    return params


def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               dtype=None, enc_len: int = 0) -> Cache:
    dtype = dtype or _dtype(cfg)
    pat, n_full, tail = _group_structure(cfg)

    def layer_cache(kind):
        if kind in (ATTN, LOCAL_ATTN):
            c = attn_mod.init_attn_cache(cfg, kind, batch, cache_len, dtype)
        elif kind == RGLRU:
            c = rglru_mod.init_rglru_cache(cfg, batch, dtype)
        elif kind == SSD:
            c = ssd_mod.init_ssd_cache(cfg, batch, dtype)
        else:
            raise ValueError(kind)
        if cfg.is_encdec:
            f = cfg.n_kv_heads * cfg.head_dim   # flattened (see init_attn_cache)
            c["cross"] = {"k": jnp.zeros((batch, enc_len, f), dtype),
                          "v": jnp.zeros((batch, enc_len, f), dtype)}
        return c

    def stacked(kind):
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_full,) + x.shape), layer_cache(kind))

    return {
        "groups": {f"pos{i}": stacked(kind) for i, kind in enumerate(pat)}
        if n_full else {},
        "tail": [layer_cache(kind) for kind in tail],
    }


# ======================================================================
# forward


def _apply_layer(lp, x, cfg, kind, cache, pos, enc_out, flash, causal=True):
    h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
    aux = {}
    if kind in (ATTN, LOCAL_ATTN):
        acache = None
        if cache is not None:
            acache = (cache if "k_pages" in cache
                      else {k: cache[k] for k in ("k", "v", "kpos")})
        out, new_acache = attn_mod.attn_apply(
            lp["attn"], h, cfg, kind, cache=acache, pos=pos, causal=causal,
            flash=flash)
        new_cache = dict(cache) if cache is not None else None
        if new_cache is not None and new_acache is not None:
            new_cache.update(new_acache)
    elif kind == RGLRU:
        sub = None if cache is None else {"h": cache["h"], "conv": cache["conv"]}
        out, nc = rglru_mod.rglru_apply(lp["rglru"], h, cfg, cache=sub)
        new_cache = dict(cache) if cache is not None else None
        if new_cache is not None:
            new_cache.update(nc)
    elif kind == SSD:
        sub = None if cache is None else {"ssm": cache["ssm"], "conv": cache["conv"]}
        out, nc = ssd_mod.ssd_apply(lp["ssd"], h, cfg, cache=sub)
        new_cache = dict(cache) if cache is not None else None
        if new_cache is not None:
            new_cache.update(nc)
    else:
        raise ValueError(kind)
    x = x + out

    if "cross" in lp:
        hx = rmsnorm(x, lp["norm_x"], cfg.norm_eps)
        ccache = cache.get("cross") if cache is not None else None
        # use cached cross-KV when it has been populated (prefill writes it)
        out, new_cc = attn_mod.attn_apply(
            lp["cross"], hx, cfg, ATTN, cache=ccache, enc_out=enc_out,
            cross=True, flash=flash)
        x = x + out
        if new_cache is not None:
            new_cache["cross"] = new_cc

    if "norm2" in lp:
        h2 = rmsnorm(x, lp["norm2"], cfg.norm_eps)
        if "moe" in lp:
            out2, aux = moe_mod.moe_apply(lp["moe"], h2, cfg)
        else:
            out2 = mlp_apply(lp["mlp"], h2)
        x = x + out2
    return x, new_cache, aux


def _aux_zero():
    return {"lb_loss": jnp.zeros((), jnp.float32),
            "dropped_frac": jnp.zeros((), jnp.float32)}


def _aux_add(a, b):
    if not b:
        return a
    return {k: a[k] + jnp.asarray(b.get(k, 0.0), jnp.float32) for k in a}


def encode(cfg: ModelConfig, params: Params, embeds, flash=None):
    """Bidirectional encoder over precomputed frame embeddings (audio stub)."""
    x = embeds.astype(_dtype(cfg))
    enc = params["encoder"]

    def body(x, lp):
        h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
        # bidirectional: all positions valid for all queries
        out, _ = attn_mod.attn_apply(lp["attn"], h, cfg, ATTN, cache=None,
                                     pos=None, causal=False, flash=flash)
        x = x + out
        h2 = rmsnorm(x, lp["norm2"], cfg.norm_eps)
        x = x + mlp_apply(lp["mlp"], h2)
        return x, None

    x, _ = lax.scan(body, x, enc["groups"])
    return rmsnorm(x, enc["norm"], cfg.norm_eps)


def forward(cfg: ModelConfig, params: Params, tokens, *, cache: Optional[Cache] = None,
            pos=None, prefix_embeds=None, enc_out=None, logits: str = "last",
            flash: Optional[bool] = None, remat: bool = False):
    """Run the decoder stack.

    tokens: (B, S) int32 (ignored for pure-embeds input). pos: (B,) absolute
    position of tokens[:, 0] (None -> zeros). Returns (output, new_cache, aux):
    output is last-token logits (B, V), all logits (B, S, V), or hidden states
    (B, S, D) depending on ``logits`` in {"last", "all", "hidden"}.
    """
    dtype = _dtype(cfg)
    if cfg.input_mode == "mixed" and prefix_embeds is not None:
        xt = embed_lookup(params["embed"], tokens) * jnp.asarray(
            cfg.d_model ** 0.5, dtype)
        x = jnp.concatenate([prefix_embeds.astype(dtype), xt], axis=1)
    elif cfg.input_mode == "embeds" and prefix_embeds is not None and not cfg.is_encdec:
        x = prefix_embeds.astype(dtype)
    else:
        x = embed_lookup(params["embed"], tokens) * jnp.asarray(
            cfg.d_model ** 0.5, dtype)

    B = x.shape[0]
    if pos is None:
        pos = jnp.zeros((B,), jnp.int32)

    pat, n_full, tail = _group_structure(cfg)
    aux = _aux_zero()
    gc = cache["groups"] if cache is not None and n_full else None
    # Paged KV (``kvcache.paged``): the layer-stacked pool rides in the scan
    # CARRY and each layer writes its rows into it in place, reading its own
    # layer by index. As scan xs/ys it would be sliced per layer and rebuilt
    # as a second whole pool on every call.
    pool = gc if gc is not None and "k_pages" in gc["pos0"] else None
    if pool is not None:
        gc = {key: {"layer": jnp.arange(n_full, dtype=jnp.int32)}
              for key in pool}

    def group_body(carry, slc):
        x, pool = carry
        gp, gc = slc
        a = _aux_zero()
        new_gc = {} if gc is not None and pool is None else None
        for i, kind in enumerate(pat):
            key = f"pos{i}"
            ci = gc[key] if gc is not None else None
            if pool is not None:
                ci = {**pool[key], **ci}
            x = _constrain(x)
            x, nc, ax = _apply_layer(gp[key], x, cfg, kind, ci, pos,
                                     enc_out, flash)
            a = _aux_add(a, ax)
            if pool is not None:
                pool = {**pool, key: {k: nc[k]
                                      for k in attn_mod.PAGED_CACHE_KEYS}}
            elif new_gc is not None:
                new_gc[key] = nc
        return (x, pool), (new_gc, a)

    if n_full:
        body = jax.checkpoint(group_body) if remat else group_body
        (x, pool), (new_groups, auxs) = lax.scan(
            body, (x, pool), (params["groups"], gc))
        if pool is not None:
            new_groups = pool
        aux = jax.tree.map(lambda a: a.sum(0), auxs)
    else:
        new_groups = {}

    new_tail = []
    for i, kind in enumerate(tail):
        ci = cache["tail"][i] if cache is not None else None
        lp = params["tail"][i]
        x, nc, ax = _apply_layer(lp, x, cfg, kind, ci, pos, enc_out, flash)
        aux = _aux_add(aux, ax)
        new_tail.append(nc)

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    new_cache = None
    if cache is not None:
        new_cache = {"groups": new_groups, "tail": new_tail}

    table = params.get("unembed", params["embed"])
    if logits == "hidden":
        out = x
    elif logits == "all":
        out = unembed(x, table, cfg.final_softcap)
    else:
        out = unembed(x[:, -1], table, cfg.final_softcap)
    return out, new_cache, aux


# ======================================================================
# losses


def train_loss(cfg: ModelConfig, params: Params, tokens, targets, mask,
               *, prefix_embeds=None, enc_embeds=None, remat: bool = True,
               flash=None, ce_chunk: int = 512, lb_coeff: float = 0.01):
    """Next-token CE with chunked unembedding (avoids (B,S,V) materialization)."""
    enc_out = None
    if cfg.is_encdec:
        enc_out = encode(cfg, params, enc_embeds, flash=flash)
    hidden, _, aux = forward(cfg, params, tokens, cache=None, pos=None,
                             prefix_embeds=prefix_embeds, enc_out=enc_out,
                             logits="hidden", flash=flash, remat=remat)
    if cfg.input_mode == "mixed" and prefix_embeds is not None:
        hidden = hidden[:, prefix_embeds.shape[1]:]

    table = params.get("unembed", params["embed"])
    B, S, D = hidden.shape
    c = ce_chunk
    while S % c:
        c -= 1
    nchunk = S // c

    @jax.checkpoint
    def chunk_loss(idx):
        # rematted: the (B, c, V) logits would otherwise be stored as AD
        # residuals for every chunk — 67GB/chip at gemma2's 256k vocab.
        h = lax.dynamic_slice_in_dim(hidden, idx * c, c, axis=1)
        t = lax.dynamic_slice_in_dim(targets, idx * c, c, axis=1)
        m = lax.dynamic_slice_in_dim(mask, idx * c, c, axis=1)
        logits = unembed(h, table, cfg.final_softcap)        # (B,c,V) f32
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
        nll = (lse - picked) * m
        return nll.sum(), m.sum()

    nlls, counts = lax.map(chunk_loss, jnp.arange(nchunk))
    loss = nlls.sum() / jnp.maximum(counts.sum(), 1.0)
    if cfg.is_moe:
        loss = loss + lb_coeff * aux["lb_loss"]
    return loss, aux
