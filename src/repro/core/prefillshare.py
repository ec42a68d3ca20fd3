"""PrefillShare core (paper §3): factorization + cache-conditioned fine-tuning.

The model is factorized into
  - a *base prefill module* ``θ_base`` (frozen): processes the shared prompt X
    once, producing the shared sequence state ``C_base`` (KV cache for
    attention archs, SSD/RG-LRU state for SSM/hybrid archs — DESIGN.md §4);
  - N *task-specific decode modules* ``θ_dec``: generate conditioned on
    ``C_base``.

Cache-conditioned fine-tuning (Eq. 7):
    L(θ_dec) = −Σ_t log P(y_t | y_<t, stop_grad(C_base); θ_dec)
Teacher forcing over the target, with the prompt's cache produced by the
frozen base model. Because every decode module is trained against the *same*
frozen prefill parameterization, their caches are mutually compatible and the
prefill stage + cache can be shared across models at serving time.

``share_ratio`` implements the paper's Fig. 2 knob: the fraction of layers
whose prompt cache comes from the base model (the rest come from the decode
model's own prefill). ratio=1.0 is the PrefillShare operating point;
sweeping it against a normally-fine-tuned model reproduces the collapse curve.
"""
from __future__ import annotations

import hashlib
import json
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encode, forward, init_cache
from repro.models.model import train_loss as _plain_train_loss
from repro.runtime import donating_jit

Params = Any
Cache = Any


# ======================================================================
# Base prefill module


def base_prefill(cfg: ModelConfig, base_params: Params, tokens, *, cache_len: int,
                 pos=None, cache: Optional[Cache] = None, prefix_embeds=None,
                 enc_embeds=None, stop_grad: bool = True, flash=None):
    """Run the (frozen) base prefill module; returns (last_logits, C_base).

    Supports PARTIAL prefill: pass an existing ``cache`` + ``pos`` to extend it
    with newly appended tokens only (paper §3.3 step 1).
    """
    B = tokens.shape[0]
    enc_out = None
    if cfg.is_encdec and enc_embeds is not None:
        enc_out = encode(cfg, base_params, enc_embeds, flash=flash)
    if cache is None:
        enc_len = enc_embeds.shape[1] if enc_embeds is not None else 0
        cache = init_cache(cfg, B, cache_len, enc_len=enc_len)
    if pos is None:
        pos = jnp.zeros((B,), jnp.int32)
    out, cache, _ = forward(cfg, base_params, tokens, cache=cache, pos=pos,
                            prefix_embeds=prefix_embeds, enc_out=enc_out,
                            flash=flash)
    if stop_grad:
        cache = jax.lax.stop_gradient(cache)
    return out, cache


def base_prefill_paged(cfg: ModelConfig, base_params: Params, new_tokens, *,
                       pool, block_table, n_cached: int, flash=None):
    """Partial prefill against the paged data plane (§3.3 step 1, for real).

    The cached prefix (``n_cached`` tokens, page-aligned by construction —
    the prefix index matches whole blocks) is gathered out of ``pool`` via
    ``block_table`` into a dense working cache; the frozen base model runs
    over ``new_tokens`` only; the freshly produced KV rows are scattered back
    into the pool's physical pages with the ``paged_write`` kernel. Returns
    the last-token logits. B=1 (one request per call).

    Mixed-provenance contract: the cached pages may be prefill-published OR
    relay-published (decode-written by a finished sequence whose KV path
    equals the base module's — ``engine._relay_compatible`` gates
    publication). Both hold position p's KV for the token INPUT at p, bit-
    identical to what this function would itself have written, so the
    gather treats them uniformly; no provenance plumbing reaches here.
    """
    assert n_cached % pool.page_size == 0, "prefix reuse is page-granular"
    cache = pool.gather_prefill_cache(block_table, n_cached)
    out, cache = base_prefill(
        cfg, base_params, new_tokens,
        cache_len=len(block_table) * pool.page_size, cache=cache,
        pos=jnp.array([n_cached], jnp.int32), flash=flash)
    start = n_cached // pool.page_size
    pool.scatter_from_dense(cache, block_table, start,
                            len(block_table) - start)
    return out


def paged_forward(cfg: ModelConfig, params, tokens, state, block_tables, pos,
                  **kw):
    """``forward`` over the paged pool (traceable: call it inside a jitted
    step). ``state`` is a ``decode_state`` pytree, ``block_tables`` (B,
    npages) int32 and ``pos`` (B,) the absolute position of ``tokens[:, 0]``.
    Each layer appends the tokens' K/V rows to their pages and attends
    straight from the pool. Returns (output, new_state)."""
    bt = jnp.asarray(block_tables, jnp.int32)

    def wire(st):
        return {"k_pages": st["k"], "v_pages": st["v"], "block_tables": bt}

    cache = {"groups": {g: wire(st) for g, st in state["groups"].items()},
             "tail": [wire(st) for st in state["tail"]]}
    out, cache, _ = forward(cfg, params, tokens, cache=cache, pos=pos, **kw)

    def unwire(c):
        return {"k": c["k_pages"], "v": c["v_pages"]}

    return out, {"groups": {g: unwire(c) for g, c in cache["groups"].items()},
                 "tail": [unwire(c) for c in cache["tail"]]}


#: one jitted chunk step per config. It retraces per distinct (B, S, npages)
#: shape (``repro.runtime.PROGRAM_TRACES["chunk_step"]`` counts them): with
#: the scheduler's power-of-two table bucketing, npages contributes O(log
#: pages) retraces instead of one per page of prefix growth.
_CHUNK_STEPS: dict = {}


def _make_chunk_step(cfg: ModelConfig):
    def _step(params, toks, pos, state, block_tables):
        _, new_state = paged_forward(cfg, params, toks, state, block_tables,
                                     pos, logits="hidden")
        return new_state
    # the pool (arg 3) is donated on TPU: each chunk appends its rows in
    # place instead of allocating a second pool
    return donating_jit(_step, (3,), name="chunk_step")


def base_prefill_chunk(cfg: ModelConfig, base_params: Params, tokens, *,
                       pool, block_tables, pos):
    """One chunked-prefill step against the paged plane (the scheduler's
    prefill primitive).

    Unlike ``base_prefill_paged`` there is NO dense gather of the prefix:
    inside one jitted forward, each layer scatters the chunk's fresh K/V
    rows into their pool pages and the chunk queries attend to prefix+self
    straight from the pages (``flash_prefill_paged`` on TPU, the jnp gather
    twin elsewhere). The prefix pages obey the same mixed-provenance
    contract as ``base_prefill_paged``: prefill-published and
    relay-published (decode-written) pages are indistinguishable here.
    Batches chunks from several requests: ``tokens`` (B, S) int32, ``pos``
    (B,) absolute start positions, ``block_tables`` (B, npages) zero-padded
    to a common width. Chunk start positions and
    the cached-prefix boundary may land mid-page. Returns the updated-page
    pytree (already absorbed into ``pool``) for completion sync.
    """
    if cfg not in _CHUNK_STEPS:
        _CHUNK_STEPS[cfg] = _make_chunk_step(cfg)
    new_state = _CHUNK_STEPS[cfg](
        base_params, jnp.asarray(tokens, jnp.int32),
        jnp.asarray(pos, jnp.int32), pool.decode_state(),
        jnp.asarray(block_tables, jnp.int32))
    pool.absorb_decode_state(new_state)
    return new_state


# ======================================================================
# Share-ratio mixing (Fig. 2 mechanism)


def _layer_share_mask(cfg: ModelConfig, ratio: float):
    """Boolean per layer: True = use the base model's cache for this layer.

    The first ``round(ratio * n_layers)`` layers share (bottom-up, matching
    the paper's progressive-sharing sweep)."""
    n = cfg.n_layers
    k = int(round(ratio * n))
    return [i < k for i in range(n)]


def mix_caches(cfg: ModelConfig, cache_base: Cache, cache_self: Cache,
               ratio: float) -> Cache:
    """Per-layer blend: layers under the share mask take the base cache."""
    if ratio >= 1.0:
        return cache_base
    if ratio <= 0.0:
        return cache_self
    mask = _layer_share_mask(cfg, ratio)
    pat = cfg.layer_pattern
    n_full = cfg.n_layers // len(pat)

    def pick(path_mask_stacked, b, s):
        # b, s: stacked leaves (n_full, ...); path_mask_stacked: (n_full,) bools
        sel = jnp.asarray(path_mask_stacked)
        shape = (n_full,) + (1,) * (b.ndim - 1)
        return jnp.where(sel.reshape(shape), b, s)

    mixed_groups = {}
    for i in range(len(pat)):
        layer_ids = [g * len(pat) + i for g in range(n_full)]
        m = [mask[j] for j in layer_ids]
        bg = cache_base["groups"][f"pos{i}"]
        sg = cache_self["groups"][f"pos{i}"]
        mixed_groups[f"pos{i}"] = jax.tree.map(lambda b, s: pick(m, b, s), bg, sg)
    mixed_tail = []
    for t, (bt, st) in enumerate(zip(cache_base["tail"], cache_self["tail"])):
        lid = n_full * len(pat) + t
        mixed_tail.append(bt if mask[lid] else st)
    return {"groups": mixed_groups, "tail": mixed_tail}


# ======================================================================
# Cache-conditioned fine-tuning loss (Eq. 7)


def cache_conditioned_loss(cfg: ModelConfig, dec_params: Params,
                           base_params: Params, prompt, target_in, target_out,
                           target_mask, *, share_ratio: float = 1.0,
                           prefix_embeds=None, enc_embeds=None, remat: bool = False,
                           flash=None, ce_chunk: int = 512):
    """−Σ log P(y_t | y_<t, C_base; θ_dec), gradients only through θ_dec.

    prompt: (B, Sp) shared-context tokens; target_in/out: (B, St) teacher-forced
    decoder input and next-token labels; target_mask: (B, St).
    ``share_ratio < 1`` mixes in the decode model's own prompt cache (used to
    train/eval intermediate sharing points for Fig. 2).
    """
    B, Sp = prompt.shape
    St = target_in.shape[1]
    npfx = prefix_embeds.shape[1] if prefix_embeds is not None else 0
    cache_len = Sp + npfx + St

    _, c_base = base_prefill(cfg, base_params, prompt, cache_len=cache_len,
                             prefix_embeds=prefix_embeds, enc_embeds=enc_embeds,
                             stop_grad=True, flash=flash)
    if share_ratio < 1.0:
        _, c_self = base_prefill(cfg, dec_params, prompt, cache_len=cache_len,
                                 prefix_embeds=prefix_embeds,
                                 enc_embeds=enc_embeds, stop_grad=False,
                                 flash=flash)
        cache = mix_caches(cfg, c_base, c_self, share_ratio)
    else:
        cache = c_base

    pos = jnp.full((B,), Sp + npfx, jnp.int32)
    hidden, _, aux = forward(cfg, dec_params, target_in, cache=cache, pos=pos,
                             logits="hidden", flash=flash, remat=remat)

    table = dec_params.get("unembed", dec_params["embed"])
    from repro.models.layers import unembed
    logits = unembed(hidden, table, cfg.final_softcap)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, target_out[..., None], axis=-1)[..., 0]
    nll = (lse - picked) * target_mask
    loss = nll.sum() / jnp.maximum(target_mask.sum(), 1.0)
    if cfg.is_moe:
        loss = loss + 0.01 * aux["lb_loss"]
    return loss, aux


def full_ft_loss(cfg: ModelConfig, params: Params, prompt, target_in, target_out,
                 target_mask, **kw):
    """Baseline: standard full fine-tuning (self-generated cache implicitly).

    Implemented as a plain next-token loss over [prompt; target] with the loss
    masked to the target segment — the conventional setup the paper compares
    against."""
    tokens = jnp.concatenate([prompt, target_in], axis=1)
    pmask = jnp.zeros_like(prompt, dtype=jnp.float32)
    # next-token targets: shift left; prompt positions masked out except the
    # boundary token which predicts target_in[0] -> included via target side
    tgt = jnp.concatenate([prompt[:, 1:], target_in[:, :1], target_out], axis=1)
    mask = jnp.concatenate([pmask, target_mask], axis=1)
    return _plain_train_loss(cfg, params, tokens, tgt, mask, remat=False,
                             prefix_embeds=kw.get("prefix_embeds"),
                             enc_embeds=kw.get("enc_embeds"))


# ======================================================================
# Cache compatibility schema (handoff contract)


@dataclass(frozen=True)
class CacheSchema:
    """Identity of a shared cache: which frozen base produced it, over what."""
    base_model_id: str       # id of θ_base (hash of config + param fingerprint)
    arch: str
    n_layers: int
    cache_len: int
    dtype: str

    def compatible_with(self, other: "CacheSchema") -> bool:
        return (self.base_model_id == other.base_model_id
                and self.arch == other.arch
                and self.n_layers == other.n_layers
                and self.dtype == other.dtype)


def model_fingerprint(cfg: ModelConfig, params: Params) -> str:
    """Cheap, deterministic parameter fingerprint (sum/norm of a few leaves)."""
    leaves = jax.tree.leaves(params)
    probe = [float(jnp.sum(leaf).astype(jnp.float32)) for leaf in leaves[:4]]
    blob = json.dumps({"cfg": cfg.name, "n": len(leaves), "probe": probe})
    return hashlib.sha1(blob.encode()).hexdigest()[:16]


def cache_schema(cfg: ModelConfig, base_params: Params, cache_len: int) -> CacheSchema:
    return CacheSchema(
        base_model_id=model_fingerprint(cfg, base_params),
        arch=cfg.name, n_layers=cfg.n_layers, cache_len=cache_len,
        dtype=cfg.dtype)
