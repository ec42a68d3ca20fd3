"""Beyond-paper: LoRA decode modules under cache-conditioned fine-tuning.

The paper fine-tunes FULL decode modules (N × full model storage on the
decode pool). A natural extension: keep the decode module = frozen base +
low-rank adapters, trained with the SAME cache-conditioned objective (Eq. 7).
If it holds accuracy, the decode pool stores ONE base copy + N tiny adapter
sets — compounding the paper's memory argument (Eq. 9) on the weight side the
way PrefillShare already compounds it on the KV side.

Adapter trees mirror the base param tree: every targeted weight position
holds a ``LoRAPair`` (a NamedTuple, hence a proper pytree node — gradients
and optimizers traverse it transparently), every other position holds None.
The pair is a DEDICATED type, not a bare ``{"A", "B"}`` dict: classification
happens by position (a base LEAF pairs with whatever subtree the adapter
tree holds there) and by ``isinstance``, so a real param subtree that merely
happens to have keys A/B can never be mistaken for an adapter
(tests/test_lora.py::test_real_param_subtree_named_a_b_is_not_an_adapter).

Serving has two ways to consume adapters:
  - ``lora_apply`` materializes ``W_eff = W + (alpha/r)·(A @ B)`` once (the
    legacy per-model decode path);
  - the fused decode plane stacks just the (tiny) A/B factors
    (``stack_lora_params``) and performs the same merge INSIDE its jitted
    step (serving/decode.py), so N adapter-factored decode modules store
    one base copy + N adapter sets instead of N full models.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

DEFAULT_TARGETS = ("wq", "wv", "wo")


class LoRAPair(NamedTuple):
    """One adapter: ``delta = scale * A @ B``. NamedTuple => pytree node."""
    A: Any
    B: Any


def _is_target(path, targets) -> bool:
    leafname = str(getattr(path[-1], "key", path[-1]))
    return leafname in targets


def lora_init(key, base_params, *, rank: int = 8,
              targets=DEFAULT_TARGETS) -> Any:
    """A/B pairs (A ~ N(0, 1/r), B = 0) for every targeted 2D+ weight."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(base_params)
    out = []
    for i, (path, leaf) in enumerate(flat):
        if hasattr(leaf, "ndim") and leaf.ndim >= 2 and _is_target(path, targets):
            *batch, m, n = leaf.shape
            ka = jax.random.fold_in(key, i)
            a = jax.random.normal(ka, (*batch, m, rank), jnp.float32) / rank
            b = jnp.zeros((*batch, rank, n), jnp.float32)
            out.append(LoRAPair(a.astype(leaf.dtype), b.astype(leaf.dtype)))
        else:
            out.append(None)
    return jax.tree_util.tree_unflatten(treedef, out)


def _pair(ab):
    """View ``ab`` as an adapter pair, else None. LoRAPair is the canonical
    type; a bare two-key {"A", "B"} dict is still accepted here — at a base
    LEAF position it is unambiguous (the base tree was already flattened, so
    no base subtree can be swallowed by the check)."""
    if isinstance(ab, LoRAPair):
        return ab
    if isinstance(ab, dict) and set(ab) == {"A", "B"}:
        return LoRAPair(ab["A"], ab["B"])
    return None


def lora_delta(ab: LoRAPair, scale: float):
    """The low-rank update ``scale * A @ B`` in float32."""
    return jnp.einsum("...mr,...rn->...mn", ab.A.astype(jnp.float32),
                      ab.B.astype(jnp.float32)) * scale


def lora_apply(base_params, lora_params, *, alpha: float = 16.0,
               rank: int = 8):
    """Materialize effective params: W + (alpha/rank) * A @ B.

    Adapter classification is positional: the merge pairs each base LEAF
    with the adapter tree's subtree at the same position, and only a
    ``LoRAPair`` there is treated as an adapter (None and any real param
    structure pass through untouched). No ``is_leaf`` key-sniffing — the old
    ``set(x) == {"A", "B"}`` heuristic could misclassify a genuine base
    param subtree with those key names and crash (or silently corrupt) the
    merge."""
    scale = alpha / rank

    def merge(w, ab):
        pair = _pair(ab)
        if pair is None:
            return w
        return (w.astype(jnp.float32) + lora_delta(pair, scale)).astype(w.dtype)

    # flatten_up_to semantics: base leaves drive; the adapter tree's whole
    # subtree at each base-leaf position (LoRAPair or None) reaches merge.
    leaves, treedef = jax.tree_util.tree_flatten(base_params)
    ab_subtrees = treedef.flatten_up_to(lora_params)
    return jax.tree_util.tree_unflatten(
        treedef, [merge(w, ab) for w, ab in zip(leaves, ab_subtrees)])


def lora_param_count(lora_params) -> int:
    return sum(x.size for x in jax.tree.leaves(lora_params))


def stack_lora_params(lora_list):
    """Stack LoRA adapter pytrees (None where untargeted) on a NEW leading
    model axis.

    The fused plane's layout for adapter-only decoders: stack just the
    (tiny) A/B factors and merge ``W + scale * A[m] @ B[m]`` inside the
    jitted step, instead of holding N full materialized models. None
    positions (untargeted weights) are empty pytree nodes and survive as-is;
    adapter-target mismatches between the stacked models surface as a tree
    structure error."""
    assert lora_list, "need at least one adapter pytree to stack"
    try:
        return jax.tree.map(lambda *xs: jnp.stack(xs), *lora_list)
    except ValueError as e:
        raise ValueError(
            f"cannot stack adapters: targeted-weight sets differ across the "
            f"{len(lora_list)} models ({e})") from e


def cache_conditioned_lora_loss(cfg, lora_params, base_params, prompt,
                                target_in, target_out, target_mask, *,
                                alpha: float = 16.0, rank: int = 8,
                                share_ratio: float = 1.0, **kw):
    """Eq. 7 with θ_dec = θ_base + LoRA; gradients flow ONLY to the adapters
    (θ_base enters both the frozen prefill and the decode trunk, but is a
    constant w.r.t. the optimizer)."""
    from repro.core.prefillshare import cache_conditioned_loss
    dec = lora_apply(jax.lax.stop_gradient(base_params), lora_params,
                     alpha=alpha, rank=rank)
    return cache_conditioned_loss(cfg, dec, base_params, prompt, target_in,
                                  target_out, target_mask,
                                  share_ratio=share_ratio, **kw)
