"""Per-sequence token sampling for the decode planes (pure jnp, jit-safe).

The serving API's ``SamplingParams`` are executed INSIDE the jitted decode
step (fused plane and per-model loop alike), so sampling costs no
extra dispatch. Two properties the tests pin:

  - ``temperature == 0`` is EXACTLY ``jnp.argmax(logits, -1)`` — the
    pre-redesign greedy path, bit-identical in both decode modes. The greedy
    branch is computed on the raw logits and selected with ``jnp.where``, so
    adding sampling to the step cannot perturb greedy outputs.
  - sampled streams are reproducible REGARDLESS of batch packing: the PRNG
    key for the token generated at absolute position ``p`` of a request with
    seed ``s`` is ``fold_in(PRNGKey(s), p)`` — a pure function of the
    request, never of which other sequences share the batch, which lane the
    sequence landed in, or how wide the step's padding is.

Filtering follows the usual order: top-k mask, then nucleus (top-p) mask
over the surviving distribution's sorted tail, then temperature scaling and
a categorical draw. ``top_k <= 0`` and ``top_p >= 1`` disable their filters;
the most-probable token is always kept, so the filtered distribution can
never become empty.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def fold_keys(seeds, positions):
    """Per-sequence PRNG keys from (seed, position) pairs.

    ``seeds``/``positions``: (B,) int32. The fold chain depends only on the
    request's own seed and the absolute position of the token being sampled,
    so a request's random stream is invariant to batch composition.
    """

    def one(seed, pos):
        return jax.random.fold_in(jax.random.PRNGKey(seed), pos)

    return jax.vmap(one)(seeds, positions)


def sample_logits(logits, temperature, top_k, top_p, keys):
    """Sample one token per row; greedy rows (temperature <= 0) are exact
    argmax over the RAW logits.

    logits: (B, V); temperature/top_p: (B,) float32; top_k: (B,) int32;
    keys: (B, 2) uint32 (from ``fold_keys``). Returns (B,) int32.
    """
    greedy = jnp.argmax(logits, -1).astype(jnp.int32)
    V = logits.shape[-1]
    neg = jnp.finfo(jnp.float32).min
    # top-k first: rank every vocab id, mask those beyond the k-th
    sort_idx = jnp.argsort(-logits, axis=-1)
    ranks = jnp.argsort(sort_idx, axis=-1)          # rank of each vocab id
    k = jnp.where(top_k > 0, jnp.minimum(top_k, V), V)
    fk = jnp.where(ranks < k[:, None], logits.astype(jnp.float32), neg)
    # nucleus over the SURVIVING (top-k-renormalized) distribution: sort the
    # filtered logits and keep a sorted entry while the renormalized mass
    # STRICTLY BEFORE it is < top_p (rank 0 always survives: its exclusive
    # mass is 0; masked entries carry probability 0)
    fk_idx = jnp.argsort(-fk, axis=-1)
    fk_ranks = jnp.argsort(fk_idx, axis=-1)
    probs = jax.nn.softmax(jnp.take_along_axis(fk, fk_idx, axis=-1), axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_p = jnp.take_along_axis((cum - probs) < top_p[:, None], fk_ranks,
                                 axis=-1)
    filtered = jnp.where(keep_p, fk, neg)
    scaled = filtered / jnp.maximum(temperature, 1e-6)[:, None]
    sampled = jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


def sample_step(logits, positions, temperature, top_k, top_p, seeds):
    """Convenience wrapper used by the decode steps: fold the per-sequence
    keys from (seed, position) and sample. All args are (B,)-aligned with
    ``logits`` rows; traceable inside jit."""
    keys = fold_keys(seeds, positions)
    return sample_logits(logits, temperature, top_k, top_p, keys)
