"""The plain reference: the model's forward pass in float32, layer by layer.

It imports nothing of the program. The weights are remade from the run seed
by the family's ``init_module``, the same function that made the weights the
program served. The prompt's keys and values come from the prefill (base)
module; the served positions run through the decode module that served
them, attending to the base module's prompt rows and to their own, which is
PrefillShare's cache-conditioned decode. Each matrix product runs at
``precision=HIGHEST``.

A family's ``served_logits`` (``bench/families/<model_type>.py``) runs its
own layers over the helpers here: matrix products, RMSNorm, rope, causal GQA
attention, the embedding and the output head.

Departure from the published models, taken because the program takes it:
token embeddings enter scaled by ``sqrt(hidden_size)``.

``control=True`` is the same computation one precision step below the
program's bf16: every matrix product's operands, and the cached keys and
values, pass through float8 (e4m3) with a scale per row or column.
"""
from __future__ import annotations

import gc
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import family

HIGHEST = jax.lax.Precision.HIGHEST
QBLOCK = 128                 # query rows per attention block
PROMPT_PAD = 1024            # prompt rows are padded to a multiple of this
REPLY_PAD = 128
FP8_MAX = 448.0


def _q8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, w, control):
    """a (S, in) @ w (in, out) in float32."""
    w = w.astype(jnp.float32)
    if control:
        a, w = _q8(a, -1), _q8(w, 0)
    return jnp.matmul(a, w, precision=HIGHEST)


def _rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w.astype(jnp.float32))


def _rope(x, pos, theta):
    """x (S, H, D); rotate the two halves of each head (NeoX style)."""
    half = x.shape[-1] // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, qpos, kpos):
    """Causal GQA: q (S, Hq, D) at qpos; k/v (T, Hkv, D) at kpos (-1 marks
    a padded row). Runs in blocks of query rows."""
    S, hq, D = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qb = q.reshape(S // QBLOCK, QBLOCK, hkv, g, D) * D ** -0.5
    pb = qpos.reshape(S // QBLOCK, QBLOCK)

    def block(args):
        qq, pp = args
        s = jnp.einsum("qhgd,khd->hgqk", qq, k, precision=HIGHEST)
        ok = (kpos[None, :] <= pp[:, None]) & (kpos[None, :] >= 0)
        s = jnp.where(ok[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, (qb, pb))
    return o.reshape(S, hq * D)


@partial(jax.jit, static_argnums=(0,))
def _embed(s, table, tokens):
    return table[tokens].astype(jnp.float32) * jnp.float32(s.d ** 0.5)


@partial(jax.jit, static_argnums=(0, 1))
def _logits(s, control: bool, x, norm, table):
    x = _rms(x, norm, s.eps)
    t = table.astype(jnp.float32)
    if control:
        x, t = _q8(x, -1), _q8(t, -1)
    return jnp.matmul(x, t.T, precision=HIGHEST)


def _layer_weights(params, layer):
    return jax.tree.map(lambda a: a[layer], params["groups"]["pos0"])


def _pad(n, m):
    return -(-n // m) * m


@jax.jit
def _gaps(ref, served, ctrl):
    """Per position: how far the served token's reference logit, and the
    control's first token's, lie below the reference's best."""
    best = ref.max(-1)
    got = jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    alt = jnp.take_along_axis(ref, ctrl.argmax(-1)[:, None], -1)[:, 0]
    return best - got, best - alt


def logit_gaps(conf: dict, seed: int, sample: list, *, control=False):
    """For each sampled request (dict with module, prompt, served,
    first_token): the gap of every served token below the float32
    reference's best, and with ``control`` the gap of the token that the
    float8 computation puts first. Weights are remade one decode module at a
    time beside the base module."""
    fam = family(conf)
    s = fam.shapes(conf)
    prefill = conf["deployment"]["prefill_module"]
    out = [None] * len(sample)
    with jax.default_matmul_precision("highest"):
        base = fam.init_module(conf, seed, prefill)
        for module in sorted({r["module"] for r in sample}):
            dec = fam.init_module(conf, seed, module)
            for i, r in enumerate(sample):
                if r["module"] != module:
                    continue
                args = (s, base, dec, r["prompt"], r["served"],
                        r["first_token"])
                ref = fam.served_logits(*args)
                ctrl = (fam.served_logits(*args, control=True) if control
                        else ref)
                g, c = _gaps(ref, jnp.asarray(r["served"], jnp.int32), ctrl)
                out[i] = {"gap": np.asarray(g),
                          "control_gap": np.asarray(c) if control else None}
                del ref, ctrl
            del dec
            gc.collect()
        del base
        gc.collect()
    return out
