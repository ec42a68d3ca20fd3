"""Operations and bytes that the served work needs, from the config's shapes
and the token positions of the traffic alone, whatever implements it.

A multiply-add counts as two operations. Bytes are bf16 (2 bytes) and count
what must cross HBM at least once: the keys and values a call reads, its
queries and outputs. Causal attention counts only the keys a query may see.
"""
from __future__ import annotations

from bench.model import Shapes

BF16 = 2


def _attn_flops(s: Shapes, keys: int) -> int:
    """One query row of one layer against ``keys`` keys: q.k and p.v."""
    return 4 * s.hq * s.head_dim * keys


def _layer_matmul_flops(s: Shapes) -> int:
    """One token through one layer's projections and MLP."""
    d = s.d
    return 2 * (d * s.q_width + 2 * d * s.kv_width + s.q_width * d
                + 3 * d * s.d_ff)


def paged_decode(s: Shapes, lengths) -> tuple[int, int]:
    """One layer's ``paged_decode`` call over sequences that attend to
    ``lengths`` keys each (the new token's included)."""
    keys = sum(lengths)
    flops = _attn_flops(s, keys)
    qo = 2 * s.q_width * BF16
    bytes_ = 2 * keys * s.kv_width * BF16 + len(lengths) * qo
    return flops, bytes_


def _chunk_keys(start: int, size: int) -> int:
    """Keys seen by the queries at start..start+size-1, causally."""
    return size * start + size * (size + 1) // 2


def flash_prefill_paged(s: Shapes, chunks) -> tuple[int, int]:
    """One layer's ``flash_prefill_paged`` call over ``chunks`` of
    (start, size): each chunk's queries attend to every key up to their own
    position, read once per chunk."""
    flops = sum(_attn_flops(s, _chunk_keys(st, n)) for st, n in chunks)
    bytes_ = sum(2 * (st + n) * s.kv_width * BF16 + 2 * n * s.q_width * BF16
                 for st, n in chunks)
    return flops, bytes_


def decode_step(s: Shapes, lengths) -> int:
    """Model operations of decode steps that produce one token for each of
    ``lengths`` (keys attended, the token's own included): every layer and
    the output head."""
    per_token = s.layers * _layer_matmul_flops(s) + 2 * s.d * s.vocab
    return (len(lengths) * per_token
            + s.layers * sum(_attn_flops(s, n) for n in lengths))


def chunk_step(s: Shapes, chunks) -> int:
    """Model operations of prefill chunks (start, size): every layer, no
    output head (a chunk step returns no logits)."""
    tokens = sum(n for _, n in chunks)
    return s.layers * (tokens * _layer_matmul_flops(s)
                       + sum(_attn_flops(s, _chunk_keys(st, n))
                             for st, n in chunks))
