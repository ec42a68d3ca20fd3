"""Operations and bytes that one attention layer's served work needs, from
the shapes and the token positions of the traffic alone, whatever
implements it. A family (``bench/families/<model_type>.py``) sums them over
its layers and adds its own projections, MLP or experts and output head.

A multiply-add counts as two operations. Bytes are bf16 (2 bytes) and count
what must cross HBM at least once: the keys and values a call reads, its
queries and outputs. Causal attention counts only the keys a query may see.
"""
from __future__ import annotations

BF16 = 2


def _attn_flops(s, keys: int) -> int:
    """One query row of one layer against ``keys`` keys: q.k and p.v."""
    return 4 * s.hq * s.head_dim * keys


def paged_decode(s, lengths) -> tuple[int, int]:
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


def flash_prefill_paged(s, chunks) -> tuple[int, int]:
    """One layer's ``flash_prefill_paged`` call over ``chunks`` of
    (start, size): each chunk's queries attend to every key up to their own
    position, read once per chunk."""
    flops = sum(_attn_flops(s, _chunk_keys(st, n)) for st, n in chunks)
    bytes_ = sum(2 * (st + n) * s.kv_width * BF16 + 2 * n * s.q_width * BF16
                 for st, n in chunks)
    return flops, bytes_
