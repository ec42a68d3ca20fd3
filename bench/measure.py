"""Arithmetic the metric readers share: tails, and the served work and device
time of a traced window."""
from __future__ import annotations

import numpy as np

from bench import flops, tracefile

#: a step program is told apart by the kernel that runs inside it
STEP_KERNELS = {"decode": "paged_decode", "chunk": "flash_prefill_paged"}


def p95(values) -> float | None:
    values = list(values)
    return float(np.percentile(values, 95)) if values else None


def end_of(run, r) -> float:
    """A request's last token, or the drain's end where it never finished:
    a request that fails counts at least that late."""
    return r.token_times[-1] if r.finished and r.token_times else run.drain_end


def first_of(run, r) -> float:
    return r.token_times[0] if r.token_times else run.drain_end


def decode_lengths(run, t0: float, t1: float) -> list:
    """Keys attended by every token pushed in [t0, t1): token i of a request
    is computed at position prompt_len + i, over that many keys plus its own."""
    out = []
    for r in run.requests:
        for i, t in enumerate(r.token_times):
            if t0 <= t < t1:
                out.append(r.prompt_len + i + 1)
    return out


def prefill_chunks(run, t0: float, t1: float) -> list:
    """(start, size) of every prefill chunk that finished in [t0, t1)."""
    return [(done - n, n) for r in run.requests for t, n, done in r.chunks
            if t0 <= t < t1]


def window_perf(run):
    """The traced window in perf_counter seconds."""
    tv = run.trace
    return tv.perf_open, tv.perf_open + (tv.t1_ns - tv.t0_ns) / 1e9


def kernel_roofline(run, kernel: str, cost) -> float | None:
    """Share (%) of ``kernel``'s device time that the chip's peak would need
    for the work served: max(flops / peak, bytes / bandwidth) / time."""
    tv = run.trace
    if tv is None or run.peaks is None:
        return None
    ns = tracefile.kernel_ns(tv.rec, kernel, tv.t0_ns, tv.t1_ns)
    f, b = cost
    if ns <= 0 or f <= 0:
        return None
    least = max(f / run.peaks["bf16_flops"], b / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ns / 1e9)


def step_mfu(run, kind: str, model_flops: int) -> float | None:
    """Share (%) of the chip's bf16 peak that the model operations of the
    served work take over the device time of ``kind`` step programs."""
    tv = run.trace
    if tv is None or run.peaks is None or model_flops <= 0:
        return None
    ns = tracefile.program_ns(tv.rec, STEP_KERNELS, kind, tv.t0_ns, tv.t1_ns)
    if ns <= 0:
        return None
    return 100.0 * model_flops / (ns / 1e9) / run.peaks["bf16_flops"]


def decode_cost(run):
    a, b = window_perf(run)
    lengths = decode_lengths(run, a, b)
    f, by = flops.paged_decode(run.shapes, lengths)
    return f * run.shapes.layers, by * run.shapes.layers


def prefill_cost(run):
    a, b = window_perf(run)
    chunks = prefill_chunks(run, a, b)
    f, by = flops.flash_prefill_paged(run.shapes, chunks)
    return f * run.shapes.layers, by * run.shapes.layers
