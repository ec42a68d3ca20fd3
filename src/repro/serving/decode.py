"""Fused cross-model decode plane: every decode module, ONE jitted step.

The paper's decode pool hosts many task-specific modules over one shared
prefill KV pool — but a per-model dispatch loop pays one jitted forward (and
one retrace key) per decode model per engine step. Every decode module
sharing a ``ModelConfig`` is structurally identical (full fine-tunes and
LoRA merges alike), so one jitted step advances EVERY active sequence of
EVERY model: one model lane per module, each lane a forward over that
module's own weights and its own rows of the batch.

Weight layout per group (``DecodeModelSpec.group_key``):
  - FULL specs keep each module's own param pytree (the registry's arrays,
    never restacked: a stacked copy would hold every decoder's weights
    twice on the device).
  - LORA specs stack ONLY the low-rank A/B factors (``stack_lora_params``);
    the frozen base weights enter the step once, and each lane merges
    ``W + scale * A[m] @ B[m]`` inside the jitted step right before its
    forward — the decode plane stores one base copy + M adapter sets
    instead of M materialized full models (Eq. 9 on the weight side), and
    the merge is asserted bit-identical to pre-merged ``lora_apply``
    decoders (tests/test_registry.py).

Layout per step (``StackedDecoders.step``):
  - sequences are bucketed per model into an (M, Bmax) grid, padded with fake
    rows whose block tables point at the sentinel page 0 (never allocated, so
    their garbage writes cannot alias live KV). M is the group's CURRENT
    model count: the registry (serving/registry.py) rebuilds the plane at
    step boundaries on churn, and every sequence's lane index is re-derived
    from its model id per step, so hot (un)registration remaps lanes without
    touching any live sequence's pages;
  - block-table width is bucketed to the next power of two, so jit retraces
    stop scaling with prompt length (growth by one page within a bucket
    reuses the trace);
  - the pool's page buffers enter the jitted step as ONE donated-on-TPU
    pytree (``PagedKVPool.decode_state``) and thread through the lanes one
    after another: each lane's forward carries the pool through its layer
    scan and writes its sequences' fresh rows in place. Pages are private
    per sequence, so a lane never reads another lane's rows, and no lane
    holds a copy of the pool.

Per-request SamplingParams execute INSIDE the fused step (serving/
sampling.py): each real sequence's logits row is gathered out of its lane
and sampled with a PRNG key folded from (seed, position) — batch-packing-
invariant — while temperature=0 rows take the exact argmax graph, keeping
greedy outputs asserted identical to the per-model loop
(tests/test_fused_decode.py); the per-model path remains available as
``LocalDisaggEngine(fused=False)`` for comparison.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.lora import lora_apply, stack_lora_params
from repro.core.prefillshare import paged_forward
from repro.runtime import donating_jit
from repro.serving.metrics import PS_DECODE_BUILD, PS_DECODE_READBACK
from repro.serving.sampling import sample_step


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (>= 1): the block-table width bucket."""
    return 1 << max(0, int(n) - 1).bit_length()


def sampling_arrays(seqs):
    """Per-sequence (temperature, top_k, top_p, seed) arrays for a decode
    batch, aligned with ``seqs`` (values, not trace keys — changing a
    request's SamplingParams never retraces the step), plus a host-side
    ``greedy_only`` flag. The flag IS a (binary) trace key: an all-greedy
    batch — the default, and every pre-API workload — dispatches an
    argmax-only step with none of the sampling graph's sort/softmax/draw
    dead weight on the decode hot path."""
    temps = np.asarray([s.params.temperature for s in seqs], np.float32)
    top_ks = np.asarray([s.params.top_k for s in seqs], np.int32)
    top_ps = np.asarray([s.params.top_p for s in seqs], np.float32)
    seeds = np.asarray([s.params.seed for s in seqs], np.int32)
    greedy_only = bool((temps <= 0.0).all())
    return (jnp.asarray(temps), jnp.asarray(top_ks), jnp.asarray(top_ps),
            jnp.asarray(seeds), greedy_only)


def group_specs(specs):
    """Partition ``{model_id: (cfg, DecodeModelSpec)}`` into fusable groups:
    models sharing an identical ModelConfig AND weight-layout bucket
    (``DecodeModelSpec.group_key``: all-full, or all-LoRA of one
    (alpha, rank)) stack into one StackedDecoders lane set; each distinct
    group costs one dispatch per step."""
    groups: dict = {}
    for mid, (cfg, spec) in specs.items():
        groups.setdefault((cfg, spec.group_key()), {})[mid] = spec
    return groups


class StackedDecoders:
    """All decode modules of ONE fusable group, the lanes of one fused step."""

    def __init__(self, cfg, members: dict, kvpool, base_params=None):
        """``members``: {model_id: DecodeModelSpec}, all sharing ``cfg`` and
        one ``group_key``. ``base_params`` (the engine's single frozen copy)
        is required for LoRA groups — it is NOT copied: the stacked storage
        is just the A/B factors. Full groups hold the members' own arrays."""
        assert members, "need at least one decode module"
        self.cfg = cfg
        self.kvpool = kvpool
        self.model_ids = sorted(members)             # stable model-axis order
        self.index = {mid: m for m, mid in enumerate(self.model_ids)}
        specs = [members[mid] for mid in self.model_ids]
        self.lora = specs[0].kind == "lora"
        if self.lora:
            assert base_params is not None, "LoRA group needs the base copy"
            ad = specs[0].lora
            self.alpha, self.rank = ad.alpha, ad.rank
            # one UNBATCHED base copy (shared with the engine — no new
            # arrays) + M stacked adapter sets: the whole per-model storage
            self.stacked = {"base": base_params,
                            "ab": stack_lora_params(
                                [s.lora.params for s in specs])}
        else:
            self.stacked = [s.full for s in specs]
        self.dispatches = 0                          # jitted-step invocations
        # block-table pages the step hands paged_decode (lanes x rows x
        # bucketed width) and the pages its real rows attend to
        self.table_pages = 0
        self.live_pages = 0
        self._step = self._build_step()

    def param_bytes(self) -> int:
        """Bytes of decode weights THIS group stores beyond the engine's
        base copy: M × full-model bytes for full groups; the stacked A/B
        factors only for LoRA groups (the base is aliased, not copied)."""
        tree = self.stacked["ab"] if self.lora else self.stacked
        return sum(x.nbytes for x in jax.tree.leaves(tree))

    # ------------------------------------------------------------------
    def _build_step(self):
        cfg = self.cfg
        n_lanes = len(self.model_ids)
        if self.lora:
            alpha, rank = self.alpha, self.rank

            def lane_params(packed, m):
                # the Eq. 9 weight-side merge, INSIDE the jitted step: the
                # lane's effective weights exist only as an intermediate of
                # this trace, never as M materialized models in the pool
                ab = jax.tree.map(lambda x: x[m], packed["ab"])
                return lora_apply(packed["base"], ab, alpha=alpha, rank=rank)
        else:
            def lane_params(packed, m):
                return packed[m]

        def fused(stacked, state, toks, pos, bts, seq_m, seq_b,
                  temps, top_ks, top_ps, seeds, greedy_only):
            # lanes run one after another over the ONE pool: each writes its
            # sequences' rows in place (fake rows land on sentinel page 0)
            lanes = []
            for m in range(n_lanes):
                logits, state = paged_forward(cfg, lane_params(stacked, m),
                                              toks[m][:, None], state,
                                              bts[m], pos[m])
                lanes.append(logits)
            # per-request sampling, INSIDE the fused step (no extra
            # dispatch): each real sequence's logits row is gathered out of
            # its lane and sampled with a key folded from (seed, position) —
            # batch-packing-invariant; temperature=0 rows are exact argmax
            # (serving/sampling.py), keeping greedy outputs bit-identical.
            # greedy_only is STATIC: an all-greedy batch traces an
            # argmax-only step, paying none of the sampling graph.
            lg = jnp.stack(lanes)[seq_m, seq_b]                     # (N, V)
            if greedy_only:
                nxt = jnp.argmax(lg, -1).astype(jnp.int32)
            else:
                nxt = sample_step(lg, pos[seq_m, seq_b], temps, top_ks,
                                  top_ps, seeds)
            return nxt, state

        # donate the pool buffers (arg 1) on TPU, so the fused step appends
        # KV in place — mirrors kvcache.paged.copy_page
        return donating_jit(fused, (1,), name="decode_step",
                            static_argnums=(11,))

    # ------------------------------------------------------------------
    def step(self, seqs) -> np.ndarray:
        """Advance every sequence (any mix of this group's models) one token
        in ONE jitted forward — sampled per each sequence's SamplingParams
        (greedy when temperature=0); returns next tokens aligned with
        ``seqs``. Tail pages must already cover position ``pos``."""
        with TraceAnnotation(PS_DECODE_BUILD):
            M = len(self.model_ids)
            counts = [0] * M
            coords = []
            for s in seqs:
                m = self.index[s.model_id]
                coords.append((m, counts[m]))
                counts[m] += 1
            bmax = max(counts)
            npages = next_pow2(max(len(s.block_table) for s in seqs))
            page = self.kvpool.page_size
            self.table_pages += M * bmax * npages
            self.live_pages += sum(s.pos // page + 1 for s in seqs)
            toks = np.zeros((M, bmax), np.int32)
            pos = np.zeros((M, bmax), np.int32)
            bts = np.zeros((M, bmax, npages), np.int32)  # pad: sentinel 0
            for s, (m, b) in zip(seqs, coords):
                toks[m, b] = s.next_token
                pos[m, b] = s.pos
                bts[m, b, :len(s.block_table)] = s.block_table
            seq_m = jnp.asarray([m for m, _ in coords], jnp.int32)
            seq_b = jnp.asarray([b for _, b in coords], jnp.int32)
            args = (jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(bts),
                    seq_m, seq_b, *sampling_arrays(seqs))
        nxt, new_state = self._step(self.stacked, self.kvpool.decode_state(),
                                    *args)
        self.kvpool.absorb_decode_state(new_state)
        self.dispatches += 1
        with TraceAnnotation(PS_DECODE_READBACK):
            return np.asarray(nxt)


class FusedDecodePlane:
    """Routes sequences to their group's StackedDecoders: one jitted dispatch
    per engine step per distinct (ModelConfig, weight-layout) group — ONE
    total when every decode module shares the engine's config and layout,
    the paper's setting.

    The plane is an immutable snapshot of the registry's model set: churn
    (hot register/unregister) REPLACES it at a step boundary
    (``LocalDisaggEngine._rebuild_decode_plane``), carrying the dispatch
    counter forward so stats stay cumulative across rebuilds. Its traces
    are counted by program name in ``repro.runtime.PROGRAM_TRACES``."""

    def __init__(self, specs, kvpool, base_params=None, *,
                 dispatches0: int = 0):
        """specs: {model_id: (cfg, DecodeModelSpec)}."""
        self.groups = [StackedDecoders(cfg, members, kvpool, base_params)
                       for (cfg, _k), members in group_specs(specs).items()]
        self._group_of = {mid: g for g in self.groups for mid in g.model_ids}
        self._dispatches0 = dispatches0

    @property
    def model_ids(self) -> list:
        return sorted(self._group_of)

    @property
    def dispatches(self) -> int:
        return self._dispatches0 + sum(g.dispatches for g in self.groups)

    @property
    def table_pages(self) -> int:
        return sum(g.table_pages for g in self.groups)

    @property
    def live_pages(self) -> int:
        return sum(g.live_pages for g in self.groups)

    def param_bytes(self) -> int:
        """Decode-plane weight bytes beyond the engine's single base copy
        (benchmarks/paged_decode_bench.py --adapters reports the N×full vs
        base + N·adapter ratio from exactly this)."""
        return sum(g.param_bytes() for g in self.groups)

    def step(self, seqs) -> np.ndarray:
        """One engine decode step; returns next tokens aligned with seqs."""
        nxt = np.zeros(len(seqs), np.int32)
        for g in self.groups:
            idx = [i for i, s in enumerate(seqs) if self._group_of[s.model_id] is g]
            if idx:
                nxt[idx] = g.step([seqs[i] for i in idx])
        return nxt
