"""Small-scale REAL-JAX disaggregated engine on a paged KV data plane.

Runs actual models: a pool of prefill workers hosting the frozen base model
(selected per-session by the PrefillRouter), one shared physical
``PagedKVPool`` whose pages back every allocation the per-worker
``CacheManager``s make, and a set of task-specific decode workers that run
CONTINUOUS-BATCH decode over the pool, sampled per-request.

The public surface is the request-centric API (``repro.serving.api``, see
docs/api.md): ``generate(model_id, tokens, SamplingParams(...))`` returns a
streaming ``RequestOutput`` (per-token callbacks/iterator, finish reasons,
TTFT/ITL timestamps), ``shared_context(prefix)`` opens a first-class shared
prefix that many decode models attach to, and ``abort(request)`` cancels at
any lifecycle stage with page refcounts returned to baseline. SamplingParams
execute inside the jitted decode step; temperature=0 (the default) is the
exact historical greedy graph. The legacy ``submit``/``invoke`` surface
survives as a thin DeprecationWarning shim over the same internals.

The decode-model set is a live lifecycle surface (``engine.models``, a
``repro.serving.registry.ModelRegistry``): models hot-(un)register while the
engine serves — new requests validate against the registry immediately
(first-class ``UnknownModelError``), the fused plane relayouts at step
boundaries with live sequences' lanes remapped bit-identically, and
``unregister`` drains or aborts in-flight work per its ``drain`` flag.
LoRA-spec'd models store one base copy + stacked adapter factors, merged
inside the jitted step (serving/decode.py). A construction-time ``decoders``
dict survives as a DeprecationWarning shim that registers each entry.

The run loop is owned by the chunked-prefill scheduler
(``repro.serving.scheduler``): with ``chunked=True`` each step packs one
decode token per active sequence plus as many prefill chunks as fit a
per-step token budget (chunks attend to the cached prefix straight from the
pool pages via ``flash_prefill_paged`` — no dense gather); with the default
eager mode ``submit`` prefills whole prompts synchronously (the historical
behaviour, kept bit-identical) and the scheduler steps decode only.

Automatic prefix caching (default-on): the per-worker radix indexes are ONE
ENGINE-GLOBAL refcounted radix tree over the shared pool's pages, so EVERY
request — no explicit ``SharedContext`` needed — starts its prefill at the
longest prefix ANY worker ever published (system prompts, few-shot headers,
multi-turn history dedup automatically, fleet-wide). The router can price
the expected prefix-hit length alongside backlog (``prefix_aware`` policy),
the chunked scheduler packs cached-history prefills ahead of cold long
prompts (they finish in a chunk or two and reach decode immediately), and
pool evictions notify the tree before a page re-enters the free list, so a
stale prefix is never served. ``prefix_cache=False`` disables all of it for
A/B: outputs are bit-identical either way (reuse only skips recomputation of
identical KV); ``engine.stats()`` rolls the per-worker hit accounting into
one fleet-wide surface.

Data plane (pure global-attention archs, the paper's operating point):
  - prefill: the router picks a worker; its CacheManager matches the longest
    cached prefix (radix, page-granular) and allocates physical pages for the
    tail; ``base_prefill_paged`` gathers the prefix KV out of the pool,
    extends it with the frozen base model, and scatters the fresh rows back
    into the pages via the ``paged_write`` kernel. The allocation is held for
    the whole session (released in ``end_session``), so a live session's
    pages are never evictable.
  - handoff: ZERO-COPY. The decode side receives a block-table reference and
    takes a refcount on every page; a partially-filled tail page is cloned
    first (page-level copy-on-write) so concurrent decoders can append
    privately. ``handoff_bytes`` counts only the block-table metadata.
  - decode: all active sequences (across sessions AND decode models sharing
    this config) advance one token per engine step in ONE fused, jitted
    step with one lane per decode module (serving/decode.py;
    ``fused=False`` restores the per-model dispatch loop), using the paged
    decode-attention step (Pallas kernel on TPU, jnp gather twin elsewhere),
    with generated KV appended to freshly allocated private pages. The pool's
    page buffers are donated into the jitted step on TPU so pages update in
    place. Pages are freed only when the last holder (prefill session or
    decode sequence) releases them.

Relay KV (default-on, ``relay=False`` to A/B): when a sequence FINISHES
decoding, its private decode pages are published into the same engine-global
radix tree, keyed by the full token stream (prompt ⧺ generated tokens) — the
handoff machinery run in reverse. A later request from ANY model whose
prompt extends that stream then starts prefill past the finished sequence's
entire output with a zero-copy block-table reference, extending the paper's
fan-out prefill sharing to sequential agent pipelines (model A's answer is
model B's prompt). Publication is gated on KV-compatibility: only decoders
whose KV path is bit-identical to the frozen base (same full weights, or
differing only in post-KV leaves — the unembed head / final norm) may
publish, so a relayed prefix is always bit-identical to cold prefill.
Full pages are adopted directly; the partial tail was already privatized by
the handoff's page-level CoW, and a still-partial tail at finish is dropped
as before. Aborted sequences never publish.

Archs with non-KV sequence state (SSM/recurrent/hybrid/enc-dec) fall back to
the dense per-session path (``paged=False``), preserving the state-handoff
semantics validated in tests/test_engine_ssm.py.

Prefix-hit accounting comes from the SAME CacheManager bookkeeping the
simulator uses (``Allocation.cached_tokens``), so engine and simulator stats
share one accounting path.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core.prefillshare import (base_prefill, base_prefill_paged,
                                     cache_schema, paged_forward)
from repro.kvcache.blocks import BlockPool, PoolExhausted
from repro.kvcache.handoff import HandoffChannel, transfer_cache
from repro.kvcache.manager import CacheManager, CacheStats
from repro.kvcache.paged import PagedKVPool
from repro.kvcache.radix import NullPrefixIndex, PrefixIndex
from repro.kvcache.sanitize import PoolSanitizer, SanitizedKVPool
from repro.models import forward
from repro.runtime import donating_jit, program_traces
from repro.serving.api import (FINISH_ABORT, FINISH_LENGTH, RequestOutput,
                               SamplingParams, SharedContext)
from repro.serving.autoscale import Autoscaler
from repro.serving.backpressure import ThroughputEWMA
from repro.serving.decode import (FusedDecodePlane, next_pow2,
                                  sampling_arrays)
from repro.serving.metrics import (PS_DECODE_BUILD, PS_DECODE_PUSH,
                                   SPAN_FIRST_TOKEN, SPAN_HANDOFF,
                                   SPAN_ROUTED, SPAN_TOKEN, MetricsRegistry)
from repro.serving.preempt import PreemptConfig, SwapManager
from repro.serving.registry import ModelRegistry, as_spec
from repro.serving.router import PrefillRouter
from repro.serving.sampling import sample_step
from repro.serving.scheduler import (ChunkedScheduler, Request,
                                     SchedulerConfig)


@dataclass
class SessionCache:
    """Dense-path session state (SSM/hybrid/enc-dec fallback)."""
    cache: object
    n_tokens: int
    capacity: int
    alloc: object = None          # held until end_session (residency == refs)


@dataclass
class PagedSession:
    alloc: object                 # CacheManager Allocation, held for lifetime
    block_table: list             # physical page per logical page
    n_tokens: int
    tokens: list                  # context (for sibling-submit fast path)


@dataclass
class DecodeSeq:
    """One in-flight generation: a block-table reference into the shared
    pool (zero-copy handoff) plus private pages for generated tokens."""
    rid: int
    sid: int
    model_id: str
    block_table: list
    shared_blocks: list           # refcounted prefix pages (unref on finish)
    private_blocks: list          # CoW tail + generated pages (drop on finish)
    pos: int                      # tokens currently in the cache
    next_token: int               # token whose KV the next step writes
    remaining: int
    params: SamplingParams = field(default_factory=SamplingParams)
    finish_reason: str | None = None   # set on eos/stop; None -> length
    out: list = field(default_factory=list)
    tokens: list = field(default_factory=list)  # prompt (relay publication
                                                # keys pages by full stream)
    first0: int = 2               # the handoff's first decode input token
    priority: int = 0             # preemption rank (serving/preempt.py):
                                  # lower priorities are victims first


class _CounterField:
    """EngineStats field descriptor backed by a registry ``Counter``: reads
    return ints (legacy ``stats.handoffs == 3`` comparisons keep holding),
    writes (``+= n``, ``= 0``) go straight to the counter cell — so the SAME
    number the old attribute surface exposes is what ``engine.metrics()``
    snapshots and ``render_prometheus()`` exports, with no double
    bookkeeping."""

    __slots__ = ("prom", "help", "name")

    def __init__(self, prom: str, help: str = ""):
        self.prom = prom
        self.help = help

    def __set_name__(self, owner, name):
        self.name = name

    def __get__(self, obj, objtype=None):
        if obj is None:
            return self
        return int(obj._cells[self.name].value)

    def __set__(self, obj, v):
        obj._cells[self.name].value = float(v)


class EngineStats:
    """Engine counters, re-implemented as a VIEW over the metrics registry
    (serving/metrics.py): each field is a registry counter cell, so the
    legacy attribute surface (``stats.handoffs += 1``, ``stats() -> dict``)
    and the new observability surface (``engine.metrics()``, Prometheus
    exposition) are the same numbers by construction. Counters stay real
    even with metrics disabled — ``stats()`` predates the registry and must
    keep working either way."""

    prefill_tokens_computed = _CounterField(
        "engine_prefill_tokens_computed_total",
        "prompt tokens actually run through the base prefill model")
    prefill_tokens_reused = _CounterField(
        "engine_prefill_tokens_reused_total",
        "prompt tokens served from cached prefix KV (never recomputed)")
    prefill_tokens_duplicated = _CounterField(
        "engine_prefill_tokens_duplicated_total",
        "prompt tokens admitted for prefill that an admitted, not yet "
        "committed request is already computing")
    handoffs = _CounterField(
        "engine_handoffs_total", "prefill->decode cache handoffs")
    handoff_bytes = _CounterField(
        "engine_handoff_bytes_total",
        "handoff wire bytes (paged: block-table metadata only)")
    cow_page_copies = _CounterField(
        "engine_cow_page_copies_total",
        "partial tail pages cloned at handoff (page-level copy-on-write)")
    decode_steps = _CounterField(
        "engine_decode_steps_total", "engine decode steps")
    decode_tokens = _CounterField(
        "engine_decode_tokens_total", "tokens generated across all sequences")
    decode_dispatches = _CounterField(
        "engine_decode_dispatches_total", "jitted decode forwards issued")
    decode_table_pages = _CounterField(
        "engine_decode_table_pages_total",
        "block-table pages handed to decode attention: rows x bucketed width")
    decode_live_pages = _CounterField(
        "engine_decode_live_pages_total",
        "pages decode attention reads: ceil(length / page) per real row")
    model_churn_events = _CounterField(
        "engine_model_churn_events_total",
        "accepted register/unregister mutations")
    plane_rebuilds = _CounterField(
        "engine_plane_rebuilds_total",
        "fused-plane relayouts applied at step boundaries")
    relay_publishes = _CounterField(
        "engine_relay_publishes_total",
        "finished sequences whose decode KV entered the prefix tree")
    relay_pages_published = _CounterField(
        "engine_relay_pages_published_total",
        "decode-written pages adopted into the radix tree at finish")
    relay_skipped = _CounterField(
        "engine_relay_skipped_total",
        "finished sequences not published (relay-incompatible decoder)")
    preemptions = _CounterField(
        "engine_preemptions_total",
        "decode sequences preempted under pool pressure")
    swap_out_pages = _CounterField(
        "engine_swap_out_pages_total",
        "pages gathered out to the host swap tier")
    swap_in_pages = _CounterField(
        "engine_swap_in_pages_total",
        "pages scattered back from the host swap tier")
    recompute_tokens = _CounterField(
        "engine_recompute_tokens_total",
        "cache-cold tokens re-prefilled to restore dropped victims")
    swap_bytes = _CounterField(
        "engine_swap_bytes_total",
        "KV bytes moved device<->host by the swap tier")

    FIELDS = ("prefill_tokens_computed", "prefill_tokens_reused",
              "prefill_tokens_duplicated", "handoffs",
              "handoff_bytes", "cow_page_copies", "decode_steps",
              "decode_tokens", "decode_dispatches", "decode_table_pages",
              "decode_live_pages", "model_churn_events",
              "plane_rebuilds", "relay_publishes", "relay_pages_published",
              "relay_skipped", "preemptions", "swap_out_pages",
              "swap_in_pages", "recompute_tokens", "swap_bytes")

    def __init__(self, _engine: object = None,
                 registry: MetricsRegistry | None = None):
        self._engine = _engine
        # standalone EngineStats() (DensePrefillWorker default) gets a
        # private registry; the engine passes its own so all surfaces share
        # one set of cells
        self.registry = MetricsRegistry() if registry is None else registry
        cls = type(self)
        self._cells = {
            name: self.registry.counter(cls.__dict__[name].prom,
                                        cls.__dict__[name].help)
            for name in self.FIELDS}

    @property
    def hit_ratio(self):
        tot = self.prefill_tokens_computed + self.prefill_tokens_reused
        return self.prefill_tokens_reused / tot if tot else 0.0

    @property
    def decode_batch_mean(self):
        return self.decode_tokens / self.decode_steps if self.decode_steps else 0.0

    def __call__(self) -> dict:
        """ONE engine-wide stats surface (``engine.stats()``): the counter
        fields above plus the per-worker ``CacheStats`` rolled up fleet-wide
        (prefix-hit tokens / lookups / hit ratio via ``CacheStats.merge`` —
        the same accounting path the simulator reports) and the pool's
        eviction/occupancy counters. Benches and the simulator read this one
        number instead of stitching per-manager fragments."""
        d = {name: getattr(self, name) for name in self.FIELDS}
        d["hit_ratio"] = self.hit_ratio
        d["decode_batch_mean"] = self.decode_batch_mean
        d["program_traces"] = program_traces()
        eng = self._engine
        if eng is None:
            return d
        agg = CacheStats.merge(w.mgr.stats for w in eng.prefill_workers)
        pools = ([eng.block_pool] if eng.block_pool is not None
                 else [w.mgr.pool for w in eng.prefill_workers])
        # pages_cached counts EVERY radix-resident evictable page regardless
        # of provenance — prefill-published and decode-(relay-)published pages
        # live in the same pool population; the relay share is split out so
        # dashboards can see how much cache occupancy decode contributed
        idx = eng.prefix_index
        relay_nodes = getattr(idx, "relay_nodes", 0) if idx is not None else 0
        cached_relay = 0
        if (eng.block_pool is not None and idx is not None
                and hasattr(idx, "_by_block")):
            cached = eng.block_pool._cached
            cached_relay = sum(1 for bid, nd in idx._by_block.items()
                               if nd.provenance == "relay" and bid in cached)
        d.update(
            prefix_hit_tokens=agg.hit_tokens,
            prefix_total_tokens=agg.total_tokens,
            prefix_lookups=agg.lookups,
            prefix_hit_ratio=agg.hit_ratio,
            relay_hit_tokens=agg.relay_hit_tokens,
            relay_hit_ratio=(agg.relay_hit_tokens / agg.total_tokens
                             if agg.total_tokens else 0.0),
            evictions=sum(p.stats.evictions for p in pools),
            pages_active=sum(p.active_count for p in pools),
            pages_cached=sum(p.cached_count for p in pools),
            pages_cached_relay=cached_relay,
            relay_nodes=relay_nodes,
            prefix_nodes=(len(eng.prefix_index)
                          if eng.prefix_index is not None
                          else sum(len(w.mgr.index)
                                   for w in eng.prefill_workers)),
        )
        swap = getattr(eng, "swap", None)
        d.update(
            pages_swapped=sum(getattr(p, "swapped_count", 0) for p in pools),
            swapped_seqs=len(swap.records) if swap is not None else 0,
        )
        return d


# ======================================================================
# Prefill workers


class PrefillWorker:
    """Paged prefill worker: frozen base model + CacheManager over the
    engine's SHARED physical page pool and (by default) the engine's
    SHARED GLOBAL radix tree, so a prefix published by any worker is a hit
    on every worker."""

    def __init__(self, wid: int, cfg: ModelConfig, base_params,
                 kvpool: PagedKVPool, block_pool: BlockPool,
                 stats: EngineStats, index=None):
        self.wid = wid
        self.cfg = cfg
        self.base_params = base_params
        self.kvpool = kvpool
        self.mgr = CacheManager(cfg, block_pool.num_blocks,
                                block_pool.block_size, pool=block_pool,
                                index=index)
        self.sessions: dict[int, PagedSession] = {}
        self.stats = stats
        self.backlog_s = 0.0      # router load signal (estimated work issued)
        self.last_decay_t = time.monotonic()   # backlog decay clock
        self.ewma = ThroughputEWMA()       # measured prefill s/token
        self.pending_chunk_tokens = 0      # admitted-but-uncomputed (chunked)

    def prefill(self, sid: int, tokens) -> tuple[list, int]:
        """Ensure pool pages cover ``tokens``; compute only the uncached
        tail. Returns (block_table, n_tokens)."""
        tokens = [int(t) for t in np.asarray(tokens)]
        n = len(tokens)
        sc = self.sessions.get(sid)
        if sc is not None and sc.tokens == tokens:
            # sibling submit of the identical context (e.g. several decode
            # models fanning out over one turn): the session's pages already
            # hold it — no acquire, no recompute, no fresh partial page.
            self.mgr.record_hit(n)             # same accounting path
            self.stats.prefill_tokens_reused += n
            return list(sc.block_table), n
        alloc = self.mgr.acquire(tokens)
        n_cached = alloc.cached_tokens
        bt = list(alloc.blocks)
        try:
            if n_cached < n:
                new = jnp.asarray(tokens[n_cached:], jnp.int32)[None]
                t0 = time.perf_counter()
                out = base_prefill_paged(self.cfg, self.base_params, new,
                                         pool=self.kvpool, block_table=bt,
                                         n_cached=n_cached)
                jax.block_until_ready(out)
                self.ewma.observe(n - n_cached, time.perf_counter() - t0)
        except BaseException:
            # nothing was committed: tail pages hold partial KV and must be
            # hard-freed, cached prefix refs go back (RPR002 discipline)
            self.mgr.abandon(alloc)
            raise
        self.mgr.commit(tokens, alloc)
        if sc is not None:
            self.mgr.release(sc.alloc)     # swap, don't drop: new alloc holds
        self.sessions[sid] = PagedSession(alloc, bt, n, tokens)
        self.stats.prefill_tokens_computed += n - n_cached
        self.stats.prefill_tokens_reused += n_cached
        self.backlog_s += (n - n_cached) * self.ewma.s_per_token
        return bt, n

    def end_session(self, sid: int):
        sc = self.sessions.pop(sid, None)
        if sc is not None:
            self.mgr.release(sc.alloc)     # pages -> CACHED (LRU, reusable)


class DensePrefillWorker:
    """Dense fallback: one incrementally-extended cache per session (archs
    whose sequence state is not paged KV). The page-level CacheManager still
    runs for accounting, and — unlike the seed — the allocation is HELD for
    the session lifetime so residency matches the refcounts."""

    def __init__(self, cfg: ModelConfig, base_params, *, capacity: int = 512,
                 mgr_blocks: int = 4096, block_size: int = 16,
                 stats: EngineStats | None = None, index=None):
        self.cfg = cfg
        self.base_params = base_params
        self.schema = cache_schema(cfg, base_params, capacity)
        self.capacity = capacity
        self.sessions: dict[int, SessionCache] = {}
        self.mgr = CacheManager(cfg, mgr_blocks, block_size, index=index)
        self.stats = stats if stats is not None else EngineStats()
        self.backlog_s = 0.0
        self.last_decay_t = time.monotonic()
        self.ewma = ThroughputEWMA()
        self.pending_chunk_tokens = 0

    def prefill(self, sid: int, tokens) -> SessionCache:
        tokens = np.asarray(tokens)
        n = len(tokens)
        sc = self.sessions.get(sid)
        alloc = self.mgr.acquire(tokens.tolist())      # block-level metrics
        self.mgr.commit(tokens.tolist(), alloc)
        t0 = time.perf_counter()
        try:
            if sc is None:
                _, cache = base_prefill(
                    self.cfg, self.base_params, jnp.asarray(tokens)[None],
                    cache_len=max(self.capacity, n))
                jax.block_until_ready(cache)
                self.ewma.observe(n, time.perf_counter() - t0)
                new = SessionCache(cache, n, max(self.capacity, n), alloc)
                self.stats.prefill_tokens_computed += n
            else:
                assert n > sc.n_tokens, "context is append-only"
                fresh = tokens[sc.n_tokens:]
                _, cache = base_prefill(
                    self.cfg, self.base_params, jnp.asarray(fresh)[None],
                    cache_len=sc.capacity, cache=sc.cache,
                    pos=jnp.array([sc.n_tokens], jnp.int32))
                jax.block_until_ready(cache)
                self.ewma.observe(len(fresh), time.perf_counter() - t0)
                self.stats.prefill_tokens_computed += len(fresh)
                self.stats.prefill_tokens_reused += sc.n_tokens
                self.mgr.release(sc.alloc)
                new = SessionCache(cache, n, sc.capacity, alloc)
        except BaseException:
            # already committed above, so the pages are published: release
            # (-> CACHED) rather than abandon, mirroring end_session
            self.mgr.release(alloc)
            raise
        self.sessions[sid] = new
        self.backlog_s += n * self.ewma.s_per_token
        return new

    def end_session(self, sid: int):
        sc = self.sessions.pop(sid, None)
        if sc is not None and sc.alloc is not None:
            self.mgr.release(sc.alloc)


# ======================================================================
# Decode


class DecodeWorker:
    """Hosts ONE task-specific decode module (cache-conditioned).

    Paged mode: ``step`` advances every assigned sequence by one token in a
    single batched forward (continuous batching over the shared pool).
    Dense mode: ``generate`` is the legacy B=1 loop over a private cache.

    Weights come from a ``DecodeModelSpec`` and materialize LAZILY: a
    LoRA-spec'd model only pays for full ``lora_apply`` params if one of the
    per-model paths (``fused=False`` loop, dense fallback) actually runs it —
    the fused plane reads the adapter factors straight from the registry and
    never touches this copy."""

    #: may this model's decode-written KV be relay-published as shared
    #: prefix? Set by the engine at attach time (KV path bit-identical to
    #: the frozen base); False for directly-constructed workers.
    relay_compatible = False

    def __init__(self, cfg: ModelConfig, model_id: str, spec,
                 expected_schema, base_params=None):
        self.cfg = cfg
        self.model_id = model_id
        self.spec = as_spec(spec)
        self.base_params = base_params
        self.expected_schema = expected_schema
        self._dec_params = None
        self._step = None

    @property
    def dec_params(self):
        if self._dec_params is None:
            self._dec_params = self.spec.materialize(self.base_params)
        return self._dec_params

    # ---- paged continuous batching ----
    def step(self, tokens, pos, state, block_tables, temps, top_ks, top_ps,
             seeds, greedy_only):
        """One batched decode step: feed ``tokens`` (B,) at positions ``pos``
        (B,) over the pool ``state`` (a ``decode_state``) through
        ``block_tables``, per-sequence sampling controls (B,)-aligned;
        returns (next_tokens (B,), new_state). Sampling runs inside the
        jitted step; temperature=0 rows are exact argmax (the historical
        greedy path, bit-identical), and an all-greedy batch (``greedy_only``
        static flag) traces an argmax-only step with no sampling graph."""
        if self._step is None:
            cfg = self.cfg

            def _step(params, toks, pos, state, bt, temps, top_ks, top_ps,
                      seeds, greedy_only):
                logits, new_state = paged_forward(cfg, params, toks[:, None],
                                                  state, bt, pos)
                if greedy_only:
                    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                else:
                    nxt = sample_step(logits, pos, temps, top_ks, top_ps,
                                      seeds)
                return nxt, new_state

            # jit keyed on (B, npages) shapes + the binary greedy_only flag;
            # retraces only when the batch composition or table width
            # changes (sampling controls are VALUES, never trace keys). The
            # pool state is donated on TPU, so the step appends KV in place;
            # decode_state/absorb_decode_state are the donation-aware pair
            # around this call.
            self._step = donating_jit(_step, (3,), name="model_decode_step",
                                      static_argnums=(9,))
        return self._step(self.dec_params, tokens, pos, state, block_tables,
                          temps, top_ks, top_ps, seeds, greedy_only)

    # ---- dense fallback ----
    def generate(self, cache, start_pos: int, first_token: int,
                 params: SamplingParams) -> tuple[np.ndarray, str]:
        """Legacy B=1 dense loop, now under the same SamplingParams contract
        as the paged planes. Returns (tokens, finish_reason)."""
        cfg = self.cfg
        pos = jnp.array([start_pos], jnp.int32)
        tok = jnp.array([first_token], jnp.int32)
        samp = jnp.asarray([params.temperature], jnp.float32), \
            jnp.asarray([params.top_k], jnp.int32), \
            jnp.asarray([params.top_p], jnp.float32), \
            jnp.asarray([params.seed or 0], jnp.int32)
        greedy_only = params.temperature <= 0
        out, reason = [], FINISH_LENGTH
        for _ in range(params.max_tokens):
            logits, cache, _ = forward(cfg, self.dec_params, tok[:, None],
                                       cache=cache, pos=pos)
            if greedy_only:
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
            else:
                tok = sample_step(logits, pos, *samp)
            t = int(tok[0])
            out.append(t)
            pos = pos + 1
            stop = params.is_stop(t)
            if stop is not None:
                reason = stop
                break
        return np.asarray(out, np.int32), reason


# ======================================================================
# Engine


class LocalDisaggEngine:
    """Proxy + prefill worker pool + heterogeneous decode pool over one
    shared paged KV plane (Appendix B.1, upgraded to the §3.3 pipeline)."""

    def __init__(self, cfg: ModelConfig, base_params, decoders: dict | None = None, *,
                 capacity: int = 512, paged: bool | None = None,
                 num_pages: int = 1024, page_size: int = 16,
                 n_prefill_workers: int = 1, router_policy: str = "pinned",
                 chunked: bool = False, token_budget: int = 256,
                 chunk_size: int = 64, sched_policy: str = "fcfs",
                 fused: bool | None = None, prefix_cache: bool = True,
                 relay: bool = True, metrics: bool = True, autoscale=None,
                 sanitize: bool = False, preempt: bool = False,
                 overcommit: float = 1.0):
        self.cfg = cfg
        self.base_params = base_params
        self.page_size = page_size
        # observability control plane (serving/metrics.py): ONE registry the
        # engine, router, scheduler, pool, and prefix index publish into —
        # engine.metrics() / engine.render_prometheus() export it.
        # metrics=False degrades histograms/gauges/traces to shared no-op
        # singletons (the decode hot loop skips observation entirely via
        # _metrics_on); counters stay real because stats() runs on them.
        self._metrics_on = metrics
        self.metrics_registry = MetricsRegistry(enabled=metrics)
        self.stats = EngineStats(_engine=self,
                                 registry=self.metrics_registry)
        self.chunked = chunked
        self.paged = PagedKVPool.supports(cfg) if paged is None else paged
        if self.paged and not PagedKVPool.supports(cfg):
            raise ValueError(f"{cfg.name}: arch not eligible for paged plane")
        self.schema = cache_schema(cfg, base_params, capacity)
        self.handoff = HandoffChannel(cfg)
        self.router = PrefillRouter(n_prefill_workers, router_policy)
        self.prefix_cache = prefix_cache
        # relay KV: publish finished sequences' decode pages into the radix
        # tree (zero-copy pipeline reuse; module docstring). Requires the
        # paged plane and rides on the prefix tree — with prefix_cache=False
        # the Null index adopts nothing, so relay degrades to off by
        # construction. relay=False is the A/B escape hatch (bit-identical).
        self.relay = relay and self.paged and prefix_cache
        if sanitize and not self.paged:
            raise ValueError("sanitize=True requires the paged KV plane "
                             "(the sanitizer checks page refcounts)")
        if preempt and not self.paged:
            raise ValueError("preempt=True requires the paged KV plane "
                             "(the swap tier moves pool pages)")
        if overcommit != 1.0 and not preempt:
            raise ValueError(
                "overcommit > 1 oversubscribes the decode admission reserve "
                "and is only safe with preemption armed; pass preempt=True")
        if self.paged:
            self.block_pool = BlockPool(num_pages, page_size)
            # sanitize=True swaps in the poisoning pool subclass and a
            # step-boundary invariant checker (repro.kvcache.sanitize);
            # token streams stay bit-identical — checks never mutate state
            self.kvpool = (SanitizedKVPool(cfg, num_pages, page_size)
                           if sanitize
                           else PagedKVPool(cfg, num_pages, page_size))
            # automatic prefix caching: ONE engine-global radix tree over the
            # shared pool, shared by every worker's CacheManager — its
            # eviction callback is registered exactly once, here, and fans
            # out to every manager by construction (they all serve matches
            # from this same tree). prefix_cache=False keeps the A/B escape
            # hatch: no cross-request reuse, bit-identical outputs.
            if prefix_cache:
                self.prefix_index = PrefixIndex(page_size)
                self.block_pool.add_evict_callback(
                    self.prefix_index.remove_block)
            else:
                self.prefix_index = NullPrefixIndex(page_size)
            self.prefill_workers = [
                PrefillWorker(i, cfg, base_params, self.kvpool,
                              self.block_pool, self.stats,
                              index=self.prefix_index)
                for i in range(n_prefill_workers)]
        else:
            # dense fallback: per-worker private pools, so block ids are not
            # comparable across workers — the radix tree stays per-manager
            # (prefix_cache=False still disables it for A/B)
            self.block_pool = None
            self.kvpool = None
            self.prefix_index = None
            self.prefill_workers = [
                DensePrefillWorker(cfg, base_params, capacity=capacity,
                                   block_size=page_size, stats=self.stats,
                                   index=None if prefix_cache
                                   else NullPrefixIndex(page_size))
                for _ in range(n_prefill_workers)]
        self.prefill = self.prefill_workers[0]        # 1-worker convenience
        # fused cross-model decode (serving.decode): advance every sequence
        # of every model in ONE jitted step, one lane per decode module.
        # Default on the paged plane; fused=False keeps the per-model
        # dispatch loop (comparison/regression path).
        self.fused = self.paged if fused is None else fused
        assert not (self.fused and not self.paged), \
            "fused decode requires the paged data plane"
        self.scheduler = ChunkedScheduler(
            self, SchedulerConfig(token_budget=token_budget,
                                  chunk_size=chunk_size,
                                  policy=sched_policy))
        #: step-boundary invariant checker (None unless sanitize=True);
        #: the scheduler calls sanitizer.check_step() after every step
        self.sanitizer = PoolSanitizer(self) if sanitize else None
        #: oversubscription subsystem (serving/preempt.py): None unless
        #: preempt=True; the scheduler drives resume/preempt/grow phases and
        #: scales the admission reserve by cfg.overcommit when it is armed
        self.swap = (SwapManager(self, PreemptConfig(overcommit=overcommit))
                     if preempt else None)
        # model lifecycle: the decode-model set lives in the registry
        # (engine.models) and is mutable while serving — register/unregister
        # take effect for new requests immediately and relayout the fused
        # plane at the next step boundary. ``decoders`` at construction is a
        # deprecation shim that registers each entry as a full-weight spec.
        self.decoders: dict[str, DecodeWorker] = {}
        self.models = ModelRegistry(self)
        self.decode_plane = None
        if decoders:
            warnings.warn(
                "LocalDisaggEngine(..., decoders={...}) at construction is "
                "deprecated; use engine.models.register(model_id, "
                "DecodeModelSpec(full=...|lora=...)) — the model set is a "
                "live lifecycle surface now", DeprecationWarning, stacklevel=2)
            for mid, params in decoders.items():
                self.models.register(mid, params)
        if self.fused:
            self._rebuild_decode_plane()
        self.models._dirty = False
        self.stats.model_churn_events = 0     # construction is not churn
        self._results: dict[int, np.ndarray] = {}
        self._fetched: set[int] = set()
        self._aborted: set[int] = set()
        self._next_rid = 0
        self._next_seq = 0
        # request-centric API state: live streaming handles, sessions owned
        # by the engine (SharedContext / one-shot generate) rather than the
        # caller. Context sids live in a high namespace so they can never
        # collide with caller-chosen ints on the legacy surface.
        self._requests: dict[int, RequestOutput] = {}
        self._ephemeral_sids: dict[int, int] = {}      # rid -> auto session
        self._next_ctx_sid = 1 << 40
        self._init_metrics()
        # metrics-driven elastic prefill:decode scaling: an Autoscaler
        # (serving/autoscale.py AutoscaleConfig) consumes the registry's
        # backlog/occupancy/latency signals at STEP BOUNDARIES (the same
        # place model churn applies — scheduler.step after models.sync) and
        # resizes the prefill worker pool / decode admission reserve.
        self._autoscaler = (None if autoscale is None
                            else Autoscaler(autoscale))
        #: extra pool pages held back from prefill chunking and decode
        #: admission on top of the worst-case tail-growth reserve — the
        #: autoscaler's decode-side protection knob (scheduler reads it)
        self.sched_reserve_extra = 0
        #: pages one reserve_delta step moves (quantized so a single
        #: autoscale tick shifts meaningful headroom, not one page)
        self._reserve_quantum = max(1, num_pages // 32)

    #: half-life of the issued-work router signal, in seconds of WALL TIME.
    #: Decay must be a function of elapsed time, not of pick count — a
    #: per-pick multiplicative decay makes the load signal depend on arrival
    #: rate (two bursts a second apart would see completely different
    #: backlogs), which tests/test_router.py pins as a regression.
    BACKLOG_HALFLIFE_S = 0.25

    # ------------------------------------------------------------------
    def _pick_worker(self, sid: int, tokens=None, now: float | None = None):
        # Prefill here is synchronous, so there is no literal queue; the
        # routing signal is recency-weighted issued work plus (in chunked
        # mode) the admitted-but-uncomputed chunk backlog, both priced at
        # the worker's MEASURED s/token EWMA. The issued-work term decays
        # exponentially in ELAPSED TIME (half-life above), which keeps
        # least_loaded balancing while preventing spillover from permanently
        # migrating pinned sessions off an idle worker just because its
        # lifetime total is ahead — and, unlike the old per-pick halving,
        # makes the signal invariant to how often the router is consulted.
        now = time.monotonic() if now is None else now
        for w in self.prefill_workers:
            dt = now - w.last_decay_t
            if dt > 0:
                w.backlog_s *= 0.5 ** (dt / self.BACKLOG_HALFLIFE_S)
                w.last_decay_t = now
        backlogs = [w.backlog_s + w.ewma.backlog_seconds(w.pending_chunk_tokens)
                    for w in self.prefill_workers]
        cold_s = None
        if tokens is not None:
            # expected prefix-hit pricing: the request's cost at a worker is
            # only its COLD tokens — zero on a worker whose session already
            # holds the exact context (fast path), prompt minus the longest
            # radix match otherwise (under the engine-global tree the match
            # is worker-independent; dense fallback keeps per-worker trees,
            # where this term IS the locality signal). match_len is a pure
            # peek: consulting candidates must not refresh LRU recency.
            n = len(tokens)
            cold_s = []
            for w in self.prefill_workers:
                sc = w.sessions.get(sid)
                if sc is not None and getattr(sc, "tokens", None) == tokens:
                    cold = 0
                else:
                    cold = n - w.mgr.index.match_len(tokens)
                cold_s.append(w.ewma.backlog_seconds(cold))
        # the router prices expected completion time in MEASURED seconds:
        # backlog + cold prefill + the measured handoff estimate (EWMA of
        # real zero-copy handoffs — kvcache/handoff.py observe_paged), not
        # the old decorative bandwidth constant
        picked = self.router.pick(sid, now, backlogs, cold_s,
                                  handoff_s=self.handoff.estimate_paged_s())
        if self._metrics_on:
            self._c_router_picks.inc()
            if picked != sid % len(self.prefill_workers):
                self._c_router_nonhome.inc()
        return self.prefill_workers[picked]

    # ------------------------------------------------------------------
    # observability (serving/metrics.py; docs/api.md "Observability")
    # ------------------------------------------------------------------
    def _init_metrics(self) -> None:
        """Bind the engine's instruments. Histograms are created up front so
        hot paths hold direct references (no registry lookups per sample);
        gauges are fn-backed collectors sampled only at export time, so pool
        occupancy / queue depths / radix size cost nothing per step."""
        reg = self.metrics_registry
        self._h_ttft = reg.histogram(
            "engine_ttft_seconds", "submit -> first streamed token",
            lo=1e-5, hi=600.0)
        self._h_itl = reg.histogram(
            "engine_itl_seconds", "gap between consecutive streamed tokens",
            lo=1e-6, hi=60.0)
        self._h_queue = reg.histogram(
            "engine_queue_depth", "waiting+prefilling requests, per step",
            lo=1.0, hi=4096.0, growth=1.5)
        self._h_occ = reg.histogram(
            "engine_page_occupancy",
            "non-free pool page fraction, per step", lo=1e-3, hi=1.0)
        self._h_batch = reg.histogram(
            "engine_decode_batch", "sequences per decode step",
            lo=1.0, hi=4096.0, growth=1.5)
        self._h_handoff_s = reg.histogram(
            "engine_handoff_seconds",
            "measured prefill->decode handoff wall time", lo=1e-7, hi=10.0)
        self._h_handoff_b = reg.histogram(
            "engine_handoff_plan_bytes", "handoff metadata bytes",
            lo=1.0, hi=1e9, growth=2.0)
        self._c_router_picks = reg.counter(
            "engine_router_picks_total", "prefill routing decisions")
        self._c_router_nonhome = reg.counter(
            "engine_router_nonhome_picks_total",
            "routing decisions away from the session's home worker")
        self._c_autoscale = reg.counter(
            "engine_autoscale_decisions_total",
            "autoscaler resize decisions applied")
        reg.counter_family(
            "engine_program_traces_total",
            "programs traced in this process, each one compiled or loaded "
            "from the compile cache, by program", "program", program_traces)
        reg.gauge("engine_prefill_workers", "live prefill workers",
                  fn=lambda: len(self.prefill_workers))
        reg.gauge("engine_waiting_requests", "requests awaiting admission",
                  fn=lambda: len(self.scheduler.waiting))
        reg.gauge("engine_prefilling_requests", "requests mid-prefill",
                  fn=lambda: len(self.scheduler.prefilling))
        reg.gauge("engine_active_sequences", "sequences decoding",
                  fn=lambda: len(self.scheduler.active))
        reg.gauge("engine_sched_reserve_extra_pages",
                  "autoscaler decode admission reserve (pages)",
                  fn=lambda: self.sched_reserve_extra)
        if self.block_pool is not None:
            reg.gauge("engine_pool_free_pages", "free pool pages",
                      fn=lambda: self.block_pool.free_count)
            reg.gauge("engine_pool_active_pages", "refcount-held pool pages",
                      fn=lambda: self.block_pool.active_count)
            reg.gauge("engine_pool_cached_pages",
                      "LRU-cached (evictable) pool pages",
                      fn=lambda: self.block_pool.cached_count)
            reg.gauge("engine_pool_swapped_pages",
                      "pages whose KV lives in the host swap tier",
                      fn=lambda: self.block_pool.swapped_count)
            reg.gauge("engine_swapped_sequences",
                      "decode sequences parked in the swap tier",
                      fn=lambda: (len(self.swap.records)
                                  if self.swap is not None else 0))
            reg.gauge("engine_swap_host_bytes",
                      "host memory held by swapped-out KV",
                      fn=lambda: (self.swap.host.total_bytes
                                  if self.swap is not None else 0))
        if self.prefix_index is not None:
            reg.gauge("engine_prefix_nodes", "radix prefix-index nodes",
                      fn=lambda: len(self.prefix_index))
            reg.gauge("engine_relay_nodes",
                      "radix nodes holding decode-written (relay) KV",
                      fn=lambda: getattr(self.prefix_index,
                                         "relay_nodes", 0))

    def metrics(self) -> dict:
        """The full observability surface as structured dicts:
        ``{"counters", "gauges", "histograms"}`` (histograms carry
        count/sum/mean/min/max/p50/p95/p99) plus ``"traces"`` — the retained
        per-request lifecycle traces (span-event dicts; see docs/api.md).
        ``engine.stats()`` remains the legacy counter rollup; this is the
        superset it is implemented on."""
        out = self.metrics_registry.snapshot()
        out["traces"] = [t.as_dict() for t in self.metrics_registry.traces()]
        return out

    def render_prometheus(self) -> str:
        """Prometheus text exposition of every registered metric (what a
        scrape endpoint would serve; linted in CI via
        ``metrics.lint_prometheus``)."""
        return self.metrics_registry.render_prometheus()

    def _observe_step(self) -> None:
        """Per-step occupancy/queue observations (called from the scheduler
        at every step boundary; one histogram sample each, no allocation
        when metrics are disabled)."""
        if not self._metrics_on:
            return
        sched = self.scheduler
        self._h_queue.observe(len(sched.waiting) + len(sched.prefilling))
        if self.block_pool is not None:
            pool = self.block_pool
            self._h_occ.observe(1.0 - pool.free_count / pool.num_blocks)

    # ------------------------------------------------------------------
    # elastic prefill:decode scaling (serving/autoscale.py)
    # ------------------------------------------------------------------
    def _autoscale_signals(self):
        """Assemble the control-loop inputs from the live engine state +
        metric windows (TTFT/ITL p95 read straight off the histograms)."""
        from repro.serving.autoscale import AutoscaleSignals
        sched = self.scheduler
        backlog_tokens = (sum(r.n - r.done for r in sched.prefilling)
                          + sum(r.n for r in sched.waiting))
        rates = [w.ewma.s_per_token for w in self.prefill_workers]
        spt = sum(rates) / len(rates)
        slots = max(sched.cfg.token_budget, 1)
        pool = self.block_pool
        return AutoscaleSignals(
            prefill_backlog_tokens=backlog_tokens,
            prefill_backlog_s=backlog_tokens * spt,
            decode_occupancy=len(sched.active) / slots,
            free_page_frac=(pool.free_count / pool.num_blocks
                            if pool is not None else 1.0),
            ttft_p95_s=self._h_ttft.percentile(95),
            itl_p95_s=self._h_itl.percentile(95),
            n_prefill=len(self.prefill_workers),
            n_decode=1,
            inflight_decode=len(sched.active))

    def _autoscale_tick(self) -> None:
        """Step-boundary resize hook (scheduler.step, right after model
        churn applies — the one place worker-set mutations are legal).
        prefill_delta resizes the REAL worker pool (PR 5 pattern: new
        workers share the pool, the radix tree, and the stats cells, and
        become routable immediately); decode_delta maps onto the decode
        admission reserve — the engine's decode plane is one fused step, so
        "more decode capacity" means holding back pages from prefill so
        promotions never squeeze running generations."""
        if self._autoscaler is None:
            return
        d = self._autoscaler.tick(self._autoscale_signals(),
                                  time.monotonic())
        if d.prefill_delta > 0:
            self._add_prefill_worker()
            self._c_autoscale.inc()
        elif d.prefill_delta < 0:
            if self._remove_prefill_worker():
                self._c_autoscale.inc()
        if d.decode_delta:
            cap = self.block_pool.num_blocks if self.block_pool else 0
            self.sched_reserve_extra = min(
                max(self.sched_reserve_extra
                    + d.decode_delta * self._reserve_quantum, 0),
                cap // 2)

    def _add_prefill_worker(self) -> None:
        """Grow the prefill pool by one worker sharing the engine's page
        pool, global radix tree, and stats cells; the router sees it for the
        next pick. Paged plane only (the dense fallback has per-worker
        private pools that cannot be hot-joined)."""
        assert self.paged, "elastic prefill pool requires the paged plane"
        w = PrefillWorker(len(self.prefill_workers), self.cfg,
                          self.base_params, self.kvpool, self.block_pool,
                          self.stats, index=self.prefix_index)
        self.prefill_workers.append(w)
        self.router.n = len(self.prefill_workers)

    def _remove_prefill_worker(self) -> bool:
        """Shrink the prefill pool by one, only if the LAST worker is fully
        idle — no live sessions, no admitted request holds a reference to
        it, no pending chunk work. Returns False (decision deferred) when
        the candidate is busy; the autoscaler retries next tick. Never drops
        below one worker."""
        if len(self.prefill_workers) <= 1:
            return False
        w = self.prefill_workers[-1]
        sched = self.scheduler
        if (w.sessions or w.pending_chunk_tokens
                or any(r.worker is w for r in sched.prefilling)):
            return False
        self.prefill_workers.pop()
        self.router.n = len(self.prefill_workers)
        return True

    # ------------------------------------------------------------------
    # model lifecycle (driven by repro.serving.registry.ModelRegistry)
    # ------------------------------------------------------------------
    #: top-level param subtrees that never feed a KV row: the unembed head
    #: and the final norm run strictly AFTER the last layer's KV write, so a
    #: decoder differing ONLY here produces bit-identical KV to the frozen
    #: base — the canonical PrefillShare shape of a frozen trunk + tuned head
    _KV_NEUTRAL_KEYS = ("unembed", "final_norm")

    def _relay_compatible(self, spec) -> bool:
        """May ``spec``'s decode-written KV be republished as shared prefix?
        Only if its KV path is bit-identical to the frozen base model's:
        full weights that ARE the base, or differ solely in KV-neutral
        leaves (``_KV_NEUTRAL_KEYS``). LoRA adapters perturb attention
        weights, so their KV is theirs alone. Checked once at attach time
        (weight identity is a property of the registration, not the step)."""
        if spec.full is None:
            return False
        if spec.full is self.base_params:
            return True
        tu = jax.tree_util
        if (tu.tree_structure(spec.full)
                != tu.tree_structure(self.base_params)):
            return False
        base = tu.tree_flatten_with_path(self.base_params)[0]
        for (path, lb), (_, ld) in zip(base,
                                       tu.tree_flatten_with_path(spec.full)[0]):
            key = getattr(path[0], "key", None) if path else None
            if key in self._KV_NEUTRAL_KEYS:
                continue
            if lb is not ld and not np.array_equal(np.asarray(lb),
                                                   np.asarray(ld)):
                return False
        return True

    def _attach_decoder(self, model_id: str, spec) -> None:
        """Registry hook: make ``model_id`` servable NOW (the per-model
        DecodeWorker materializes its weights lazily; the fused plane picks
        the model up at the next step boundary)."""
        dw = DecodeWorker(self.cfg, model_id, spec,
                          self.schema, self.base_params)
        dw.relay_compatible = self._relay_compatible(dw.spec)
        self.decoders[model_id] = dw

    def _detach_decoder(self, model_id: str) -> None:
        self.decoders.pop(model_id, None)

    def _rebuild_decode_plane(self) -> None:
        """Relayout the fused plane to the registry's CURRENT model set.
        Called at step boundaries only (``ModelRegistry.sync`` via the
        scheduler; plus once at construction): sequences are addressed by
        model id and every step re-derives lane indices from the new plane,
        so live sequences keep decoding bit-identically — their pages,
        positions, and sampling keys are untouched by the remap. The
        dispatch counter carries across rebuilds (stats stay cumulative)."""
        if not self.fused:
            return
        old = self.decode_plane
        self.decode_plane = FusedDecodePlane(
            {mid: (self.cfg, spec)
             for mid, spec in self.models._specs.items()},
            self.kvpool, self.base_params,
            dispatches0=old.dispatches if old is not None else 0)
        if old is not None:
            self.stats.plane_rebuilds += 1

    def _has_inflight(self, model_id: str) -> bool:
        """Any live work addressed to ``model_id`` (waiting / prefilling /
        decoding)? Gates drain completion and plane-lane retirement."""
        sched = self.scheduler
        return (any(r.model_id == model_id for r in sched.waiting)
                or any(r.model_id == model_id for r in sched.prefilling)
                or any(s.model_id == model_id for s in sched.active)
                or (self.swap is not None
                    and any(rec.seq.model_id == model_id
                            for rec in self.swap.records.values())))

    def _inflight_rids(self, model_id: str) -> list[int]:
        sched = self.scheduler
        parked = ([rid for rid, rec in self.swap.records.items()
                   if rec.seq.model_id == model_id]
                  if self.swap is not None else [])
        return ([r.rid for r in sched.waiting if r.model_id == model_id]
                + [r.rid for r in sched.prefilling if r.model_id == model_id]
                + [s.rid for s in sched.active if s.model_id == model_id]
                + parked)

    def _handoff_seq(self, block_table, n: int, sid: int, model_id: str,
                     params: SamplingParams, first_token: int,
                     rid: int, tokens=None, priority: int = 0) -> DecodeSeq:
        """Zero-copy handoff: block-table reference + page refcounts, with a
        page-level copy-on-write clone of a partially-filled tail page so the
        decode sequence can append privately. Raises PoolExhausted (with the
        handoff refs rolled back) if the clone page cannot be allocated.
        ``tokens`` (the prompt) rides along on the sequence so relay
        publication can key its pages by the full token stream at finish."""
        dw = self.decoders[model_id]
        HandoffChannel.check(self.schema, dw.expected_schema)
        t0 = time.perf_counter()
        bt = list(block_table)
        self.block_pool.ref(bt)
        shared, private = list(bt), []
        if n % self.page_size:
            # partial tail page is shared with the prefill session (and any
            # sibling decoder): clone it so this sequence can append.
            last = bt[-1]
            try:
                [fresh] = self.block_pool.alloc(1)
            except PoolExhausted:
                self.block_pool.unref(bt)      # roll back the handoff refs
                raise
            self.kvpool.copy_page(last, fresh)
            self.block_pool.unref([last])
            shared.pop()
            private.append(fresh)
            bt = bt[:-1] + [fresh]
            self.stats.cow_page_copies += 1
        plan = self.handoff.plan_paged(len(bt))
        # the handoff channel is priced by MEASUREMENT: the wall time of the
        # refcount + CoW work just done (the whole zero-copy handoff) feeds
        # the EWMA that plan_paged/estimate_paged_s report and the router
        # prices — replacing the old link-bandwidth fiction
        dt = time.perf_counter() - t0
        self.handoff.observe_paged(plan.bytes, dt)
        self.stats.handoffs += 1
        self.stats.handoff_bytes += plan.bytes         # metadata only
        if self._metrics_on:
            self._h_handoff_s.observe(dt)
            self._h_handoff_b.observe(plan.bytes)
            self.metrics_registry.trace(rid).event(
                SPAN_HANDOFF, bytes=plan.bytes, seconds=dt)
        return DecodeSeq(rid, sid, model_id, bt, shared, private, n,
                         first_token, params.max_tokens, params,
                         tokens=list(tokens) if tokens is not None else [],
                         first0=first_token, priority=priority)

    def submit(self, sid: int, context_tokens, model_id: str,
               gen_tokens: int, first_token: int = 2,
               priority: int = 0) -> int:
        """DEPRECATED legacy surface: queue one greedy, fixed-length request
        against a caller-managed session id; drive with ``run``/``step`` and
        fetch via ``result``/``pop_result``. Use ``generate`` (a streaming
        ``RequestOutput`` with SamplingParams and abort) or a
        ``shared_context`` instead — this shim survives only as a
        token-identical wrapper over that path."""
        warnings.warn(
            "LocalDisaggEngine.submit() is deprecated; use "
            "engine.generate(model_id, tokens, SamplingParams(...)) or "
            "engine.shared_context(prefix).generate(...) instead",
            DeprecationWarning, stacklevel=2)
        return self._submit(sid, context_tokens, model_id,
                            SamplingParams(max_tokens=gen_tokens),
                            first_token, priority)

    def _submit(self, sid: int, context_tokens, model_id: str | None,
                params: SamplingParams, first_token: int = 2,
                priority: int = 0) -> int:
        """Queue one generation request (internal, both API surfaces).

        Chunked mode: the request enters the scheduler's admission queue and
        its prompt is prefilled in token-budget chunks interleaved with
        decode, ordered by ``priority`` under the priority policy. Legacy
        mode: whole-prompt prefill + handoff happen here, synchronously and
        in call order, so ``priority`` has no effect. ``max_tokens == 0`` is
        a prefill-only request: the prompt becomes resident (and published
        for prefix reuse) but no decode sequence is created."""
        assert self.paged, "submit/run requires the paged data plane"
        if model_id is not None:          # first-class, BEFORE any rid/pages
            self.models.check_serving(model_id)
        rid = self._next_rid
        self._next_rid += 1
        # lifecycle trace opens HERE (queued span), at the same instant the
        # rid exists; every later stage appends to it via the registry
        self.metrics_registry.start_trace(rid, model_id)
        params = self._resolve_seed(params, rid)
        tokens = [int(t) for t in np.asarray(context_tokens)]
        if self.chunked:
            self.scheduler.add(Request(
                rid=rid, sid=sid, model_id=model_id, tokens=tokens,
                gen_tokens=params.max_tokens, first_token=first_token,
                priority=priority, seq=self._next_seq, params=params))
            self._next_seq += 1
            return rid
        worker = self._pick_worker(sid, tokens)
        self.metrics_registry.trace(rid).event(SPAN_ROUTED, worker=worker.wid)
        bt, n = worker.prefill(sid, tokens)
        if params.max_tokens == 0:
            self._finish_prefill_only(rid)
            return rid
        self.scheduler.add_decode_seq(self._handoff_seq(
            bt, n, sid, model_id, params, first_token, rid, tokens=tokens,
            priority=priority))
        return rid

    # ------------------------------------------------------------------
    # request-centric API (repro.serving.api)
    # ------------------------------------------------------------------
    def generate(self, model_id: str, tokens,
                 params: SamplingParams | None = None, *, session: int | None = None,
                 priority: int = 0, first_token: int = 2,
                 stream_callback=None) -> RequestOutput:
        """Queue one generation and return its streaming ``RequestOutput``.

        ``session=None`` runs the request in an engine-owned one-shot
        session, released automatically when the request finishes (or is
        aborted) — no manual ``end_session``. Pass a ``SharedContext``'s
        session (via ``ctx.generate``) to attach to a shared prefix.
        Iterate the handle / call ``result()`` to drive the engine, or drive
        it yourself with ``run()``/``step()``."""
        self.models.check_serving(model_id)   # UnknownModelError before any
        params = SamplingParams() if params is None else params   # state
        if not priority:
            priority = params.priority    # SamplingParams carries it too
        ephemeral = session is None
        sid = self._new_context_sid() if ephemeral else session
        if not self.paged:
            params = self._resolve_seed(params, self._next_rid)
            return self._generate_dense(sid, tokens, model_id, params,
                                        first_token, ephemeral,
                                        stream_callback)
        rid = self._next_rid                      # _submit assigns this rid
        params = self._resolve_seed(params, rid)  # handle sees the real seed
        out = RequestOutput(self, rid, sid, model_id, params)
        if stream_callback is not None:
            out.add_callback(stream_callback)
        self._requests[rid] = out
        if ephemeral:
            self._ephemeral_sids[rid] = sid
        try:
            got = self._submit(sid, tokens, model_id, params, first_token,
                               priority)
        except Exception:
            # eager-mode prefill can raise (PoolExhausted) after the handle
            # was registered: unwind so retries don't leak orphan handles
            self._requests.pop(rid, None)
            self._ephemeral_sids.pop(rid, None)
            raise
        assert got == rid
        return out

    def shared_context(self, prefix_tokens=(), *,
                       prefill: bool = True) -> SharedContext:
        """Open a first-class shared prefix (see ``repro.serving.api``):
        one prefilled context that multiple ``ctx.generate(model_id, tail)``
        calls attach to — the paper's execution pattern as the API's main
        verb. Use as a context manager; exit releases the pages."""
        return SharedContext(self, prefix_tokens, prefill=prefill)

    def abort(self, request) -> bool:
        """Cancel a request at any lifecycle stage. Accepts a
        ``RequestOutput`` or a raw request id. Returns True if the request
        was still live (False: already finished, already aborted, unknown).

        Queued: removed before any pages are touched. Prefilling (including
        held under pool backpressure): its chunk-granular allocation is
        reclaimed — cached prefix pages return to the LRU cache, partially
        written tail pages are dropped. Decoding: its handoff refs and
        private pages are released. In every case the pool's free-page count
        returns exactly to its pre-request baseline."""
        rid = request.request_id if isinstance(request, RequestOutput) \
            else int(request)
        if rid in self._results or rid in self._fetched \
                or rid in self._aborted:
            return False
        sched = self.scheduler
        for r in sched.waiting:                    # queued: nothing held yet
            if r.rid == rid:
                sched.waiting.remove(r)
                self._on_request_aborted(rid)
                return True
        for r in sched.prefilling:                 # mid-chunk / held / stalled
            if r.rid != rid:
                continue
            sched.prefilling.remove(r)
            if r.sibling_bt is not None:
                self.block_pool.unref(r.sibling_bt)   # drop the sibling pin
            elif r.committed:
                pass       # the session owns the allocation now; pages stay
            else:
                r.worker.mgr.abandon(r.alloc)
                r.worker.pending_chunk_tokens -= r.n - r.done
            self._on_request_aborted(rid)
            return True
        if self.swap is not None and rid in self.swap.records:
            # parked in the swap tier: shared refs released, still-resident
            # swapped rows freed, host copy discarded — free pages return
            # exactly to the pre-request baseline (revoked rows already
            # belong to their new owners and are not touched)
            self.swap.abort(rid)
            self._on_request_aborted(rid)
            return True
        for s in sched.active:                     # decoding
            if s.rid != rid:
                continue
            if s.remaining <= 0:
                return False   # generation already complete, merely awaiting
                               # the next step's reap — not abortable
            sched.active.remove(s)
            self.block_pool.unref(s.shared_blocks)
            self.block_pool.drop(s.private_blocks)
            self._on_request_aborted(rid)
            return True
        return False

    @staticmethod
    def _resolve_seed(params: SamplingParams, rid: int) -> SamplingParams:
        """``seed=None`` -> a distinct engine-assigned per-request seed (the
        rid), so N sampled fan-outs over one prompt give N different draws;
        an explicit seed passes through untouched for cross-run
        reproducibility. Idempotent once resolved."""
        if params.seed is not None:
            return params
        return dataclasses.replace(params, seed=rid)

    def _new_context_sid(self) -> int:
        sid = self._next_ctx_sid
        self._next_ctx_sid += 1
        return sid

    def _prefill_context(self, sid: int, tokens) -> None:
        """Make ``tokens`` resident for session ``sid`` (SharedContext
        warm-up). Eager mode prefills synchronously; chunked mode drives the
        scheduler until the prefill-only request completes."""
        assert self.paged, "shared contexts require the paged data plane"
        rid = self._submit(sid, tokens, None, SamplingParams(max_tokens=0))
        while rid not in self._results:
            self.scheduler.step()
        self.pop_result(rid)                       # empty marker array

    def _finish_prefill_only(self, rid: int) -> None:
        self._results[rid] = np.zeros(0, np.int32)
        self._on_request_done(rid, FINISH_LENGTH)

    def _on_request_done(self, rid: int, reason: str) -> None:
        # terminal trace span: "aborted" for aborts (at ANY lifecycle
        # stage — queued / prefilling / held / decoding all funnel here),
        # "finished" with the reason otherwise
        self.metrics_registry.trace(rid).close(reason)
        out = self._requests.pop(rid, None)        # engine-side handle ref:
        if out is not None:                        # dropped once finished
            out._mark_finished(reason)
        sid = self._ephemeral_sids.pop(rid, None)
        if sid is not None:
            self.end_session(sid)                  # one-shot session cleanup

    def _on_request_aborted(self, rid: int) -> None:
        self._aborted.add(rid)
        self._on_request_done(rid, FINISH_ABORT)

    def _generate_dense(self, sid, tokens, model_id, params, first_token,
                        ephemeral, stream_callback=None) -> RequestOutput:
        """Dense-fallback generate (SSM/hybrid archs): synchronous, but the
        same RequestOutput contract (params honoured, tokens streamed to
        callbacks, finish reason set)."""
        out = RequestOutput(self, self._next_rid, sid, model_id, params)
        self._next_rid += 1
        if stream_callback is not None:
            out.add_callback(stream_callback)
        toks, reason = self._invoke_dense(sid, tokens, model_id, params,
                                          first_token)
        for t in toks:
            out._push(int(t))
        if self._metrics_on and out.ttft is not None:
            self._h_ttft.observe(out.ttft)
            for gap in out.inter_token_latencies():
                self._h_itl.observe(gap)
        out._mark_finished(reason)
        if ephemeral:
            self.end_session(sid)
        return out

    def run(self) -> None:
        """Drive the scheduler until every queued request finishes: each step
        packs (one decode token per active sequence) + (prefill chunks under
        the token budget) — see serving/scheduler/."""
        self.scheduler.run()

    def step(self) -> None:
        """One scheduler step (benchmarks/tests interleave arrivals)."""
        self.scheduler.step()

    def _grow_tail_pages(self, seqs: list[DecodeSeq]) -> None:
        page = self.page_size
        for s in seqs:                       # grow private tail pages
            if s.pos >= len(s.block_table) * page:
                [fresh] = self.block_pool.alloc(1)
                s.block_table.append(fresh)
                s.private_blocks.append(fresh)

    def decode_step(self, seqs: list[DecodeSeq]) -> None:
        """Advance every active sequence — across ALL decode models — one
        token, sampled per each request's SamplingParams (temperature=0:
        exact greedy). Fused mode (default): ONE jitted step per engine
        step per distinct decode config (one total here, every decoder shares
        the engine config). fused=False: the per-model dispatch loop.

        Token bookkeeping is centralized here: streaming pushes to the
        request handles, and eos/stop detection that zeroes ``remaining`` so
        the scheduler retires the sequence (freeing its budget slot and,
        via ``_finish``, its pages) on the next step — variable-length
        finishes mid-flight."""
        if not seqs:
            return
        with TraceAnnotation(PS_DECODE_BUILD):
            self._grow_tail_pages(seqs)
        if self.decode_plane is not None:
            plane, st = self.decode_plane, self.stats
            before = plane.dispatches, plane.table_pages, plane.live_pages
            nxt = plane.step(seqs)
            st.decode_dispatches += plane.dispatches - before[0]
            st.decode_table_pages += plane.table_pages - before[1]
            st.decode_live_pages += plane.live_pages - before[2]
        else:
            nxt = np.zeros(len(seqs), np.int32)
            by_model: dict[str, list] = {}
            for i, s in enumerate(seqs):
                by_model.setdefault(s.model_id, []).append(i)
            for mid, idx in by_model.items():
                nxt[idx] = self._batched_step(mid, [seqs[i] for i in idx])
        with TraceAnnotation(PS_DECODE_PUSH):
            metrics_on = self._metrics_on  # ONE branch per token when off —
            for i, s in enumerate(seqs):   # no metric objects touched at all
                t = int(nxt[i])
                s.out.append(t)
                s.next_token = t
                s.pos += 1
                s.remaining -= 1
                out = self._requests.get(s.rid)
                if out is not None:
                    out._push(t)
                    if metrics_on:
                        # TTFT/ITL histograms + trace spans use the SAME
                        # timestamps RequestOutput just recorded at push
                        # time, so exported percentiles are exactly what a
                        # streaming client observes
                        times = out.token_times
                        if len(times) == 1:
                            self._h_ttft.observe(times[0] - out.submit_time)
                            self.metrics_registry.trace(s.rid).event(
                                SPAN_FIRST_TOKEN, t=times[0])
                        else:
                            self._h_itl.observe(times[-1] - times[-2])
                            self.metrics_registry.trace(s.rid).event(
                                SPAN_TOKEN, t=times[-1])
                reason = s.params.is_stop(t)
                if reason is not None:
                    s.finish_reason = reason
                    s.remaining = 0                # retired next reap
            # one ENGINE step regardless of mode, so decode_steps (and
            # decode_batch_mean) mean the same thing fused and legacy
            self.stats.decode_steps += 1
            self.stats.decode_tokens += len(seqs)
            if metrics_on:
                self._h_batch.observe(len(seqs))

    def _batched_step(self, mid: str, seqs: list[DecodeSeq]) -> np.ndarray:
        """One per-model jitted forward (legacy fused=False dispatch unit);
        returns the sampled next tokens aligned with ``seqs``.
        ``decode_step`` owns all bookkeeping and has already grown the tail
        pages for the whole batch."""
        # pow2-bucket the table width: the padded columns are masked by pos,
        # so this is token-identical while bounding jit retraces at O(log)
        npages = next_pow2(max(len(s.block_table) for s in seqs))
        self.stats.decode_table_pages += len(seqs) * npages
        self.stats.decode_live_pages += sum(s.pos // self.page_size + 1
                                            for s in seqs)
        bt = np.zeros((len(seqs), npages), np.int32)
        for i, s in enumerate(seqs):
            bt[i, :len(s.block_table)] = s.block_table
        toks = jnp.asarray([s.next_token for s in seqs], jnp.int32)
        pos = jnp.asarray([s.pos for s in seqs], jnp.int32)
        nxt, new_state = self.decoders[mid].step(
            toks, pos, self.kvpool.decode_state(), jnp.asarray(bt),
            *sampling_arrays(seqs))
        self.kvpool.absorb_decode_state(new_state)
        self.stats.decode_dispatches += 1
        return np.asarray(nxt)

    def _relay_publish(self, s: DecodeSeq) -> set:
        """Publish a FINISHED sequence's resident KV into the radix tree,
        keyed by its full token stream (prompt ⧺ first decode input ⧺
        generated tokens bar the last, whose KV was never written) — the
        zero-copy handoff run in reverse. Returns the set of page ids the
        tree adopted; ``_finish`` keeps those (unref -> CACHED, evictable,
        reusable by ANY model) and hard-drops the rest as before. Only
        relay-compatible decoders publish (KV bit-identical to the frozen
        base — ``_relay_compatible``); everything else, plus aborts (which
        never reach here), behaves exactly as without relay."""
        if not (self.relay and s.tokens and s.out):
            return set()
        dw = self.decoders.get(s.model_id)
        if dw is None or not dw.relay_compatible:
            self.stats.relay_skipped += 1
            return set()
        # position p holds the KV of the token INPUT at p: prompt tokens at
        # 0..n-1, the handoff's first decode input at n, out[:-1] after —
        # len(stream) == s.pos, and only full pages are indexable
        stream = list(s.tokens) + [s.first0] + [int(t) for t in s.out[:-1]]
        full = s.pos // self.page_size
        adopted = set(self.prefix_index.insert_pages(
            stream, s.block_table[:full], provenance="relay"))
        if adopted:
            self.stats.relay_publishes += 1
            self.stats.relay_pages_published += len(adopted)
        return adopted

    def _finish(self, s: DecodeSeq) -> None:
        self._results[s.rid] = np.asarray(s.out, np.int32)
        adopted = self._relay_publish(s)
        self.block_pool.unref(s.shared_blocks)   # freed only w/ last holder
        if adopted:
            # relay-published pages stay resident (CACHED, LRU-evictable,
            # tree-served); duplicates/partial tail are dropped as before
            self.block_pool.unref([b for b in s.private_blocks
                                   if b in adopted])
            self.block_pool.drop([b for b in s.private_blocks
                                  if b not in adopted])
        else:
            self.block_pool.drop(s.private_blocks)   # generated KV: private
        self._on_request_done(s.rid, s.finish_reason or FINISH_LENGTH)

    # ------------------------------------------------------------------
    def invoke(self, sid: int, context_tokens, model_id: str,
               gen_tokens: int, first_token: int = 2) -> np.ndarray:
        """DEPRECATED legacy surface: one blocking greedy invocation against
        a caller-managed session id. Use ``generate(...).result()`` or a
        ``shared_context`` instead; this shim stays token-identical to that
        path (asserted in tests/test_api.py)."""
        warnings.warn(
            "LocalDisaggEngine.invoke() is deprecated; use "
            "engine.generate(model_id, tokens, SamplingParams(...)).result() "
            "or engine.shared_context(prefix).generate(...) instead",
            DeprecationWarning, stacklevel=2)
        params = SamplingParams(max_tokens=gen_tokens)
        if not self.paged:
            toks, _ = self._invoke_dense(sid, context_tokens, model_id,
                                         params, first_token)
            return toks
        rid = self._submit(sid, context_tokens, model_id, params, first_token)
        self.run()
        return self.pop_result(rid)

    def _check_rid(self, rid: int) -> None:
        if rid in self._results:
            return
        if rid in self._fetched:
            raise KeyError(
                f"request {rid}: result was already fetched via pop_result()")
        if rid in self._aborted:
            raise KeyError(
                f"request {rid}: aborted — no result was produced (streamed "
                f"tokens, if any, live on its RequestOutput handle)")
        if 0 <= rid < self._next_rid:
            raise KeyError(
                f"request {rid}: submitted but not finished — still waiting, "
                f"prefilling, or decoding; drive the engine with run()/step()")
        raise KeyError(
            f"request {rid}: unknown request id (ids 0..{self._next_rid - 1} "
            f"have been issued)")

    def result(self, rid: int) -> np.ndarray:
        """Return the finished output for ``rid`` WITHOUT consuming it —
        repeated calls return the same array; the entry is retained until an
        explicit ``pop_result``. Raises a KeyError naming the rid and its
        fetch state (pending / already-popped / unknown) instead of a bare
        lookup failure."""
        self._check_rid(rid)
        return self._results[rid]

    def pop_result(self, rid: int) -> np.ndarray:
        """Fetch and release the finished output for ``rid`` (frees the
        engine-side copy; a second pop raises a descriptive KeyError)."""
        self._check_rid(rid)
        self._fetched.add(rid)
        return self._results.pop(rid)

    def _invoke_dense(self, sid, context_tokens, model_id, params,
                      first_token):
        self.models.check_serving(model_id)
        worker = self._pick_worker(
            sid, [int(t) for t in np.asarray(context_tokens)])
        sc = worker.prefill(sid, context_tokens)
        dw = self.decoders[model_id]
        HandoffChannel.check(self.schema, dw.expected_schema)
        cache = transfer_cache(sc.cache)               # decode-side copy
        plan = self.handoff.plan(sc.n_tokens)
        self.stats.handoffs += 1
        self.stats.handoff_bytes += plan.bytes
        return dw.generate(cache, sc.n_tokens, first_token, params)

    def end_session(self, sid: int):
        for w in self.prefill_workers:
            w.end_session(sid)
