"""Continuous token-budget step scheduler: the engine's run loop.

Every engine step packs, under one per-step token budget:
  - one decode token for EVERY active sequence (decode is never starved —
    each active sequence reserves one budget token), and
  - as many prefill CHUNKS as fit the remaining budget, split off waiting
    prompts at ``chunk_size`` granularity in policy order (fcfs/priority).

Long prompts therefore stop head-of-line-blocking the decode plane: a 10k
prompt becomes many budget-sized slices interleaved with everyone else's
decode steps, instead of one monolithic forward that stalls every sequence
behind it (the paper's prefill-decode interference, and the top ROADMAP item).

Data plane per chunk: pages are allocated CHUNK-GRANULARLY (``CacheManager
.extend`` — only the pages this chunk spills into, so a request's pool
footprint grows with progress, not with prompt length), and the chunk runs
through ``base_prefill_chunk``: one jitted forward in which each layer
scatters its fresh K/V into the pages and attends prefix+self straight from
the pool via ``flash_prefill_paged`` — no dense gather of the prefix, ever.
Equal-length chunks from different requests batch into ONE base-model
forward.

Backpressure is wired to the existing pool machinery: ``PoolExhausted`` on a
chunk's page growth (or on the handoff's CoW clone) holds that request —
pages it already computed stay put — and retries after decode steps free
pages; a step that can make no progress at all raises ``PoolExhausted``
rather than spinning.

The same object also runs the legacy eager mode (chunking off): ``submit``
prefills whole prompts synchronously and the scheduler's step is decode-only
— semantically today's engine, which is what the chunked path is tested
bit-identical against.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.prefillshare import base_prefill_chunk
from repro.kvcache.blocks import PoolExhausted
from repro.serving.decode import next_pow2
from repro.serving.metrics import (PS_CHUNK_BUILD, PS_CHUNK_WAIT,
                                   PS_SCHED_ADMIT, PS_SCHED_PLAN,
                                   PS_SCHED_PROMOTE, PS_SCHED_REAP,
                                   PS_SCHED_SYNC, PS_STEP, SPAN_ROUTED)
from repro.serving.scheduler.queue import POLICIES, order_requests


@dataclass
class SchedulerConfig:
    token_budget: int = 256      # per-step cap: decode tokens + chunk tokens
    chunk_size: int = 64         # max prefill tokens per request per step
    policy: str = "fcfs"         # fcfs | priority (queue.py)
    cached_first: bool = True    # chunk-budget order: cached-history prefills
                                 # before cold prompts within a priority class
                                 # (PPD; see queue.py — schedule-only, token
                                 # streams stay bit-identical)

    def __post_init__(self):
        assert self.token_budget > 0 and self.chunk_size > 0
        assert self.policy in POLICIES, self.policy


@dataclass
class SchedStats:
    steps: int = 0
    chunks: int = 0
    chunk_tokens: int = 0
    stalls: int = 0              # chunk/handoff attempts deferred on pool pressure
    max_prefill_batch: int = 0   # widest batched chunk forward


@dataclass(eq=False)             # identity equality: list.remove stays O(1)
class Request:
    """One submitted generation request moving WAITING -> PREFILL -> DECODE."""
    rid: int
    sid: int
    model_id: str | None         # None: prefill-only (gen_tokens == 0)
    tokens: list
    gen_tokens: int
    first_token: int
    priority: int
    seq: int                     # arrival order (fcfs tiebreak)
    params: object = None        # SamplingParams (None on internal paths)
    tok_hash: int = 0            # precomputed hash of tokens (sibling check)
    worker: object = None        # PrefillWorker, assigned at admission
    alloc: object = None         # CacheManager Allocation (chunk-granular)
    block_table: list = field(default_factory=list)
    done: int = 0                # tokens whose KV is in pages (incl. cached)
    committed: bool = False      # published to the radix index / session
    sibling_bt: list | None = None   # identical-context fast path block table
    resume_seq: object = None    # preempted DecodeSeq this request restores
                                 # (drop-and-recompute path, serving/preempt)
    tok_array: np.ndarray = field(init=False, repr=False)  # tokens, numpy

    def __post_init__(self):
        self.tok_hash = hash(tuple(self.tokens))
        self.tok_array = np.asarray(self.tokens, np.int32)

    @property
    def n(self) -> int:
        return len(self.tokens)

    @property
    def cached_tokens(self) -> int:
        """Prompt tokens served from the prefix cache at admission — the
        cached-history vs cold classification signal (queue.py)."""
        if self.sibling_bt is not None:
            return self.n
        return self.alloc.cached_tokens if self.alloc is not None else 0


class ChunkedScheduler:
    """Owns the engine step loop (both chunked and legacy-eager modes)."""

    def __init__(self, engine, cfg: SchedulerConfig):
        self.engine = engine
        self.cfg = cfg
        self.waiting: list[Request] = []
        self.prefilling: list[Request] = []
        self.active: list = []           # DecodeSeqs (engine dataclass)
        self.stats = SchedStats()
        self.promoted: list[int] = []    # rids in prefill-completion order

    # ------------------------------------------------------------------
    def add(self, req: Request) -> None:
        self.waiting.append(req)

    def add_decode_seq(self, seq) -> None:
        """Register an already-prefilled sequence (legacy eager submit)."""
        self.active.append(seq)

    def has_work(self) -> bool:
        return bool(self.waiting or self.prefilling or self.active
                    or self._swap_parked())

    def _swap_parked(self) -> bool:
        """Swap-mode preemption victims parked off the step loop — still the
        engine's work (they resume and finish) even though they sit in none
        of the three queues."""
        swap = self.engine.swap
        return swap is not None and swap.parked

    def run(self) -> None:
        while self.has_work():
            self.step()

    # ------------------------------------------------------------------
    def step(self) -> None:
        """One engine step: reap finished sequences (EOS/stop/length/abort —
        their budget slots and pages free BEFORE this step's packing); admit;
        pack prefill chunks under the budget; promote finished prefills
        (zero-copy handoff); advance every active sequence one decode
        token. Each phase runs inside its ``ps.*`` profiler span."""
        with TraceAnnotation(PS_STEP):
            self.stats.steps += 1
            with TraceAnnotation(PS_SCHED_REAP):
                progress = self._reap_finished()
            with TraceAnnotation(PS_SCHED_SYNC):
                # step boundary: apply deferred model churn (finalize
                # drained unregisters — the reap above may have retired a
                # draining model's last sequence — and relayout the fused
                # plane; live sequences' lane indices are re-derived from
                # the new plane this same step, so surviving requests
                # decode bit-identically across the churn)
                self.engine.models.sync()
                # same boundary: observe queue depth / pool occupancy into
                # the metrics registry, then let the autoscaler resize the
                # prefill pool or the decode admission reserve off those
                # signals — worker-set mutations are only legal here,
                # exactly like model churn
                self.engine._observe_step()
                self.engine._autoscale_tick()
            with TraceAnnotation(PS_SCHED_ADMIT):
                progress += self._admit()
            with TraceAnnotation(PS_SCHED_PLAN):
                progress += self._oversub_phase()
                budget = self.cfg.token_budget - len(self.active)
                chunks = self._plan_chunks(budget)
            progress += self._run_chunks(chunks)
            with TraceAnnotation(PS_SCHED_PROMOTE):
                progress += self._promote()
            with TraceAnnotation(PS_SCHED_PLAN):
                progress += self._tail_growth_guard()
            progress += self._decode_phase()
            if self.engine.sanitizer is not None:
                # step boundary: every transient ref/alloc has settled, so
                # the pool/index/holder cross-check must hold exactly here
                self.engine.sanitizer.check_step()
            if progress == 0 and (self.waiting or self.prefilling
                                  or self._swap_parked()):
                if self.engine.sched_reserve_extra > 0:
                    # the autoscaler's extra decode headroom is advisory —
                    # it must never wedge the engine. If it is the only
                    # thing blocking progress, give it back and retry next
                    # step.
                    self.engine.sched_reserve_extra = 0
                    return
                swap = self.engine.swap
                raise PoolExhausted(
                    f"scheduler stalled: {len(self.waiting)} waiting / "
                    f"{len(self.prefilling)} prefilling / "
                    f"{len(swap.records) if swap is not None else 0} "
                    f"swapped-out requests cannot obtain pages and no "
                    f"decode is active to free any")

    # ---- oversubscription (serving/preempt.py) -------------------------
    def _oversub_phase(self) -> int:
        """After admission, before chunk packing: resume parked victims when
        pages allow, then preempt low-priority decodes when the highest-
        priority pending request is page-blocked. Runs before the budget is
        computed so a resumed sequence claims its decode slot this step."""
        swap = self.engine.swap
        if swap is None:
            return 0
        progress = swap.resume_step(self)
        progress += swap.preempt_step(self)
        return progress

    def _tail_growth_guard(self) -> int:
        """Right before decode: with overcommit the admission reserve is
        deliberately under-scaled, so the pool may lack the tail pages the
        coming decode step must allocate — evict victims until it cannot
        fail mid-flight."""
        swap = self.engine.swap
        if swap is None:
            return 0
        return swap.grow_guard(self)

    # ---- admission ----------------------------------------------------
    def _admit(self) -> int:
        admitted = 0
        for r in order_requests(list(self.waiting), self.cfg.policy):
            # hold a request whose identical context is already in flight:
            # once the leader promotes, the session fast path serves it
            # without recomputing (mirrors the eager sibling-submit path).
            # Hash-only compare: a collision just delays admission one step;
            # the session fast path below rechecks the exact tokens.
            if any(p.sid == r.sid and p.tok_hash == r.tok_hash
                   for p in self.prefilling):
                continue
            self.waiting.remove(r)
            w = self.engine._pick_worker(r.sid, r.tokens)
            r.worker = w
            t_routed = time.perf_counter()
            sc = w.sessions.get(r.sid)
            sibling = sc is not None and sc.tokens == r.tokens
            shared = 0 if sibling else self._in_flight_prefix(r)
            if sibling:
                # identical-context sibling: the session's pages already hold
                # it — no allocation, no chunks, straight to promote. Pin the
                # pages NOW (promotion may be deferred under pool pressure,
                # and the leader session could end in that window, leaving
                # them evictable); the pin is dropped after the handoff takes
                # its own refs.
                self.engine.block_pool.ref(sc.block_table)
                w.mgr.record_hit(r.n)
                self.engine.stats.prefill_tokens_reused += r.n
                r.sibling_bt = list(sc.block_table)
                r.done = r.n
            else:
                r.alloc = w.mgr.begin(r.tokens)
                r.block_table = list(r.alloc.cached_blocks)
                r.done = r.alloc.cached_tokens
                self.engine.stats.prefill_tokens_reused += r.done
                w.pending_chunk_tokens += r.n - r.done
            if r.resume_seq is not None:
                # drop-and-recompute restore: the cache-cold tail of the
                # victim's stream is genuine recompute work
                self.engine.stats.recompute_tokens += r.n - r.done
            self.prefilling.append(r)
            admitted += 1
            # prompt tokens this request computes that another request in
            # flight is computing too; the routed event's time is the pick
            self.engine.stats.prefill_tokens_duplicated += max(
                0, shared - r.done)
            self.engine.metrics_registry.trace(r.rid).event(
                SPAN_ROUTED, t=t_routed, worker=w.wid, cached=r.done)
        return admitted

    def _in_flight_prefix(self, r: Request) -> int:
        """Longest prefix ``r`` shares with an admitted request that has not
        committed yet: tokens another request in flight is already
        computing, which the radix tree cannot serve ``r`` until it
        commits."""
        best = 0
        for p in self.prefilling:
            if p.committed or p.sibling_bt is not None:
                continue
            n = min(r.n, p.n)
            diff = np.flatnonzero(r.tok_array[:n] != p.tok_array[:n])
            best = max(best, int(diff[0]) if diff.size else n)
        return best

    # ---- prefill chunk packing ----------------------------------------
    def _plan_chunks(self, budget: int):
        """Split pending prompts into (request, start, take) chunks, policy
        order, chunk-granular page growth; pool pressure defers a request."""
        page = self.engine.page_size
        chunks = []
        # prefill never takes the pool below the pages active decodes are
        # still entitled to (worst-case tail growth, overcommit-scaled) plus
        # the autoscaler's extra decode headroom, so chunking cannot starve
        # the decode plane mid-flight
        reserve = self._reserve_target()
        pool = self.engine.block_pool
        pending = [r for r in self.prefilling
                   if r.done < r.n and r.sibling_bt is None]
        # cached-history prefills pack ahead of cold prompts (within a
        # priority class): their remaining cold work is a chunk or two, so
        # they reach decode immediately instead of queueing behind cold long
        # prompts' many-step prefills (PPD classification, queue.py)
        for r in order_requests(pending, self.cfg.policy,
                                cached_first=self.cfg.cached_first):
            if budget <= 0:
                break
            take = min(self.cfg.chunk_size, r.n - r.done, budget)
            need = -(-(r.done + take) // page) - len(r.block_table)
            if need > 0:
                if pool.free_count - need < reserve:
                    self.stats.stalls += 1
                    continue          # hold; decode may free pages
                try:
                    fresh = r.worker.mgr.extend(r.alloc, need)
                except PoolExhausted:
                    self.stats.stalls += 1
                    continue
                r.block_table.extend(fresh)
            chunks.append((r, r.done, take))
            budget -= take
        return chunks

    def _run_chunks(self, chunks) -> int:
        """Execute planned chunks; equal-length chunks from different
        requests run as ONE batched base-model forward over the pool."""
        if not chunks:
            return 0
        eng = self.engine
        groups: dict[int, list] = {}
        for r, start, take in chunks:
            groups.setdefault(take, []).append((r, start))
        for S, items in groups.items():
            B = len(items)
            # bucket the chunk block-table width to the next power of two,
            # exactly like the fused decode step buckets decode tables:
            # table growth WITHIN a bucket reuses the jitted chunk-step
            # trace, so prefill retraces stop scaling with prefix length
            # (padding = sentinel page 0, never live KV)
            # the tables and their transfer are ps.chunk.build; the jitted
            # call's dispatch inside it is ps.run.chunk_step
            with TraceAnnotation(PS_CHUNK_BUILD):
                npages = next_pow2(max(len(r.block_table) for r, _ in items))
                toks = np.zeros((B, S), np.int32)
                bt = np.zeros((B, npages), np.int32)
                pos = np.zeros((B,), np.int32)
                for i, (r, start) in enumerate(items):
                    toks[i] = r.tokens[start:start + S]
                    bt[i, :len(r.block_table)] = r.block_table
                    pos[i] = start
                t0 = time.perf_counter()
                out = base_prefill_chunk(eng.cfg, eng.base_params, toks,
                                         pool=eng.kvpool, block_tables=bt,
                                         pos=pos)
            with TraceAnnotation(PS_CHUNK_WAIT):
                jax.block_until_ready(out)
            dt = time.perf_counter() - t0
            for r, _ in items:
                r.done += S
                r.worker.pending_chunk_tokens -= S
                r.worker.ewma.observe(S, dt / B)
                eng.metrics_registry.trace(r.rid).event(
                    "chunk_prefilled", tokens=S, done=r.done)
            eng.stats.prefill_tokens_computed += B * S
            self.stats.chunks += B
            self.stats.chunk_tokens += B * S
            self.stats.max_prefill_batch = max(self.stats.max_prefill_batch, B)
        return len(chunks)

    def _decode_reserve(self) -> int:
        """Pages the active decode sequences are still entitled to: the
        worst-case tail growth each committed-to generation may yet need.
        Prefill chunking and decode admission both stay above this line, so
        a running generation can never hit PoolExhausted mid-flight."""
        page = self.engine.page_size
        return sum(
            max(0, -(-(s.pos + s.remaining) // page) - len(s.block_table))
            for s in self.active)

    def _reserve_target(self) -> int:
        """Admission floor: the decode reserve, scaled down by the
        oversubscription factor when preemption is armed — with victims as
        the escape hatch the pool may admit beyond the strict worst case,
        which is exactly the paper's oversubscription lever."""
        reserve = self._decode_reserve()
        swap = self.engine.swap
        if swap is not None and swap.cfg.overcommit > 1.0:
            reserve = -(-reserve // swap.cfg.overcommit)
        return int(reserve) + self.engine.sched_reserve_extra

    # ---- prefill -> decode handoff -------------------------------------
    def _commit_request(self, r: Request) -> None:
        """Publish a fully-prefilled (non-sibling) request for prefix reuse
        + session bookkeeping, exactly once (promotion may retry under pool
        pressure)."""
        if r.committed:
            return
        from repro.serving.engine import PagedSession
        w = r.worker
        w.mgr.commit(r.tokens, r.alloc)
        old = w.sessions.get(r.sid)
        w.sessions[r.sid] = PagedSession(
            r.alloc, list(r.block_table), r.n, list(r.tokens))
        if old is not None:
            w.mgr.release(old.alloc)
        r.committed = True

    def _promote(self) -> int:
        promoted = 0
        page = self.engine.page_size
        pool = self.engine.block_pool
        for r in list(self.prefilling):
            if r.done < r.n:
                continue
            if r.gen_tokens == 0:
                # prefill-only request (SharedContext warm-up): commit the
                # session and finish — no decode model, no handoff, no CoW
                if r.sibling_bt is not None:
                    pool.unref(r.sibling_bt)
                else:
                    self._commit_request(r)
                self.prefilling.remove(r)
                self.promoted.append(r.rid)
                self.engine._finish_prefill_only(r.rid)
                promoted += 1
                continue
            # decode admission control: the handoff's CoW clone plus THIS
            # sequence's worst-case tail growth must fit above the pages
            # already-running decodes are entitled to — otherwise admitting
            # it could deadlock every generation mid-flight
            cow = 1 if r.n % page else 0
            growth = -(-(r.n + r.gen_tokens) // page) - (-(-r.n // page))
            if pool.free_count - cow - growth < self._reserve_target():
                self.stats.stalls += 1
                continue
            bt = r.sibling_bt
            if bt is None:
                self._commit_request(r)
                bt = r.block_table
            try:
                seq = self.engine._handoff_seq(
                    bt, r.n, r.sid, r.model_id, r.params,
                    r.first_token, r.rid, tokens=r.tokens,
                    priority=r.priority)
            except PoolExhausted:
                self.stats.stalls += 1   # CoW clone page unavailable: retry
                continue
            if r.sibling_bt is not None:
                pool.unref(r.sibling_bt)   # handoff holds its own refs now
            self.prefilling.remove(r)
            self.active.append(seq)
            if r.resume_seq is not None:
                # drop-and-recompute restore: graft the preempted victim's
                # identity onto the re-prefilled sequence; the rid already
                # completed its public prefill, so it is not re-promoted
                self.engine.swap.finish_recompute_resume(r, seq)
            else:
                self.promoted.append(r.rid)
            promoted += 1
        return promoted

    # ---- decode --------------------------------------------------------
    def _reap_finished(self) -> int:
        """Retire sequences whose generation is over — length exhausted OR
        terminated early by an eos/stop token (engine.decode_step zeroes
        ``remaining``). Runs at the TOP of every step, so an early finish
        frees its token-budget slot, its decode-reserve pages, and its pool
        pages before this step's packing decisions."""
        still = []
        finished = 0
        for s in self.active:
            if s.remaining > 0:
                still.append(s)
            else:
                self.engine._finish(s)
                finished += 1
        self.active = still
        return finished

    def _decode_phase(self) -> int:
        """Advance every active sequence one token. Model grouping is the
        ENGINE's concern now: the fused decode plane batches all models
        sharing a config into one jitted step (engine.decode_step), so
        the scheduler no longer splits the batch by model.

        The engine steps a COPY of the active list: stream callbacks fire
        inside decode_step's bookkeeping loop and may re-enter the engine —
        abort() removes from ``self.active``, an eager generate() appends to
        it — and either mutation mid-enumeration would cross-wire the step's
        token/sequence alignment."""
        if not self.active:
            return 0
        stepped = list(self.active)
        self.engine.decode_step(stepped)
        return len(stepped)          # self.active may have shrunk mid-step
