"""One run of one cell: set up the deployment, offer the cell's open-loop
traffic, measure a window, follow every request due in it to its end, then
check what the timed path served against the float32 reference.

The traffic drives the engine only through what a user calls:
``LocalDisaggEngine``, ``engine.models.register``, ``engine.generate``,
``engine.step``, ``engine.stats``, the request handles and the metrics
registry's request traces. Set-up alone reaches further: ``bench/warm.py``
makes the step shapes ready through the engine's own step objects, because
the program compiles a program per shape and offers no public call that
warms them; where that fails, the warm-up traffic alone stands in.

The harness marks its own host spans for the trace: ``bench.step`` around
``engine.step``, ``bench.generate`` around each submission, ``bench.wait``
around the wait for the next arrival, ``bench.client`` around its own
bookkeeping, and ``bench.window`` around the measured window.
"""
from __future__ import annotations

import gc
import heapq
import os
import sys
import tempfile
import time
from dataclasses import dataclass, field

import jax
import numpy as np

from bench import model as bmodel
from bench import tracefile
from bench import traffic as btraffic
from bench import warm as bwarm
from bench.peaks import peaks

#: the compile cache lives at this fixed path inside the checkout
CACHE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".bench_cache", "jax")
FIRST_TOKEN = 2                 # the engine's first decode input
WAIT_SLICE_S = 0.002            # longest idle sleep between arrival checks


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def setup_compile_cache() -> None:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Programs compiled or loaded from the persistent cache, by JAX's own
    monitoring events."""

    def __init__(self):
        self.compiles = 0
        self.cache_loads = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_loads += 1

    @property
    def programs(self) -> int:
        return self.compiles + self.cache_loads


@dataclass
class Request:
    """One request as the client sees it. Times are ``perf_counter``."""
    stream: str
    session: str
    turn: int
    module: str
    due: float
    prompt: list
    max_tokens: int
    handle: object = None
    trace: object = None

    @property
    def finished(self) -> bool:
        return self.handle is not None and self.handle.finished


@dataclass
class RequestRecord:
    """What a metric reader sees of one request, the engine let go."""
    stream: str
    module: str
    due: float
    prompt_len: int
    max_tokens: int
    finished: bool
    served: list
    token_times: list
    routed: float | None
    chunks: list                 # [(t, tokens, done)]
    prompt: object = None        # token ids (window requests only)


@dataclass
class Run:
    """The record of one run: what every metric reader reads."""
    cell: str
    seconds: float
    setup_s: float
    t_open: float
    t_close: float
    drain_end: float
    window: list                 # RequestRecords due in the window
    requests: list               # every RequestRecord of the run
    stats_open: dict
    stats_close: dict
    pages_peak: int
    pool_pages: int
    compiles_window: int
    shapes: object               # the family's shapes of the configuration
    peaks: dict | None
    trace: object = None         # TraceView in a traced run
    notes: dict = field(default_factory=dict)
    family: object = None        # the configuration's bench/families module


@dataclass
class TraceView:
    rec: dict                    # bench.tracefile compact record
    t0_ns: int                   # the window on the trace's clock
    t1_ns: int
    perf_open: float             # perf_counter at t0_ns


def _stats(engine) -> dict:
    """Every counter of ``engine.stats()``, and five of them again under the
    short names the readers use."""
    st = engine.stats
    return {**engine.stats(),
            "computed": st.prefill_tokens_computed,
            "reused": st.prefill_tokens_reused,
            "decode_tokens": st.decode_tokens,
            "decode_steps": st.decode_steps,
            "duplicated": getattr(st, "prefill_tokens_duplicated", None)}


def _record(r: Request) -> RequestRecord:
    h = r.handle
    routed, chunks = None, []
    if r.trace is not None:
        for name, t, attrs in r.trace.events:
            if name == "routed" and routed is None:
                routed = t
            elif name == "chunk_prefilled":
                chunks.append((t, attrs["tokens"], attrs["done"]))
    return RequestRecord(
        stream=r.stream, module=r.module, due=r.due, prompt_len=len(r.prompt),
        max_tokens=r.max_tokens, finished=bool(h is not None and h.finished),
        served=list(h.tokens) if h is not None else [],
        token_times=list(h.token_times) if h is not None else [],
        routed=routed, chunks=chunks)


class Driver:
    """The open-loop client: submits each request when it is due, steps the
    engine while it has work, and schedules a session's next turn when all
    of the turn's replies have finished."""

    def __init__(self, engine, system, modules, params_cls):
        self.engine = engine
        self.system = system
        self.modules = modules
        self.params_cls = params_cls
        self.due = []            # heap of (due, seq, stream, session, turn)
        self._seq = 0
        self.inflight = {}       # (stream, sid, turn) -> [Request]
        self.requests = []
        self.pages_peak = 0
        self.sample_pages = False

    def add_sessions(self, sessions, stream, t0):
        for s in sessions:
            self._push(t0 + s.arrival, stream, s, 0)

    def _push(self, due, stream, session, turn):
        heapq.heappush(self.due, (due, self._seq, stream, session, turn))
        self._seq += 1

    def _submit(self, due, stream, session, turn):
        reqs = []
        for m in self.modules:
            r = Request(stream=stream, session=session.sid, turn=turn,
                        module=m, due=due,
                        prompt=session.prompt(self.system, turn, m),
                        max_tokens=session.replies[turn][m])
            with jax.profiler.TraceAnnotation("bench.generate"):
                r.handle = self.engine.generate(
                    m, r.prompt, self.params_cls(max_tokens=r.max_tokens))
            r.trace = self.engine.metrics_registry.trace(r.handle.request_id)
            reqs.append(r)
        self.requests += reqs
        self.inflight[(stream, session.sid, turn)] = (session, reqs)

    def _reap(self):
        for key, (session, reqs) in list(self.inflight.items()):
            if all(r.finished for r in reqs):
                del self.inflight[key]
                stream, _, turn = key
                if turn + 1 < session.turns:
                    done = max(r.handle.token_times[-1] for r in reqs)
                    self._push(done, stream, session, turn + 1)

    def tick(self, now):
        """Submit what is due, then one engine step or a short wait."""
        while self.due and self.due[0][0] <= now:
            due, _, stream, session, turn = heapq.heappop(self.due)
            self._submit(due, stream, session, turn)
        if self.inflight:        # a submitted request has not finished
            with jax.profiler.TraceAnnotation("bench.step"):
                self.engine.step()
            with jax.profiler.TraceAnnotation("bench.client"):
                if self.sample_pages:
                    self.pages_peak = max(self.pages_peak,
                                          self.engine.stats()["pages_active"])
                self._reap()
        else:
            nxt = self.due[0][0] if self.due else now + WAIT_SLICE_S
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(min(max(nxt - time.perf_counter(), 0.0),
                               WAIT_SLICE_S))


def _sample(window: list, seed: int, tokens: int) -> list:
    """Finished window requests to check, drawn from the seed: the one with
    the longest reply, then others until ``tokens`` served tokens."""
    done = [r for r in window if r.finished and r.served]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: (len(done[i].served),
                                                    done[i].prompt_len))
    rng = np.random.default_rng([seed % (1 << 63), 7])
    order = [longest] + [i for i in rng.permutation(len(done))
                         if i != longest]
    out, total = [], 0
    for i in order:
        out.append(done[i])
        total += len(done[i].served)
        if total >= tokens:
            break
    return out


def check(conf: dict, seed: int, run: Run, control: bool = False) -> dict:
    """The comparison that decides ``correct``: the widest gap by which a
    served token's float32 reference logit lies below the reference's best,
    over a seeded sample of finished window requests."""
    from bench.reference import logit_gaps
    want = conf["check"]["sample_tokens"]
    sample = _sample(run.window, seed, want)
    items = [{"module": r.module, "prompt": r.prompt.tolist(),
              "served": r.served, "first_token": FIRST_TOKEN}
             for r in sample]
    gaps = logit_gaps(conf, seed, items, control=control) if items else []
    widest = max((float(g["gap"].max()) for g in gaps), default=float("inf"))
    out = {"logit_gap": widest,
           "sampled_tokens": sum(len(r.served) for r in sample),
           "sampled_requests": len(sample),
           "longest_prompt": max((r.prompt_len for r in sample), default=0)}
    if control:
        out["control_gap"] = max((float(g["control_gap"].max())
                                  for g in gaps), default=float("inf"))
    return out


class Deployment:
    """The configuration's modules in one engine, the shared system prompt
    prefilled and every step shape the traffic reaches made ready."""

    def __init__(self, conf: dict, traffic: dict, seed: int, t_start: float,
                 fault=None, shape_warmup: bool = True):
        from repro.serving.api import SamplingParams
        from repro.serving.engine import LocalDisaggEngine
        from repro.serving.registry import DecodeModelSpec
        self.conf, self.seed = conf, seed
        self.family = bmodel.family(conf)
        self.shapes = self.family.shapes(conf)
        self.counter = CompileCounter()
        self.params_cls = SamplingParams
        dep = conf["deployment"]
        prefill = dep["prefill_module"]
        self.modules = sorted(m for m in dep["modules"] if m != prefill)
        weights = {m: self.family.init_module(conf, seed, m)
                   for m in dep["modules"]}
        self.engine = LocalDisaggEngine(
            self.family.program_config(conf), weights[prefill],
            num_pages=conf["pool_pages"], page_size=conf["page_size"],
            **conf["engine"])
        for m in self.modules:
            self.engine.models.register(m, DecodeModelSpec(full=weights[m]))
        del weights
        log(f"engine up at {time.perf_counter() - t_start:.3f}s, "
            f"{self.counter.programs} programs")
        if fault is not None:
            fault(self.engine)
        self.system = btraffic.system_prompt(traffic, self.shapes.vocab, seed)
        h = self.engine.generate(self.modules[0], self.system,
                                 SamplingParams(max_tokens=0))
        while not h.finished:
            self.engine.step()
        del h
        self.warm = (bwarm.run(self.engine, traffic, conf["engine"],
                               conf["page_size"], log)
                     if shape_warmup else {})
        log(f"shapes ready at {time.perf_counter() - t_start:.3f}s, "
            f"{self.counter.programs} programs")

    def serve(self, traffic: dict, seconds: float, trace: bool,
              t_start: float, name: str = "", out_dir: str | None = None,
              log_compiles: bool = False):
        """Warm-up traffic, the window, the drain; returns the Run."""
        engine, seed = self.engine, self.seed
        counter = self.counter
        drv = Driver(engine, self.system, self.modules, self.params_cls)
        warmup_s = float(traffic["warmup_seconds"])
        drain_s = float(traffic["drain_limit_seconds"])

        def arrivals(stream, t0, length):
            drv.add_sessions(btraffic.sessions(
                traffic, self.modules, self.shapes.vocab, seed, stream,
                length), stream, t0)

        trace_dir = (tempfile.mkdtemp(prefix="bench_trace_", dir=out_dir)
                     if trace else None)
        tracing = False
        t_w = time.perf_counter()
        arrivals("warmup", t_w, warmup_s)
        while time.perf_counter() < t_w + warmup_s:
            if (trace and not tracing
                    and time.perf_counter() > t_w + warmup_s - 2.0):
                opts = jax.profiler.ProfileOptions()
                opts.host_tracer_level = 2
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                tracing = True
            drv.tick(time.perf_counter())
        t_open = time.perf_counter()
        t_close = t_open + seconds
        arrivals("window", t_open, seconds)
        arrivals("post", t_close, drain_s)
        setup_s = t_open - t_start
        stats_open, c_open = _stats(engine), counter.programs
        drv.sample_pages = True
        if log_compiles:
            jax.config.update("jax_log_compiles", True)
        with jax.profiler.TraceAnnotation("bench.window"):
            perf_open = time.perf_counter()
            while time.perf_counter() < t_close:
                drv.tick(time.perf_counter())
        stats_close, c_close = _stats(engine), counter.programs
        jax.config.update("jax_log_compiles", False)
        drv.sample_pages = False
        log(f"window open at {setup_s:.3f}s after {c_open} programs; "
            f"{c_close - c_open} more in the window")

        def in_window(r):
            return t_open <= r.due < t_close
        # drain: every request due in the window to its end
        deadline = t_close + drain_s
        while time.perf_counter() < deadline and not (
                not (drv.due and drv.due[0][0] < t_close)
                and all(r.finished for r in drv.requests if in_window(r))):
            drv.tick(time.perf_counter())
        drain_end = time.perf_counter()
        log(f"drained {drain_end - t_close:.3f}s after the window")
        mem = jax.devices()[0].memory_stats() or {}
        view = None
        if tracing:
            jax.profiler.stop_trace()
            view = _trace_view(trace_dir, perf_open)
        window, records = [], []
        for r in drv.requests:
            rec = _record(r)
            if in_window(r):
                rec.prompt = np.asarray(r.prompt, np.int32)
                window.append(rec)
            records.append(rec)
        dev = jax.devices()[0]
        run = Run(cell=name, seconds=seconds, setup_s=setup_s,
                  t_open=t_open, t_close=t_close, drain_end=drain_end,
                  window=window, requests=records,
                  stats_open=stats_open, stats_close=stats_close,
                  pages_peak=drv.pages_peak, pool_pages=self.conf["pool_pages"],
                  compiles_window=c_close - c_open, shapes=self.shapes,
                  peaks=peaks(dev.device_kind) if dev.platform == "tpu"
                  else None, trace=view, family=self.family)
        run.notes.update(
            memory_peak_bytes=int(mem.get("peak_bytes_in_use", 0)),
            unsent=sum(len(self.modules) for d in drv.due if d[0] < t_close),
            warm=self.warm, programs_setup=c_open,
            compile_s=counter.compile_s,
            decode_steps=stats_close["decode_steps"] - stats_open["decode_steps"],
            host_split=(None if view is None else tracefile.host_split(
                view.rec, view.t0_ns, view.t1_ns)))
        return run

    def close(self) -> None:
        """Let go of the engine, its weights and its pool."""
        del self.engine
        gc.collect()
        live = sum(a.nbytes for a in jax.live_arrays())
        log(f"engine freed; {live} bytes of arrays still live")


def run_cell(cell, seed: int, seconds: float, trace: bool, t_start: float,
             *, fault=None, out_dir: str | None = None,
             control: bool = False, log_compiles: bool = False):
    """Run one cell once; returns (run, checks). ``fault`` (tests only)
    receives the engine before the traffic starts."""
    conf = bmodel.load(cell.config_path)
    traffic = bmodel.load(cell.traffic_path)
    dep = Deployment(conf, traffic, seed, t_start, fault=fault)
    run = dep.serve(traffic, seconds, trace, t_start, name=cell.name,
                    out_dir=out_dir, log_compiles=log_compiles)
    dep.close()
    del dep
    t0 = time.perf_counter()
    checks = with_limits(conf, check(conf, seed, run, control=control))
    log(f"reference took {time.perf_counter() - t0:.3f}s: {checks}")
    return run, checks


def with_limits(conf: dict, checks: dict) -> dict:
    """The check's readings with the configuration's limits beside them."""
    return {**checks, "limit": conf["check"]["max_logit_gap"],
            "sampled_tokens_min": conf["check"]["min_sampled_tokens"]}


def as_control(checks: dict) -> dict:
    """The readings with the control's gap in the program's place: judged
    by the same limit, they have to come out as not correct."""
    return {**checks, "logit_gap": checks["control_gap"]}


def _trace_view(trace_dir: str, perf_open: float):
    import glob
    import shutil

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    rec = tracefile.extract(paths[0])
    shutil.rmtree(trace_dir, ignore_errors=True)
    spans = [e for e in rec["host"] if e[0] == tracefile.WINDOW_SPAN]
    if not spans:
        return None
    _, t0, dur = spans[0]
    return TraceView(rec=rec, t0_ns=t0, t1_ns=t0 + dur, perf_open=perf_open)
