"""The engine step's profiler spans and its named programs: under
``jax.profiler``, every ``ps.*`` span of the vocabulary lands on the host
plane inside a ``ps.step``, with a ``ps.run.<program>`` span around each
program's dispatch; ``donating_jit`` names its program and counts its
traces."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import ModelConfig
from repro.models import init_params
from repro.runtime import PROGRAM_TRACES, donating_jit, program_traces
from repro.serving.api import SamplingParams
from repro.serving.engine import LocalDisaggEngine
from repro.serving.metrics import PS_RUN, PS_SPANS, PS_STEP

CFG = ModelConfig(name="spans-eng", arch_type="dense", n_layers=2,
                  d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
                  vocab_size=64, dtype="float32")


def _host_events(path: str) -> list:
    """(thread, name, start, end) of every host event in one trace."""
    [pb] = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(pb).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(line.name, e.name, int(e.start_ns),
                         int(e.start_ns + e.duration_ns))
                        for e in line.events]
    return out


@pytest.fixture(scope="module")
def traced_steps(tmp_path_factory):
    """A tiny chunked engine with two decode modules, a fan-out over one
    prompt and a follow-up turn, stepped to the end under the profiler."""
    eng = LocalDisaggEngine(CFG, init_params(CFG, jax.random.PRNGKey(0)),
                            num_pages=64, page_size=4, chunked=True,
                            chunk_size=8, token_budget=16)
    for i, m in enumerate(("a", "b")):
        eng.models.register(m, init_params(CFG, jax.random.PRNGKey(1 + i)))
    rng = np.random.default_rng(0)
    prompt = list(rng.integers(4, 60, size=27))
    path = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(path):
        outs = [eng.generate(m, prompt, SamplingParams(max_tokens=3))
                for m in ("a", "b")]
        eng.run()
        outs.append(eng.generate("a", prompt + outs[0].tokens + [5, 6],
                                 SamplingParams(max_tokens=2)))
        eng.run()
    assert all(o.finished for o in outs)
    return _host_events(path)


def test_every_step_span_appears_inside_a_step(traced_steps):
    steps = [(th, a, b) for th, n, a, b in traced_steps if n == PS_STEP]
    assert steps
    ours = [e for e in traced_steps if e[1].startswith("ps.")]
    assert set(PS_SPANS) <= {n for _, n, _, _ in ours}
    for th, name, a, b in ours:
        if name != PS_STEP:
            assert any(t == th and s <= a and b <= e for t, s, e in steps), \
                name


def test_programs_run_inside_named_dispatch_spans(traced_steps):
    names = {n for _, n, _, _ in traced_steps}
    assert {PS_RUN + "decode_step", PS_RUN + "chunk_step"} <= names
    # the jitted functions carry the program's name
    assert {"PjitFunction(decode_step)", "PjitFunction(chunk_step)"} <= names


def test_donating_jit_counts_traces_per_program_name():
    step = donating_jit(lambda x, k: x * k, (0,), name="spans_test_scale",
                        static_argnums=(1,))
    before = PROGRAM_TRACES["spans_test_scale"]
    for n in (4, 4, 8, 4):
        step(jnp.ones((n,)), 2)
    assert program_traces()["spans_test_scale"] - before == 2
    step(jnp.ones((4,)), 3)                # a static argument is a key
    assert program_traces()["spans_test_scale"] - before == 3
    assert step.__wrapped__(2.0, 3) == 6.0
