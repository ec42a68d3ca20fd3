"""Process-level device plumbing: backend probe, buffer donation, named
programs and their trace counts, compile cache.

Nothing here runs at import time: importing ``repro`` initialises no JAX
backend, so a launcher may still configure JAX (platform, compile cache)
before its first computation, and a process that only imports the package
never takes the accelerator.
"""
from __future__ import annotations

import functools
import os
from collections import Counter
from pathlib import Path

import jax

#: the compile cache's home when ``JAX_COMPILATION_CACHE_DIR`` is unset: a
#: fixed path inside the checkout (the path is part of the cache key, so a
#: directory that moves between runs never hits)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU: the Mosaic kernels run and
    donated buffers are reused in place. Elsewhere the kernels' jnp twins
    run and donation is off, so callers may keep reading pre-step buffers."""
    return jax.default_backend() == "tpu"


#: traces per program name, process-wide: ``donating_jit``'s wrapped body
#: runs once per trace, and each trace is one program compiled or loaded
#: from the persistent compile cache
PROGRAM_TRACES: Counter = Counter()


def program_traces() -> dict:
    """``{program name: traces so far}`` over every engine in the process."""
    return dict(PROGRAM_TRACES)


def donating_jit(fun, donate_argnums, *, name: str, **jit_kwargs):
    """``jax.jit(fun)`` named ``name`` that donates ``donate_argnums`` on
    TPU only.

    The program's XLA module reads ``jit_<name>``; each trace adds one to
    ``PROGRAM_TRACES[name]``; each call runs inside the profiler span
    ``ps.run.<name>`` (dispatch: trace, lower, compile or load, enqueue).
    The backend is asked at the first call, not at definition, so a
    module-level step can be declared without initialising a backend."""
    @functools.wraps(fun)
    def traced(*args, **kwargs):
        PROGRAM_TRACES[name] += 1
        return fun(*args, **kwargs)

    traced.__name__ = traced.__qualname__ = name
    built = []

    def call(*args, **kwargs):
        if not built:
            # imported here: the serving package imports this module
            from repro.serving.metrics import PS_RUN
            built.append((jax.jit(
                traced, donate_argnums=donate_argnums if on_tpu() else (),
                **jit_kwargs), PS_RUN + name))
        jitted, span = built[0]
        with jax.profiler.TraceAnnotation(span):
            return jitted(*args, **kwargs)

    call.__wrapped__ = fun
    return call


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, names the directory and JAX
    reads it itself; otherwise the cache lives at ``DEFAULT_CACHE_DIR``.
    Call before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, however quick its compile: a cold chip run pays
    # for each one again
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
