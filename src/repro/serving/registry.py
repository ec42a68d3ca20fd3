"""Model-lifecycle registry: hot (un)register decode models while serving.

The paper's premise is a POOL of task-specific decode modules sharing one
frozen prefill module — and production multi-LLM serving (vLLM LoRA
hot-swap, S-LoRA's adapter pools) treats that pool as dynamic: adapters
arrive and retire while traffic flows. ``engine.models`` is that surface:

    engine.models.register("summarizer", DecodeModelSpec(
        lora=LoRAAdapter(params=lora_init(key, base, rank=8))))
    engine.models.register("planner", DecodeModelSpec(full=planner_params))
    ...
    engine.models.unregister("planner", drain=True)   # or drain=False

Lifecycle semantics:
  - ``register`` takes effect for NEW requests immediately; the fused decode
    plane is rebuilt at the next STEP BOUNDARY (``sync``, called by the
    scheduler at the top of every step), never mid-step. Live sequences are
    addressed by model id, and the rebuilt plane re-derives every sequence's
    model-lane index per step, so a churn event remaps lanes without
    touching any sequence's pages or sampling keys — surviving requests'
    outputs are bit-identical across the churn (tests/test_registry.py).
  - ``unregister(drain=True)`` stops NEW work instantly (first-class
    ``UnknownModelError``) but lets in-flight requests (waiting, prefilling,
    decoding) finish; the model's lane is dropped from the plane once the
    last one retires.
  - ``unregister(drain=False)`` aborts the model's in-flight requests
    through the engine's existing abort path (every page refcount returns to
    baseline) and removes the model at the next step boundary.

Weight layout per spec kind (serving/decode.py):
  - ``full=params``: the model's own pytree drives one lane of the step.
  - ``lora=LoRAAdapter(...)``: only the (tiny) stacked A/B factors are
    stored; the frozen base weights are the ENGINE's single copy, and the
    merge ``W + scale * A[m] @ B[m]`` happens inside the jitted decode
    step — one base copy + N adapter sets instead of N full models
    (Eq. 9 on the weight side).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

from repro.core.lora import lora_apply
from repro.serving.api import UnknownModelError


@dataclass(frozen=True)
class LoRAAdapter:
    """An adapter-factored decode module: ``W_eff = W + (alpha/rank)·A@B``
    over the engine's frozen base weights. ``params`` is a ``lora_init``-
    style pytree (``LoRAPair`` at targeted weights, None elsewhere)."""
    params: Any
    alpha: float = 16.0
    rank: int = 8

    @property
    def scale(self) -> float:
        return self.alpha / self.rank


class DecodeModelSpec:
    """How a registered decode model stores its weights: exactly one of

    - ``full=params``   — a complete param pytree (the paper's full
      fine-tunes); the fused plane runs one lane over it.
    - ``lora=LoRAAdapter(...)`` — base + low-rank factors; the fused plane
      stores only the stacked factors and merges inside the step.
    """

    def __init__(self, full: Any = None, lora: LoRAAdapter | None = None):
        if (full is None) == (lora is None):
            raise ValueError(
                "DecodeModelSpec takes exactly one of full=params or "
                "lora=LoRAAdapter(...)")
        if lora is not None and not isinstance(lora, LoRAAdapter):
            raise TypeError(f"lora= expects a LoRAAdapter, got {type(lora)}")
        self.full = full
        self.lora = lora

    @property
    def kind(self) -> str:
        return "full" if self.full is not None else "lora"

    def group_key(self):
        """Fusability bucket within one ModelConfig: full models stack with
        full models; adapters stack only with adapters of the same
        (alpha, rank) — their stacked A/B shapes and merge scale agree."""
        if self.full is not None:
            return ("full",)
        return ("lora", self.lora.alpha, self.lora.rank)

    def materialize(self, base_params):
        """Full effective params (the legacy per-model decode layout)."""
        if self.full is not None:
            return self.full
        return lora_apply(base_params, self.lora.params,
                          alpha=self.lora.alpha, rank=self.lora.rank)

    def __repr__(self):
        if self.full is not None:
            return "DecodeModelSpec(full=<params>)"
        return (f"DecodeModelSpec(lora=LoRAAdapter(rank={self.lora.rank}, "
                f"alpha={self.lora.alpha}))")


def as_spec(obj) -> DecodeModelSpec:
    """Coerce to a spec: raw param pytrees register as full models (the
    construction-time ``decoders: dict`` shim feeds through here)."""
    if isinstance(obj, DecodeModelSpec):
        return obj
    if isinstance(obj, LoRAAdapter):
        return DecodeModelSpec(lora=obj)
    return DecodeModelSpec(full=obj)


class ModelRegistry:
    """The engine's decode-model set, mutable while serving.

    Mutations are split into an immediate half (bookkeeping: new requests
    validate against the registry the moment ``register``/``unregister``
    returns) and a deferred half (the fused plane's stacked layout), applied
    by ``sync()`` at step boundaries only — a stream callback may call
    ``register``/``unregister`` from INSIDE a decode step, and rebuilding
    the plane mid-step would cross-wire that step's lane routing."""

    def __init__(self, engine):
        self.engine = engine
        self._specs: dict[str, DecodeModelSpec] = {}
        self._draining: set[str] = set()
        self._dirty = False        # plane layout stale (rebuild at sync)
        self.version = 0           # bumped on every accepted mutation

    # -- queries -------------------------------------------------------
    def list(self) -> list[str]:
        """Registered model ids (draining models included until retired)."""
        return sorted(self._specs)

    def get(self, model_id: str) -> DecodeModelSpec:
        self._check_known(model_id)
        return self._specs[model_id]

    def __contains__(self, model_id) -> bool:
        return model_id in self._specs

    def __len__(self) -> int:
        return len(self._specs)

    def __iter__(self):
        return iter(self.list())

    @property
    def draining(self) -> frozenset:
        return frozenset(self._draining)

    def _check_known(self, model_id: str) -> None:
        if model_id not in self._specs:
            raise UnknownModelError(
                f"model {model_id!r} is not registered "
                f"(registered: {self.list() or 'none'}); add it with "
                f"engine.models.register(model_id, DecodeModelSpec(...))")

    def check_serving(self, model_id: str) -> None:
        """Validate a model id for NEW work (generate/submit)."""
        self._check_known(model_id)
        if model_id in self._draining:
            raise UnknownModelError(
                f"model {model_id!r} is draining (unregister pending): it "
                f"accepts no new requests; in-flight ones will finish")

    # -- mutations -----------------------------------------------------
    def register(self, model_id: str, spec) -> None:
        """Add a decode model while serving. ``spec`` is a DecodeModelSpec,
        a LoRAAdapter, or a raw param pytree (registered as full). New
        requests may target it immediately; its fused-plane lane appears at
        the next step boundary."""
        if model_id in self._specs:
            state = "draining" if model_id in self._draining else "registered"
            raise ValueError(
                f"model {model_id!r} is already {state}; unregister it "
                f"(and let it drain) before re-registering")
        spec = as_spec(spec)
        self._specs[model_id] = spec
        self.engine._attach_decoder(model_id, spec)
        self._dirty = True
        self.version += 1
        self.engine.stats.model_churn_events += 1

    def unregister(self, model_id: str, *, drain: bool = True) -> bool:
        """Retire a decode model. With ``drain=True`` (default) in-flight
        requests finish first; with ``drain=False`` they are aborted through
        the engine's abort path (pages back to baseline). Returns True if
        the model is fully gone on return, False if it is draining."""
        self._check_known(model_id)
        if model_id in self._draining:
            raise ValueError(f"model {model_id!r} is already draining")
        self.version += 1
        self.engine.stats.model_churn_events += 1
        if not drain:
            for rid in self.engine._inflight_rids(model_id):
                self.engine.abort(rid)
        if self.engine._has_inflight(model_id):
            # drain=True with live work (drain=False cannot reach here: a
            # non-abortable remaining<=0 sequence is reaped at the next step,
            # after which sync() finalizes)
            self._draining.add(model_id)
            return False
        self._finalize(model_id)
        return True

    # -- step-boundary application --------------------------------------
    def sync(self) -> None:
        """Apply deferred mutations; called by the scheduler at the top of
        every step (and once at engine construction). No-op when clean."""
        for model_id in sorted(self._draining):
            if not self.engine._has_inflight(model_id):
                self._draining.discard(model_id)
                self._finalize(model_id)
        if self._dirty:
            self._dirty = False
            self.engine._rebuild_decode_plane()

    def _finalize(self, model_id: str) -> None:
        del self._specs[model_id]
        self.engine._detach_decoder(model_id)
        self._dirty = True

    def __repr__(self):
        drain = f", draining={sorted(self._draining)}" if self._draining else ""
        return f"ModelRegistry({self.list()}{drain})"
