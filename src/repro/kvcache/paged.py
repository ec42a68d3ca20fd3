"""PagedKVPool: the physical KV data plane behind the paged control plane.

``BlockPool``/``PrefixIndex``/``CacheManager`` are the *control* plane —
refcounts, LRU, prefix matching over abstract block ids (since the
automatic-prefix-caching PR, ONE engine-global radix tree is the single
source of prefix truth over these pages: a page published by any prefill
worker is matchable by every other). This module gives
those ids physical storage: per-layer K/V page arrays shaped
``(P, page_size, Hkv * head_dim)`` (stacked over the model's scanned layer
groups), so a block id allocated by any prefill worker addresses real tensors
readable by every decode worker. That is the zero-copy handoff invariant of
the shared-prefill design: handing a request to a decode model moves a block
table (a few bytes of page ids), never the KV itself.

A token row keeps its heads contiguous (``Hkv * head_dim``, the dense caches'
flattened feature layout): one kv head of one page is then the ``(page,
head_dim)`` block at lane offset ``h * head_dim`` — a block the TPU's
compiler accepts and a strided DMA fetches without any relayout of the pool.

Data flow:
  - prefill: ``gather_prefill_cache`` materializes the cached prefix as a
    dense working cache (the compute plane for incremental attention), the
    frozen base model extends it, and ``scatter_from_dense`` writes the fresh
    page-aligned rows back into the pool via the ``paged_write`` Pallas
    kernel (interpret mode off-TPU) in one jitted, donated launch.
  - chunked prefill and decode: ``core.prefillshare.paged_forward`` wires
    the ``decode_state`` arrays + per-sequence block tables into the model
    cache pytree; ``repro.models.model.forward`` carries the pool through
    its layer scan, each layer appending its rows in place and attending
    straight from the pages (Pallas kernel on TPU, jnp gather twin
    elsewhere).

Donation-aware decode state: ``decode_state()`` hands the page buffers out as
one pytree to be passed INTO a jitted step (donated on TPU, so XLA updates
the touched pages in place instead of functionally copying the pool per
step — mirroring ``copy_page``), and ``absorb_decode_state`` stores the
step's returned buffers back. Off-TPU donation is off and the pair degrades
to the plain functional update.

Physical row 0 is the padding sentinel (``BlockPool.SENTINEL``): it is never
allocated, so ragged block tables zero-padded to a common width can never
alias live KV. The pool therefore carries ``num_pages + 1`` physical rows for
``num_pages`` usable pages (ids 1..num_pages).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, ModelConfig
from repro.kernels.paged_write import paged_write
from repro.runtime import donating_jit, on_tpu


def _copy_page_impl(state, src, dst):
    # group arrays are (n_full, P, page, F): page axis 1; tail arrays are
    # (P, page, F): page axis 0.
    def cp(a):
        if a.ndim == 4:
            return a.at[:, dst].set(a[:, src])
        return a.at[dst].set(a[src])
    return jax.tree.map(cp, state)


# One fused, jitted update over the whole pool pytree; donating the pool
# buffers lets XLA update the touched pages in place instead of copying the
# pool per clone.
_copy_page_jit = donating_jit(_copy_page_impl, (0,), name="copy_page")


def _write_pages_impl(state, rows, block_table):
    """``paged_write`` of page-aligned dense rows into every layer's pool.
    ``rows`` mirrors ``state``: (n_full, span, F) per group, (span, F) per
    tail layer; ``block_table`` (npages,) names the destination pages."""
    interp = not on_tpu()
    n_pages = block_table.shape[0]
    nvalid = jnp.full((1,), n_pages, jnp.int32)
    new = {"groups": {}, "tail": []}
    for g, st in state["groups"].items():
        # ONE kernel launch per group: the stacked (n_full, P, page, F) layer
        # axis folds into paged_write's batch axis by flattening the pool to
        # (n_full * P, page, F) and offsetting each layer's block table by
        # its pool stride.
        n_full, P = st["k"].shape[:2]
        off = (jnp.arange(n_full, dtype=jnp.int32) * P)[:, None]
        bt_l = block_table[None] + off                        # (n_full, npages)
        nv_l = jnp.broadcast_to(nvalid, (n_full,))
        flat = (n_full * P,) + st["k"].shape[2:]
        kp, vp = paged_write(rows["groups"][g]["k"], rows["groups"][g]["v"],
                             st["k"].reshape(flat), st["v"].reshape(flat),
                             bt_l, nv_l, interpret=interp)
        new["groups"][g] = {"k": kp.reshape(st["k"].shape),
                            "v": vp.reshape(st["v"].shape)}
    for st, rw in zip(state["tail"], rows["tail"]):
        kp, vp = paged_write(rw["k"][None], rw["v"][None], st["k"], st["v"],
                             block_table[None], nvalid, interpret=interp)
        new["tail"].append({"k": kp, "v": vp})
    return new


_write_pages_jit = donating_jit(_write_pages_impl, (0,), name="write_pages")


class PagedKVPool:
    """Per-layer physical K/V page arrays for a pure global-attention stack.

    Layers mirror the model cache structure: full ``layer_pattern`` groups are
    stacked on a leading axis (matching the ``lax.scan`` over groups in
    ``repro.models.model.forward``), remainder tail layers are stored
    individually.
    """

    def __init__(self, cfg: ModelConfig, num_pages: int, page_size: int,
                 dtype=None):
        assert self.supports(cfg), (
            f"paged KV plane requires a pure global-attention decoder "
            f"(got pattern {cfg.layer_pattern}, encdec={cfg.is_encdec})")
        self.cfg = cfg
        self.num_pages = num_pages            # usable pages (ids 1..num_pages)
        self.page_size = page_size
        self.hkv, self.hd = cfg.n_kv_heads, cfg.head_dim
        dt = jnp.dtype(dtype or cfg.dtype)
        pat = cfg.layer_pattern
        self.n_full = cfg.n_layers // len(pat)
        n_tail = cfg.n_layers % len(pat)

        # +1 physical row: row 0 is the never-allocated padding sentinel
        shape = (num_pages + 1, page_size, self.hkv * self.hd)
        self.k_groups = {f"pos{i}": jnp.zeros((self.n_full,) + shape, dt)
                         for i in range(len(pat))} if self.n_full else {}
        self.v_groups = {g: jnp.zeros_like(a) for g, a in self.k_groups.items()}
        self.k_tail = [jnp.zeros(shape, dt) for _ in range(n_tail)]
        self.v_tail = [jnp.zeros(shape, dt) for _ in range(n_tail)]

    @staticmethod
    def supports(cfg: ModelConfig) -> bool:
        """True if every layer's sequence state is global-attention KV."""
        return (all(k == ATTN for k in cfg.layer_kinds())
                and not cfg.is_encdec and cfg.n_heads > 0
                and cfg.input_mode == "tokens")

    @property
    def page_bytes(self) -> int:
        per_layer = 2 * self.page_size * self.hkv * self.hd
        return per_layer * self.cfg.n_layers * jnp.dtype(self.cfg.dtype).itemsize

    # ------------------------------------------------------------------
    # prefill side
    # ------------------------------------------------------------------
    def gather_prefill_cache(self, block_table, n_valid: int):
        """Materialize a dense B=1 working cache whose first ``n_valid`` rows
        come from the pool pages named by ``block_table`` (slots beyond
        ``n_valid`` are masked via kpos=-1)."""
        bt = jnp.asarray(block_table, jnp.int32)
        T = len(block_table) * self.page_size
        f = self.hkv * self.hd
        ar = jnp.arange(T, dtype=jnp.int32)
        kpos = jnp.where(ar < n_valid, ar, -1)[None]          # (1, T)

        groups = {}
        for g, kp in self.k_groups.items():
            k = kp[:, bt].reshape(self.n_full, T, f)[:, None]  # (n_full,1,T,f)
            v = self.v_groups[g][:, bt].reshape(self.n_full, T, f)[:, None]
            groups[g] = {"k": k, "v": v,
                         "kpos": jnp.broadcast_to(kpos, (self.n_full, 1, T))}
        tail = [{"k": kt[bt].reshape(T, f)[None],
                 "v": self.v_tail[i][bt].reshape(T, f)[None],
                 "kpos": kpos}
                for i, kt in enumerate(self.k_tail)]
        return {"groups": groups, "tail": tail}

    def scatter_from_dense(self, cache, block_table, start_page: int,
                           n_new_pages: int):
        """Write pages ``[start_page, start_page + n_new_pages)`` of a dense
        B=1 working cache into their physical pool pages (paged_write kernel,
        one jitted launch with the pool donated on TPU).

        Rows are taken from the *updated* dense cache, so a page that was
        partially cached before this prefill is rewritten whole — its old
        rows were gathered into the dense cache first, making every write
        page-aligned (the kernel's contract)."""
        if n_new_pages <= 0:
            return
        s0, span = start_page * self.page_size, n_new_pages * self.page_size

        def rows(c):                           # (..., 1, cap, F) -> new rows
            return {"k": c["k"][..., 0, s0:s0 + span, :],
                    "v": c["v"][..., 0, s0:s0 + span, :]}

        new = _write_pages_jit(
            self.decode_state(),
            {"groups": {g: rows(c) for g, c in cache["groups"].items()},
             "tail": [rows(c) for c in cache["tail"]]},
            jnp.asarray(block_table[start_page:start_page + n_new_pages],
                        jnp.int32))
        self.absorb_decode_state(new)

    # ------------------------------------------------------------------
    # decode side
    # ------------------------------------------------------------------
    def copy_page(self, src: int, dst: int):
        """Copy-on-write: clone one physical page (all layers) in a SINGLE
        jitted, donated update — one dispatch for the whole pool pytree
        instead of an un-jitted ``.at[].set`` per layer array (which cost
        O(pool) traffic per clone). Used when a decode holder must append
        into a partially-filled shared page."""
        new = _copy_page_jit(self.pool_state(), jnp.int32(src), jnp.int32(dst))
        self.set_pool_state(new)

    def pool_state(self):
        """Every page buffer as ONE pytree — the argument for a jitted,
        donated whole-pool update (``copy_page``'s clone, the swap tier's
        scatter-on-resume). Pair with ``set_pool_state`` on the result.

        Containers are fresh (shallow) copies so the handed-out tree can be
        invalidated independently of the pool's own references (the
        sanitized pool poisons stale handles in place)."""
        return {"kg": dict(self.k_groups), "vg": dict(self.v_groups),
                "kt": list(self.k_tail), "vt": list(self.v_tail)}

    def set_pool_state(self, new) -> None:
        """Store the buffers a jitted whole-pool update returned. After a
        donated TPU update the previous buffers are invalid (the sanitized
        pool poisons them)."""
        self.k_groups, self.v_groups = new["kg"], new["vg"]
        self.k_tail, self.v_tail = list(new["kt"]), list(new["vt"])

    def decode_state(self):
        """The pool's page buffers as ONE pytree, to be passed INTO a jitted
        step as an argument (donate it on TPU: pages then update in place
        instead of the per-step functional pool copy). Pair with
        ``absorb_decode_state`` on the step's return value."""
        return {"groups": {g: {"k": self.k_groups[g], "v": self.v_groups[g]}
                           for g in self.k_groups},
                "tail": [{"k": k, "v": v}
                         for k, v in zip(self.k_tail, self.v_tail)]}

    def absorb_decode_state(self, state) -> None:
        """Store the page buffers a jitted step returned. After a donated TPU
        step the previous buffers are invalid; off-TPU donation is off and
        this is a plain functional publish."""
        for g in self.k_groups:
            self.k_groups[g] = state["groups"][g]["k"]
            self.v_groups[g] = state["groups"][g]["v"]
        for i in range(len(self.k_tail)):
            self.k_tail[i] = state["tail"][i]["k"]
            self.v_tail[i] = state["tail"][i]["v"]

