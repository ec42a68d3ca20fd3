"""Host-memory KV swap tier: the data plane of preemption.

Oversubscription (serving/preempt.py) parks a low-priority decode sequence
by moving its PRIVATE pages' KV off the device: a single jitted gather per
swap-out pulls every selected page across all layer groups in one launch,
``jax.device_get`` lands the rows in host memory, and the pool rows become
SWAPPED (reclaimable — ``BlockPool.alloc`` may hand them to new owners).
On resume, pages whose device rows were never revoked reattach with zero
data movement; revoked ones are scattered back into freshly allocated rows
with a single jitted, donated whole-pool update (the ``copy_page`` idiom:
donate on TPU so XLA writes the pages in place).

This module is the ONE sanctioned host-materialization point for pool page
buffers: analysis rule RPR007 flags ``np.asarray``/``jax.device_get`` on
``PagedKVPool`` arrays anywhere else.

Page-count shapes are bucketed to the next power of two before entering the
jitted gather/scatter (RPR004): pad slots index the padding sentinel row 0,
which holds no live KV by construction — padded gather rows are sliced off
after the host copy, and padded scatter slots write zeros to row 0, which
no block table can ever read as live KV.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime import donating_jit


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (>= 1): the page-count shape bucket."""
    return 1 << max(0, int(n) - 1).bit_length()


def _gather_impl(state, bids):
    # group arrays are (n_full, P+1, page, F): page axis 1; tail arrays are
    # (P+1, page, F): page axis 0 (same layout as copy_page).
    return jax.tree.map(
        lambda a: a[:, bids] if a.ndim == 4 else a[bids], state)


def _scatter_impl(state, bids, vals):
    def sc(a, v):
        if a.ndim == 4:
            return a.at[:, bids].set(v)
        return a.at[bids].set(v)
    return jax.tree.map(sc, state, vals)


# Gather reads the pool (no donation: the pool stays live); scatter rewrites
# it wholesale, so the pool pytree is donated where donation is honoured —
# exactly the copy_page contract, one launch per swap either way.
_gather_jit = jax.jit(_gather_impl)
_scatter_jit = donating_jit(_scatter_impl, (0,), name="swap_scatter")


class HostSwapPool:
    """rid-keyed host-memory store of swapped-out page KV.

    ``put`` copies pages device->host (timed, fed to the bandwidth model);
    ``restore`` scatters a subset of an entry's pages back into fresh device
    rows; ``pop`` discards the host copy (resume complete, or abort while
    swapped). ``observe(nbytes, seconds)`` — when given — receives every
    measured transfer so the preemption cost model prices swap vs recompute
    from measured bandwidth, not constants.
    """

    def __init__(self, observe=None):
        self._entries: dict = {}      # rid -> {"bids": list, "host": pytree}
        self.observe = observe
        self.total_bytes = 0

    def __contains__(self, rid) -> bool:
        return rid in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    def bids(self, rid):
        return self._entries[rid]["bids"]

    # ------------------------------------------------------------------
    def put(self, kvpool, rid, bids) -> int:
        """Copy pages ``bids`` (all layers) to host memory under ``rid``.
        One jitted gather launch + one host transfer; returns bytes moved."""
        assert rid not in self._entries, f"rid {rid} already swapped"
        n = len(bids)
        width = next_pow2(n)
        idx = jnp.asarray(list(bids) + [0] * (width - n), jnp.int32)
        t0 = time.perf_counter()
        gathered = _gather_jit(kvpool.pool_state(), idx)
        host = jax.device_get(gathered)
        dt = time.perf_counter() - t0
        # drop the pad rows landed by the pow2 bucket
        host = jax.tree.map(
            lambda a: a[:, :n] if a.ndim == 4 else a[:n], host)
        nbytes = n * kvpool.page_bytes
        self._entries[rid] = {"bids": list(bids), "host": host}
        self.total_bytes += nbytes
        if self.observe is not None and n:
            self.observe(nbytes, dt)
        return nbytes

    def restore(self, kvpool, rid, positions, dst_bids) -> int:
        """Scatter the entry's pages at ``positions`` back into device rows
        ``dst_bids`` (one donated whole-pool launch); returns bytes moved.
        Pages NOT in ``positions`` were never revoked and need no transfer."""
        entry = self._entries[rid]
        n = len(positions)
        if n == 0:
            return 0
        assert len(dst_bids) == n
        width = next_pow2(n)
        idx = jnp.asarray(list(dst_bids) + [0] * (width - n), jnp.int32)
        sel = np.asarray(positions, np.intp)

        def pick(a):
            # page axis sized to the pow2 bucket up front; pad slots stay
            # zero and scatter onto sentinel row 0 (never read as live KV)
            axis = 1 if a.ndim == 4 else 0
            shape = (list(a.shape[:axis]) + [next_pow2(n)]
                     + list(a.shape[axis + 1:]))
            out = np.zeros(shape, a.dtype)
            if a.ndim == 4:
                out[:, :n] = a[:, sel]
            else:
                out[:n] = a[sel]
            return out

        vals = jax.tree.map(pick, entry["host"])
        t0 = time.perf_counter()
        new = _scatter_jit(kvpool.pool_state(), idx, vals)
        new = jax.block_until_ready(new)
        kvpool.set_pool_state(new)
        dt = time.perf_counter() - t0
        nbytes = n * kvpool.page_bytes
        if self.observe is not None:
            self.observe(nbytes, dt)
        return nbytes

    def pop(self, rid) -> None:
        """Discard ``rid``'s host copy (resume complete, or abort)."""
        entry = self._entries.pop(rid, None)
        if entry is not None:
            self.total_bytes -= len(entry["bids"]) * _entry_page_bytes(entry)

    def entry_pages(self, rid) -> int:
        return len(self._entries[rid]["bids"])


def _entry_page_bytes(entry) -> int:
    """Bytes per page of a stored entry, from its own leaves (the pool that
    produced it may already be gone at pop time)."""
    total = sum(a.nbytes for a in jax.tree.leaves(entry["host"]))
    n = len(entry["bids"])
    return total // n if n else 0
