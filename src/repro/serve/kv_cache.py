"""KV cache as an API: interchangeable dense / paged implementations.

The serving story of the paper (§4.3, §6) is that data-dependent loops
let memory track *actual* work. PR-2's scheduler still allocated every
decode slot a dense ``max_len`` cache column, so one long-``max_new``
request sized the whole pool. This module makes the cache an explicit
protocol with two implementations (DESIGN.md §8):

- ``DenseKVCache`` — the reference: per-row columns
  ``(L, n_rows, max_len, KV, hd)``, extracted verbatim from the old
  ``engine.make_cache`` tuple plumbing. Zero indirection; memory is
  ``n_rows × max_len`` regardless of occupancy.
- ``PagedKVCache`` — vLLM-style block tables: fixed-size blocks in a
  shared head-major pool ``(L, n_blocks, KV, block, hd)``, a per-row
  block table ``(n_rows, blocks_per_row)`` and an in-graph free-list (the
  ``refcount`` vector — a block is free iff ``refcount == 0``).
  ``alloc``/``free`` are pure array ops, so admission and retirement
  stay inside the runtime: a retired slot's blocks are reusable by the
  very next admission, and pool capacity is bounded by *tokens in
  flight*, not ``n_rows × max_len``. Blocks are **reference-counted**:
  ``alloc`` can map already-populated physical blocks into a new row's
  table (prefix caching — a shared system prompt's K/V prefills once),
  ``free`` decrements instead of unconditionally releasing, and
  ``ensure_private`` performs in-graph copy-on-write before a row
  writes into a block some other reference still reads.

Both are registered pytrees, so a cache rides through ``jax.jit`` /
``repro.core.while_loop`` carries unchanged (the scheduler's
``SlotPool.cache`` is one of these).

Layout invariants:

- Per-layer state is scanned: ``cache.layers`` is a pytree whose leaves
  carry the layer dim in front, ``cache.view(leaves)`` binds one
  layer's state into a ``view`` with ``write_prompt`` /
  ``write_chunk`` (a prompt chunk at per-row base offsets — the
  chunked-prefill write) / ``append`` / ``gather``, and
  ``cache.with_layers(stacked)`` rebuilds the cache
  from the scan's stacked outputs. Block tables are **shared across
  layers** (row r's logical block b lives at the same physical id in
  every layer's pool), which is what lets the per-layer view be a pure
  pool slice.
- Greedy decode through ``PagedKVCache`` is bit-identical to
  ``DenseKVCache``: ``gather`` reconstructs the dense ``(n, max_len)``
  key/value layout (same lanes, same values; unallocated lanes carry
  garbage that the attention mask hits with the same ``NEG_INF`` it
  uses for dense out-of-range lanes), so the attention math sees
  byte-identical inputs at every valid lane.
- All writes route out-of-range / unallocated positions to index
  ``n_blocks`` and scatter with ``mode="drop"`` — a retired row whose
  table was freed appends nowhere instead of corrupting a recycled
  block.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from ..dist import sharding as sh

__all__ = ["KVCache", "DenseKVCache", "PagedKVCache", "DenseView",
           "PagedView", "blocks_needed", "make_kv_cache"]


def blocks_needed(n_tokens, block: int):
    """Blocks covering ``n_tokens`` cache positions (array or int)."""
    return -(-n_tokens // block)


def _bcast_rows(rows: Optional[jax.Array], n: int) -> jax.Array:
    return jnp.arange(n, dtype=jnp.int32) if rows is None \
        else jnp.asarray(rows, jnp.int32)


def set_rows(vec: jax.Array, rows, new: jax.Array, mask) -> jax.Array:
    """``vec`` with row ``rows[i]`` set to ``new[i]`` where ``mask[i]``
    (masked rows distinct; out-of-range rows write nothing).

    A select over ``vec``'s rows, each picking its update from ``new``,
    not a scatter into ``vec``: on a pool sharded by rows over several
    TPU chips, the partitioned scatter wrote rows that belong to one
    chip into the other chip's rows as well (a 2-chip scheduler served
    request 2's tokens to request 0), where a select on ``vec``'s own
    rows and a gather from the replicated ``new`` stay on each chip."""
    n = vec.shape[0]
    rows = jnp.asarray(rows, jnp.int32)
    rows = jnp.where(rows < 0, rows + n, rows)
    hit = ((rows[None, :] == jnp.arange(n, dtype=jnp.int32)[:, None])
           & jnp.asarray(mask, bool)[None, :])
    src = jnp.argmax(hit, axis=1)
    m = hit.any(axis=1).reshape((n,) + (1,) * (vec.ndim - 1))
    return jnp.where(m, new.astype(vec.dtype)[src], vec)


# =========================== per-layer views ================================

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DenseView:
    """One layer of a dense cache: ``k``/``v`` are ``(n, T, KV, hd)``.

    ``rows``/``mask`` (optional) bind which cache rows a prompt batch
    writes into — ``rows`` is a permutation of ``range(n)`` and masked
    rows are the ones actually admitted (the scheduler's
    prefill-into-slot path); ``rows=None`` means the identity (the
    batch-synchronous path, where batch row b IS cache row b).
    """

    k: jax.Array
    v: jax.Array
    rows: Optional[jax.Array] = None
    mask: Optional[jax.Array] = None

    def tree_flatten(self):
        return (self.k, self.v, self.rows, self.mask), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def leaves(self):
        return {"k": self.k, "v": self.v}

    def write_prompt(self, k: jax.Array, v: jax.Array) -> "DenseView":
        """Write prompt K/V at positions ``[0, S)`` of the bound rows."""
        kd, vd = k.astype(self.k.dtype), v.astype(self.v.dtype)
        if self.rows is None and self.mask is None:
            kc = jax.lax.dynamic_update_slice_in_dim(self.k, kd, 0, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(self.v, vd, 0, axis=1)
        else:
            S = k.shape[1]
            rows = _bcast_rows(self.rows, k.shape[0])
            m = (jnp.ones((k.shape[0],), bool) if self.mask is None
                 else self.mask)[:, None, None, None]
            kc = self.k.at[rows, :S].set(
                jnp.where(m, kd, self.k[rows, :S]))
            vc = self.v.at[rows, :S].set(
                jnp.where(m, vd, self.v[rows, :S]))
        return dataclasses.replace(self, k=kc, v=vc)

    def write_chunk(self, k: jax.Array, v: jax.Array,
                    offsets) -> "DenseView":
        """Write a prompt CHUNK ``(n, C, KV, hd)`` at per-row base
        offsets: row ``i``'s chunk lands at positions
        ``[offsets[i], offsets[i] + C)`` — the chunked-prefill write
        (``write_prompt`` is the ``offsets == 0`` special case).
        Out-of-range positions and unmasked rows drop."""
        n, C = k.shape[0], k.shape[1]
        rows = _bcast_rows(self.rows, n)
        pos = jnp.asarray(offsets, jnp.int32)[:, None] \
            + jnp.arange(C, dtype=jnp.int32)[None, :]          # (n, C)
        keep = pos < self.k.shape[1]
        if self.mask is not None:
            keep = keep & self.mask[:, None]
        # invalid lanes route to row index n_rows -> dropped scatter
        rix = jnp.where(keep, rows[:, None], self.k.shape[0])
        kc = self.k.at[rix, pos].set(k.astype(self.k.dtype), mode="drop")
        vc = self.v.at[rix, pos].set(v.astype(self.v.dtype), mode="drop")
        return dataclasses.replace(self, k=kc, v=vc)

    def append(self, k: jax.Array, v: jax.Array, cur_len) -> "DenseView":
        """Write the single-token K/V ``(n, 1, KV, hd)`` at
        ``cur_len - 1`` (scalar: whole batch in lockstep; vector:
        per-row depths, the slot-pool path). Bound ``rows``/``mask``
        are honored like every other view write."""
        pos = jnp.asarray(cur_len) - 1
        kd, vd = k.astype(self.k.dtype), v.astype(self.v.dtype)
        bound = self.rows is not None or self.mask is not None
        if pos.ndim == 1 or bound:
            n = k.shape[0]
            rows = _bcast_rows(self.rows, n)
            if pos.ndim == 0:
                pos = jnp.full((n,), pos, jnp.int32)
            if self.mask is None:
                uk, uv = kd[:, 0], vd[:, 0]
            else:   # masked rows keep their current values
                m = self.mask[:, None, None]
                uk = jnp.where(m, kd[:, 0], self.k[rows, pos])
                uv = jnp.where(m, vd[:, 0], self.v[rows, pos])
            kc = self.k.at[rows, pos].set(uk)
            vc = self.v.at[rows, pos].set(uv)
        else:
            kc = jax.lax.dynamic_update_slice_in_dim(self.k, kd, pos,
                                                     axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(self.v, vd, pos,
                                                     axis=1)
        return dataclasses.replace(self, k=kc, v=vc)

    def gather(self) -> Tuple[jax.Array, jax.Array]:
        """Dense ``(n, T, KV, hd)`` K and V, the bound ``rows``
        applied: view row ``i`` of the result is cache row
        ``rows[i]`` — the same binding semantics ``paged_state()``
        exposes, so the gather fallback and the kernel path read the
        same rows whatever the binding (identity when unbound)."""
        if self.rows is None:
            return self.k, self.v
        return self.k[self.rows], self.v[self.rows]

    def paged_state(self):
        """Gather-free kernel operands; None — this layout IS dense."""
        return None


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedView:
    """One layer of a paged cache: pool slices plus the shared table.

    ``k_pool``/``v_pool``: ``(n_blocks, KV, block, hd)`` — head-major,
    so one (block, head) tile is a contiguous ``(block, hd)`` slab whose
    last two dims the TPU compiler accepts as a kernel block (a
    ``(block, 1, hd)`` slice of a token-major pool is refused: its
    second-to-last dim is neither a multiple of 8 nor the full KV
    width). ``table``: ``(n_rows, blocks_per_row)`` physical block ids,
    ``-1`` where unallocated. ``max_len`` (static) is the logical
    per-row width ``gather`` reconstructs — matching the dense layout
    exactly.
    """

    k_pool: jax.Array
    v_pool: jax.Array
    table: jax.Array
    max_len: int = 0
    rows: Optional[jax.Array] = None
    mask: Optional[jax.Array] = None

    def tree_flatten(self):
        return (self.k_pool, self.v_pool, self.table, self.rows,
                self.mask), (self.max_len,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        kp, vp, t, rows, mask = children
        return cls(kp, vp, t, aux[0], rows, mask)

    @property
    def leaves(self):
        # The table is NOT a per-layer leaf: appends never change it.
        return {"k": self.k_pool, "v": self.v_pool}

    @property
    def block(self) -> int:
        return self.k_pool.shape[2]

    @property
    def n_blocks(self) -> int:
        return self.k_pool.shape[0]

    def _phys(self, rows, pos):
        """Physical (block, offset) for logical positions; unallocated
        positions — and positions past the table's width, which a
        ragged chunked-prefill tail can produce — map to block id
        ``n_blocks`` (dropped on scatter). Without the column guard an
        out-of-range ``pos // block`` would CLAMP into the row's last
        real block and corrupt it."""
        bpr = self.table.shape[1]
        col = pos // self.block
        blk = self.table[rows, jnp.minimum(col, bpr - 1)]
        blk = jnp.where((blk >= 0) & (col < bpr), blk, self.n_blocks)
        return blk, pos % self.block

    def write_prompt(self, k: jax.Array, v: jax.Array) -> "PagedView":
        return self.write_chunk(k, v, jnp.zeros((k.shape[0],), jnp.int32))

    def write_chunk(self, k: jax.Array, v: jax.Array,
                    offsets) -> "PagedView":
        """Write a prompt CHUNK ``(n, C, KV, hd)`` at per-row base
        offsets through the block table (``write_prompt`` is the
        ``offsets == 0`` special case). Positions past a row's
        allocated blocks hit ``-1`` table entries and drop — a ragged
        final chunk writes its real lanes and nothing else it
        shouldn't."""
        n, C = k.shape[0], k.shape[1]
        rows = _bcast_rows(self.rows, n)
        pos = jnp.asarray(offsets, jnp.int32)[:, None] \
            + jnp.arange(C, dtype=jnp.int32)[None, :]          # (n, C)
        blk, off = self._phys(rows[:, None], pos)
        if self.mask is not None:
            blk = jnp.where(self.mask[:, None], blk, self.n_blocks)
        fb = blk.reshape(-1)
        fo = off.reshape(-1)
        # pool[fb, :, fo] is (n*C, KV, hd): the token-major chunk rows
        kp = self.k_pool.at[fb, :, fo].set(
            k.astype(self.k_pool.dtype).reshape((n * C,) + k.shape[2:]),
            mode="drop")
        vp = self.v_pool.at[fb, :, fo].set(
            v.astype(self.v_pool.dtype).reshape((n * C,) + v.shape[2:]),
            mode="drop")
        return dataclasses.replace(self, k_pool=kp, v_pool=vp)

    def append(self, k: jax.Array, v: jax.Array, cur_len) -> "PagedView":
        n = k.shape[0]
        pos = jnp.asarray(cur_len) - 1
        if pos.ndim == 0:
            pos = jnp.full((n,), pos, jnp.int32)
        rows = _bcast_rows(self.rows, n)
        blk, off = self._phys(rows, pos.astype(jnp.int32))
        if self.mask is not None:
            blk = jnp.where(self.mask, blk, self.n_blocks)
        kp = self.k_pool.at[blk, :, off].set(
            k[:, 0].astype(self.k_pool.dtype), mode="drop")
        vp = self.v_pool.at[blk, :, off].set(
            v[:, 0].astype(self.v_pool.dtype), mode="drop")
        return dataclasses.replace(self, k_pool=kp, v_pool=vp)

    def gather(self) -> Tuple[jax.Array, jax.Array]:
        """Reconstruct the dense ``(n_rows, max_len, KV, hd)`` layout.

        Unallocated table entries clip to block 0: those lanes carry
        garbage exactly where the dense cache carries stale/zero data —
        both are masked by ``cur_len`` before the softmax, so valid
        lanes are byte-identical to the dense path.

        This is the XLA-portable REFERENCE form: it pays a transient
        dense-layout K/V per layer per decode step, buying the
        bit-identical-to-dense guarantee the equivalence tests pin.
        The production form is the gather-free path: ``paged_state``
        hands (pool, table) to the Pallas paged-attention decode
        kernel (``repro.kernels.paged_attention``), whose score loop
        indexes the pool through the table directly and never
        materializes this layout — engaged by
        ``models.attention.decode_attention`` when
        ``cfg.attn_impl == "pallas"``.

        The bound ``rows`` is applied exactly as in ``paged_state()``
        (view row ``i`` reads cache row ``rows[i]``), so the two read
        paths can never disagree about which row's blocks they walk.
        """
        table = self.table if self.rows is None else self.table[self.rows]
        safe = jnp.clip(table, 0)
        n, bpr = table.shape

        def dense(pool):                  # (n, bpr, KV, block, hd)
            g = pool[safe].swapaxes(2, 3)
            return g.reshape((n, bpr * self.block) + g.shape[3:])

        return (dense(self.k_pool)[:, :self.max_len],
                dense(self.v_pool)[:, :self.max_len])

    def paged_state(self):
        """Gather-free kernel operands ``(k_pool, v_pool, table)`` —
        the per-row binding applied, so row ``i`` of the returned
        table is the table of the view's logical row ``i``. A bound
        ``mask`` gates WRITES only (``append``/``write_chunk``);
        reading through the table is a layout fact, not a lifecycle
        one, so masked views (the chunked-prefill/decode steps, where
        only some rows are advancing) still hand the kernels their
        state — unmasked rows' lanes are garbage the caller discards,
        exactly like the gather path."""
        table = self.table if self.rows is None else self.table[self.rows]
        return self.k_pool, self.v_pool, table


# =========================== cache implementations ==========================

class KVCache:
    """Protocol: a multi-layer KV cache with explicit block lifecycle.

    Pure-functional: every mutator returns a new cache. ``rows`` is a
    vector of row (slot) ids, ``mask`` selects which of them the call
    applies to — the scheduler passes its admission permutation
    unchanged. Implementations: ``DenseKVCache`` (``alloc``/``free``
    are no-ops), ``PagedKVCache`` (block tables + free-list).
    """

    # ---- per-layer scan machinery (the hot path) ----
    @property
    def layers(self) -> Any:
        """Pytree to scan over; leaves carry the layer dim in front."""
        raise NotImplementedError

    def view(self, leaves, rows=None, mask=None):
        """Bind one layer's scanned leaves (plus shared state) into a
        view with ``write_prompt`` / ``append`` / ``gather``."""
        raise NotImplementedError

    def with_layers(self, stacked) -> "KVCache":
        """Rebuild from the scan's stacked per-layer outputs."""
        raise NotImplementedError

    def view_at(self, layer: int, rows=None, mask=None):
        """View of a statically-indexed layer (hybrid's shared app)."""
        return self.view(jax.tree.map(lambda a: a[layer], self.layers),
                         rows=rows, mask=mask)

    def set_at(self, layer: int, view) -> "KVCache":
        return self.with_layers(jax.tree.map(
            lambda full, n: full.at[layer].set(n), self.layers,
            view.leaves))

    # ---- issue-protocol conveniences over the view machinery ----
    def append(self, layer: int, rows, cur_len, k, v) -> "KVCache":
        """Append one token's K/V for ``rows`` at ``cur_len - 1``.
        Copy-on-write first: an append into a block other references
        still read repoints this row to a private copy (paged only;
        the engine's scan paths call ``ensure_private`` themselves,
        once per step rather than per layer)."""
        node = self.ensure_private(rows,
                                   start=jnp.asarray(cur_len,
                                                     jnp.int32) - 1,
                                   width=1)
        return node.set_at(layer,
                           node.view_at(layer, rows=rows).append(k, v,
                                                                 cur_len))

    def gather(self, layer: int, rows=None):
        """Dense (rows, max_len, KV, hd) K/V of one layer."""
        k, v = self.view_at(layer).gather()
        if rows is None:
            return k, v
        return k[rows], v[rows]

    # ---- lifecycle ----
    def alloc(self, rows, budget, mask=None, shared=None,
              pin=None) -> "KVCache":
        """Reserve capacity for ``budget[i]`` tokens on row ``rows[i]``
        (masked rows only). Dense: no-op (capacity is preallocated).

        ``shared`` (optional, paged): ``(n, blocks_per_row)`` physical
        block ids to MAP into each row's leading table columns instead
        of allocating fresh blocks (``-1``-padded, prefix-contiguous) —
        the prefix-cache hit path. ``pin`` (optional): same-shape bool;
        pinned columns take one EXTRA reference (a host-side index
        registration that outlives the row)."""
        return self

    def free(self, rows=None, mask=None) -> "KVCache":
        """Release rows' capacity back to the pool. Dense: no-op."""
        return self

    def ensure_private(self, rows=None, *, start, width,
                       mask=None) -> "KVCache":
        """Guarantee the blocks backing positions
        ``[start, start + width)`` of ``rows`` are exclusively held
        (``refcount == 1``) before a write lands there — in-graph
        copy-on-write for the paged cache. Dense: no-op (rows never
        share storage)."""
        return self

    def reclaimable(self) -> jax.Array:
        """(n_rows,) int32 — blocks freeing each row would actually
        return to the pool: table entries whose block has
        ``refcount == 1`` (this row is the last holder — shared or
        index-pinned blocks survive a ``free``). The SLO layer's
        victim-selection signal: a row full of shared prefix blocks
        reclaims almost nothing and is a poor preemption victim.
        Dense rows hold no pool blocks → zeros."""
        return jnp.zeros((self.n_rows,), jnp.int32)

    # ---- cross-pool block shipping (disaggregated serving) ----
    def export_rows(self, rows, n_cols: int):
        """Pack the K/V bits behind ``rows``' leading ``n_cols`` table
        columns into a FRESH dense-of-blocks buffer
        ``(L, len(rows), n_cols, KV, block, hd)`` — the wire format
        for shipping finished prefill blocks to another pool
        (``serve/disagg.py``). Paged only."""
        raise NotImplementedError

    def import_rows(self, rows, k_data, v_data, mask=None) -> "KVCache":
        """Scatter ``export_rows``-shaped buffers into ``rows``'
        leading table columns (allocate the rows first). Paged only."""
        raise NotImplementedError

    # ---- placement ----
    def shardings(self, rules, mesh=None, row_axis: str = sh.BATCH):
        """Matching-structure pytree of ``NamedSharding``s."""
        raise NotImplementedError


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DenseKVCache(KVCache):
    """Reference implementation: ``(L, n_rows, max_len, KV, hd)``."""

    k: jax.Array
    v: jax.Array

    def tree_flatten(self):
        return (self.k, self.v), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @classmethod
    def create(cls, n_layers: int, n_rows: int, max_len: int, kv_heads: int,
               head_dim: int, dtype, abstract: bool = False
               ) -> "DenseKVCache":
        shape = (n_layers, n_rows, max_len, kv_heads, head_dim)
        if abstract:
            e = jax.ShapeDtypeStruct(shape, dtype)
            return cls(k=e, v=e)
        z = jnp.zeros(shape, dtype)
        return cls(k=z, v=z)

    @property
    def n_rows(self) -> int:
        return self.k.shape[1]

    @property
    def max_len(self) -> int:
        return self.k.shape[2]

    @property
    def layers(self):
        return {"k": self.k, "v": self.v}

    def view(self, leaves, rows=None, mask=None) -> DenseView:
        return DenseView(leaves["k"], leaves["v"], rows=rows, mask=mask)

    def with_layers(self, stacked) -> "DenseKVCache":
        return DenseKVCache(k=stacked["k"], v=stacked["v"])

    def shardings(self, rules, mesh=None, row_axis: str = sh.BATCH):
        spec = (sh.LAYERS, row_axis, None, sh.CACHE_KV, sh.CACHE_HD)
        s = rules.sharding(spec, mesh, dims=tuple(self.k.shape))
        return DenseKVCache(k=s, v=s)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedKVCache(KVCache):
    """Block-table cache: shared pool + per-row tables + free-list.

    ``refcount[b]`` counts the references holding physical block ``b``:
    table occurrences across rows plus host-index pins. A block is free
    iff ``refcount[b] == 0`` — the free-list as a flat vector, so
    ``alloc``/``free`` are in-graph scatters and the whole lifecycle
    stays inside jit / ``while_loop`` bodies. ``owner[b]`` records the
    row that *allocated* (and therefore writes) the block, ``-1`` when
    free — shared mappings never change it, so a block's writer stays
    unambiguous while readers come and go.
    """

    k_pool: jax.Array        # (L, n_blocks, KV, block, hd)
    v_pool: jax.Array
    table: jax.Array         # (n_rows, blocks_per_row) int32, -1 = unalloc
    owner: jax.Array         # (n_blocks,) int32, -1 = free
    refcount: jax.Array = None   # (n_blocks,) int32, 0 = free
    max_len: int = 0         # logical per-row width (static)

    def tree_flatten(self):
        return (self.k_pool, self.v_pool, self.table, self.owner,
                self.refcount), (self.max_len,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, max_len=aux[0])

    @classmethod
    def create(cls, n_layers: int, n_rows: int, max_len: int, kv_heads: int,
               head_dim: int, dtype, *, block: int = 16,
               n_blocks: Optional[int] = None, abstract: bool = False
               ) -> "PagedKVCache":
        """``n_blocks`` defaults to dense-equivalent capacity
        (``n_rows * ceil(max_len / block)``); serving pools pass less —
        that under-provisioning is the whole point."""
        bpr = math.ceil(max_len / block)
        nb = n_rows * bpr if n_blocks is None else int(n_blocks)
        pshape = (n_layers, nb, kv_heads, block, head_dim)
        if abstract:
            e = jax.ShapeDtypeStruct(pshape, dtype)
            vec = jax.ShapeDtypeStruct((nb,), jnp.int32)
            return cls(k_pool=e, v_pool=e,
                       table=jax.ShapeDtypeStruct((n_rows, bpr), jnp.int32),
                       owner=vec, refcount=vec, max_len=max_len)
        return cls(k_pool=jnp.zeros(pshape, dtype),
                   v_pool=jnp.zeros(pshape, dtype),
                   table=jnp.full((n_rows, bpr), -1, jnp.int32),
                   owner=jnp.full((nb,), -1, jnp.int32),
                   refcount=jnp.zeros((nb,), jnp.int32),
                   max_len=max_len)

    @property
    def n_rows(self) -> int:
        return self.table.shape[0]

    @property
    def block(self) -> int:
        return self.k_pool.shape[3]

    @property
    def n_blocks(self) -> int:
        return self.k_pool.shape[1]

    @property
    def blocks_per_row(self) -> int:
        return self.table.shape[1]

    @property
    def free_count(self) -> jax.Array:
        return jnp.sum(self.refcount == 0).astype(jnp.int32)

    def reclaimable(self) -> jax.Array:
        """(n_rows,) int32 — per-row count of table entries that are
        this row's EXCLUSIVELY: allocated (``table >= 0``) and backed
        by a block with ``refcount == 1``. Freeing the row returns
        exactly these blocks to the pool (shared/pinned blocks only
        drop a reference), so this is the honest "what does preempting
        row r buy" number."""
        ref = jnp.where(self.table >= 0,
                        self.refcount[jnp.clip(self.table, 0, None)], 0)
        return jnp.sum(ref == 1, axis=1).astype(jnp.int32)

    @property
    def layers(self):
        return {"k": self.k_pool, "v": self.v_pool}

    def view(self, leaves, rows=None, mask=None) -> PagedView:
        return PagedView(leaves["k"], leaves["v"], self.table,
                         self.max_len, rows=rows, mask=mask)

    def with_layers(self, stacked) -> "PagedKVCache":
        return dataclasses.replace(self, k_pool=stacked["k"],
                                   v_pool=stacked["v"])

    # ---- lifecycle (pure array ops; run inside jit / while bodies) ----

    def alloc(self, rows, budget, mask=None, shared=None,
              pin=None) -> "PagedKVCache":
        """Assign ``ceil(budget / block)`` blocks to each masked row.
        Rows must be free (``free`` first — admission does).

        ``shared`` (optional) maps already-populated physical blocks
        into each row's LEADING table columns: ``shared[i]`` is a
        prefix-contiguous run of block ids (``-1``-padded) whose
        refcounts are bumped instead of drawing from the free-list —
        the prefix-cache hit path. Only ``need - n_shared`` fresh
        blocks are allocated. ``pin[i, j]`` adds one extra reference
        to the block mapped at column ``j`` (a host-index registration
        that must survive this row's retirement).

        Allocation is **all-or-nothing per row**: a row whose fresh
        blocks don't all fit the free-list allocates nothing (its table
        stays fully ``-1`` and it maps no shared blocks), and the rows
        after it still allocate if their own needs fit — a failed row
        reserves nothing. The caller is responsible for capacity
        (the scheduler's host mirror gates admission), so failure is a
        defensive state, not a scheduling mechanism: writes on a failed
        row drop and its gathers read block-0 garbage behind the
        length mask, never corrupting live rows.
        """
        rows = jnp.asarray(rows, jnp.int32)
        n = rows.shape[0]
        mask = jnp.ones((n,), bool) if mask is None else mask
        need = blocks_needed(jnp.asarray(budget, jnp.int32), self.block)
        need = jnp.where(mask, need, 0)
        bpr = self.blocks_per_row
        j = jnp.arange(bpr, dtype=jnp.int32)[None, :]
        if shared is None:
            shared = jnp.full((n, bpr), -1, jnp.int32)
        else:
            shared = jnp.asarray(shared, jnp.int32)
        if pin is None:
            pin = jnp.zeros((n, bpr), bool)
        n_sh = jnp.sum((shared >= 0) & (j < need[:, None]),
                       axis=1).astype(jnp.int32)
        n_sh = jnp.where(mask, n_sh, 0)
        fresh_need = need - n_sh
        # Free block ids in index order, free-first (stable).
        is_free = self.refcount == 0
        free_ids = jnp.argsort(jnp.where(is_free, 0, 1),
                               stable=True).astype(jnp.int32)
        n_free = jnp.sum(is_free).astype(jnp.int32)

        # Sequential first-fit: row i succeeds iff its fresh blocks fit
        # after the rows admitted before it; failed rows reserve nothing.
        def fit(acc, fn):
            ok = acc + fn <= n_free
            return acc + jnp.where(ok, fn, 0), (ok, acc)

        _, (row_ok, starts) = jax.lax.scan(fit, jnp.int32(0), fresh_need)
        row_ok = row_ok & mask
        col_fresh = j - n_sh[:, None]                     # (n, bpr)
        is_shared = row_ok[:, None] & (j < n_sh[:, None])
        is_fresh = row_ok[:, None] & (col_fresh >= 0) & (j < need[:, None])
        want = starts[:, None] + col_fresh
        phys = free_ids[jnp.clip(want, 0, self.n_blocks - 1)]
        new_rows = jnp.where(is_shared, shared,
                             jnp.where(is_fresh, phys, -1))
        table = set_rows(self.table, rows, new_rows, mask)
        # refcount: +1 per mapped entry (shared or fresh), +1 extra per
        # pinned column
        inc = jnp.where(new_rows >= 0,
                        1 + (pin & (new_rows >= 0)).astype(jnp.int32), 0)
        refcount = self.refcount.at[
            jnp.where(new_rows >= 0, new_rows, self.n_blocks).reshape(-1)
        ].add(inc.reshape(-1), mode="drop")
        # owner records the ALLOCATING row — fresh blocks only; mapping
        # a shared block never re-attributes its writer
        owner = self.owner.at[
            jnp.where(is_fresh, phys, self.n_blocks).reshape(-1)].set(
            jnp.broadcast_to(rows[:, None], is_fresh.shape).reshape(-1),
            mode="drop")
        return dataclasses.replace(self, table=table, owner=owner,
                                   refcount=refcount)

    def free(self, rows=None, mask=None) -> "PagedKVCache":
        """Drop masked rows' table references (in-graph: the scheduler
        calls this at retirement, inside the decode loop). Each block
        loses one reference per table occurrence in a freed row; it
        returns to the free-list only when the count reaches zero —
        blocks still mapped by other rows, or pinned by the host
        prefix index, survive. Idempotent: a row whose table was
        already cleared decrements nothing."""
        n = self.n_rows
        rows = _bcast_rows(rows, n)
        mask = jnp.ones((rows.shape[0],), bool) if mask is None else mask
        row_freed = set_rows(jnp.zeros((n,), bool), rows, mask, mask)
        ids = jnp.where(row_freed[:, None] & (self.table >= 0),
                        self.table, self.n_blocks)
        dec = jnp.zeros((self.n_blocks,), jnp.int32).at[
            ids.reshape(-1)].add(1, mode="drop")
        refcount = jnp.maximum(self.refcount - dec, 0)
        owner = jnp.where(refcount == 0, -1, self.owner)
        table = jnp.where(row_freed[:, None], -1, self.table)
        return dataclasses.replace(self, table=table, owner=owner,
                                   refcount=refcount)

    def release(self, block_ids) -> "PagedKVCache":
        """Drop ONE reference from each listed physical block (``-1``
        entries ignored) — the host prefix index evicting its pins.
        A block whose count reaches zero returns to the free-list."""
        ids = jnp.asarray(block_ids, jnp.int32)
        safe = jnp.where(ids >= 0, ids, self.n_blocks)
        dec = jnp.zeros((self.n_blocks,), jnp.int32).at[safe].add(
            1, mode="drop")
        refcount = jnp.maximum(self.refcount - dec, 0)
        owner = jnp.where(refcount == 0, -1, self.owner)
        return dataclasses.replace(self, owner=owner, refcount=refcount)

    def ensure_private(self, rows=None, *, start, width,
                       mask=None) -> "PagedKVCache":
        """In-graph copy-on-write: before ``rows`` write positions
        ``[start, start + width)``, any covered block with
        ``refcount > 1`` is copied (every layer) to a fresh block and
        this row's table entry repointed — other references keep
        reading the original bits. ``width`` is static; ``start`` is a
        scalar or per-row vector. The common no-sharing case pays one
        table/refcount lookup and a predicate — the copy lives behind
        a ``lax.cond``.

        A block's OWNER writes in place: extra references on an owned
        block (the prefix index's pin, placed at alloc) are claims on
        the content the owner is still producing — copying the owner
        away would leave the pinned block permanently half-written.
        Only non-owner rows (sharers that mapped the block later) get
        copied; the prefix index never serves a block to a sharer
        until the owner has finished writing it (READY discipline).

        On the serving path even sharer-CoW never actually fires: the
        scheduler caps sharing at full blocks strictly before the
        write frontier, so every write lands in a freshly-allocated
        block. It is the safety invariant that makes sharing
        composable with ANY caller of the write API (and the property
        tests drive it directly). If the pool is dry mid-copy the
        row's entry becomes ``-1`` — its colliding write drops; the
        shared bits stay intact for the other readers.
        """
        rows = _bcast_rows(rows, self.n_rows)
        n = rows.shape[0]
        mask = jnp.ones((n,), bool) if mask is None else mask
        start = jnp.asarray(start, jnp.int32)
        if start.ndim == 0:
            start = jnp.full((n,), start, jnp.int32)
        bpr = self.blocks_per_row
        # static candidate window: [start, start+width) spans at most
        # (width - 1) // block + 2 table columns
        span = (int(width) - 1) // self.block + 2
        cols = (start // self.block)[:, None] \
            + jnp.arange(span, dtype=jnp.int32)[None, :]   # (n, span)
        covered = (cols * self.block < (start + int(width))[:, None]) \
            & (cols < bpr)
        valid = mask[:, None] & covered
        blk = self.table[rows[:, None], jnp.clip(cols, 0, bpr - 1)]
        safe_blk = jnp.clip(blk, 0)
        needs = valid & (blk >= 0) \
            & (self.refcount[safe_blk] > 1) \
            & (self.owner[safe_blk] != rows[:, None])

        def do_cow(cache):
            is_free = cache.refcount == 0
            free_ids = jnp.argsort(jnp.where(is_free, 0, 1),
                                   stable=True).astype(jnp.int32)
            n_free = jnp.sum(is_free).astype(jnp.int32)
            flat = needs.reshape(-1)
            order = jnp.cumsum(flat.astype(jnp.int32)) - 1
            ok = flat & (order < n_free)
            fresh = jnp.where(
                ok, free_ids[jnp.clip(order, 0, cache.n_blocks - 1)],
                cache.n_blocks)
            old = blk.reshape(-1)
            old_safe = jnp.clip(old, 0, cache.n_blocks - 1)
            k_pool = cache.k_pool.at[:, fresh].set(
                cache.k_pool[:, old_safe], mode="drop")
            v_pool = cache.v_pool.at[:, fresh].set(
                cache.v_pool[:, old_safe], mode="drop")
            # repoint the row's entry (fresh copy, or -1 when the pool
            # is dry); the old block loses this row's reference either
            # way
            rix = jnp.where(needs, rows[:, None], cache.n_rows)
            table = cache.table.at[
                rix.reshape(-1),
                jnp.clip(cols, 0, bpr - 1).reshape(-1)].set(
                jnp.where(ok, fresh, -1), mode="drop")
            dec = jnp.zeros((cache.n_blocks,), jnp.int32).at[
                jnp.where(flat, old_safe, cache.n_blocks)].add(
                1, mode="drop")
            refcount = jnp.maximum(cache.refcount - dec, 0)
            refcount = refcount.at[fresh].set(1, mode="drop")
            owner = jnp.where(refcount == 0, -1, cache.owner)
            owner = owner.at[fresh].set(
                jnp.broadcast_to(rows[:, None], needs.shape).reshape(-1),
                mode="drop")
            return dataclasses.replace(cache, k_pool=k_pool,
                                       v_pool=v_pool, table=table,
                                       owner=owner, refcount=refcount)

        return jax.lax.cond(jnp.any(needs), do_cow, lambda c: c, self)

    # ---- cross-pool block shipping (disaggregated serving) ----

    def export_rows(self, rows, n_cols: int):
        """Gather ``rows``' leading ``n_cols`` table columns into fresh
        ``(L, R, n_cols, KV, block, hd)`` K/V buffers — block-granular,
        layout-preserving, and *fresh* (no aliasing into the pool), so
        the caller can ``device_put`` the result into another pool's
        sharding while this pool keeps mutating. Unallocated columns
        (``table == -1`` — a short prompt's tail) come back zeroed; the
        importer's table routes them nowhere, so the zeros are inert.
        ``n_cols`` is static (the wire shape)."""
        rows = jnp.asarray(rows, jnp.int32)
        cols = self.table[rows, :int(n_cols)]             # (R, n_cols)
        live = (cols >= 0)[None, :, :, None, None, None]
        safe = jnp.clip(cols, 0)
        k = jnp.where(live, self.k_pool[:, safe], 0)
        v = jnp.where(live, self.v_pool[:, safe], 0)
        return k, v

    def import_rows(self, rows, k_data, v_data,
                    mask=None) -> "PagedKVCache":
        """Scatter ``export_rows``-shaped buffers ``(L, R, n_cols,
        KV, block, hd)`` into ``rows``' leading table columns — the
        receiving half of a block shipment. The rows must already hold
        fresh allocations (``alloc`` first); columns the destination
        table doesn't back (``-1``) and unmasked rows drop, so a short
        shipment into a longer allocation only touches what it
        carries."""
        rows = jnp.asarray(rows, jnp.int32)
        n, n_cols = rows.shape[0], k_data.shape[2]
        mask = jnp.ones((n,), bool) if mask is None else mask
        dst = self.table[rows, :n_cols]                   # (n, n_cols)
        dst = jnp.where((dst >= 0) & mask[:, None], dst, self.n_blocks)
        flat = dst.reshape(-1)
        kd = k_data.astype(self.k_pool.dtype).reshape(
            (k_data.shape[0], n * n_cols) + k_data.shape[3:])
        vd = v_data.astype(self.v_pool.dtype).reshape(
            (v_data.shape[0], n * n_cols) + v_data.shape[3:])
        k_pool = self.k_pool.at[:, flat].set(kd, mode="drop")
        v_pool = self.v_pool.at[:, flat].set(vd, mode="drop")
        return dataclasses.replace(self, k_pool=k_pool, v_pool=v_pool)

    def shardings(self, rules, mesh=None, row_axis: str = sh.BATCH):
        pool = rules.sharding(
            (sh.LAYERS, sh.BLOCK, sh.CACHE_KV, None, sh.CACHE_HD), mesh,
            dims=tuple(self.k_pool.shape))
        vec = rules.sharding((sh.BLOCK,), mesh,
                             dims=tuple(self.owner.shape))
        return PagedKVCache(
            k_pool=pool, v_pool=pool,
            table=rules.sharding((row_axis, None), mesh,
                                 dims=tuple(self.table.shape)),
            owner=vec, refcount=vec,
            max_len=self.max_len)


def make_kv_cache(cfg, n_layers: int, n_rows: int, max_len: int, *,
                  impl: str = "dense", block: int = 16,
                  n_blocks: Optional[int] = None,
                  abstract: bool = False) -> KVCache:
    """Build a self-attention KV cache for ``cfg``'s head geometry."""
    KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    dt = cfg.dtype("compute")
    if impl == "dense":
        return DenseKVCache.create(n_layers, n_rows, max_len, KV, hd, dt,
                                   abstract=abstract)
    if impl == "paged":
        return PagedKVCache.create(n_layers, n_rows, max_len, KV, hd, dt,
                                   block=block, n_blocks=n_blocks,
                                   abstract=abstract)
    raise ValueError(f"unknown kv cache impl {impl!r}")
