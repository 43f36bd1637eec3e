"""Paged decode KV cache: a global block pool + per-slot block tables.

The dense cache (:mod:`apex_tpu.serving.kv_cache`) preallocates
``[layers, slots, max_len, ...]`` — worst-case memory per slot, cap on
concurrency at ``slots``, and a prefix cache that must *copy* K/V
through host-dispatched span reads.  The paged layout replaces the
per-slot buffer with a **global pool of fixed-size blocks**

    ``k`` / ``v``: ``[layers, num_blocks, block_size, kv_heads, head_dim]``

plus a per-slot **block table** ``tables[slot, i] -> pool block id``:
memory scales with *used* tokens (a slot holding 40 tokens pins
``ceil(40 / block_size)`` blocks, not ``max_len`` rows), concurrency is
priced in blocks, and cross-request prefix reuse becomes **table
aliasing**: a hit appends the shared block ids to the new slot's table
— zero device reads, zero copies — with host-side refcounts deciding
when a block really frees.  Copy-on-write keeps sharers bit-isolated:
any write into a block referenced more than once first copies it.

Exactness is the same story as the dense cache, told through a gather:
attention reads a slot's K/V as the fixed-extent view
``pool[table[slot]] -> [max_len, kv_heads, head_dim]`` (one static
gather shape for every slot state), masked at the flash kernels' exact
``-1e30`` so rows past the committed length — stale garbage, bucket
padding, other streams' bytes behind un-CoW'd shared blocks — carry
exactly zero weight.  Valid rows hold bit-for-bit the values the dense
cache would hold at the same positions, the reduction extents are
identical, and therefore the logits are **bit-identical** to the dense
engine (pinned by ``tests/test_serving_paged.py`` against both the
dense engine and the uncached shape-stable forward).

Under tensor-parallel serving the pool shards exactly like the dense
cache — ``kv_heads`` is the split axis (``[layers, num_blocks,
block_size, kv_heads/tp, head_dim]`` per rank) while ``tables`` and
``lengths`` replicate, so every rank routes rows through the *same*
block ids and the host-side manager (refcounts, CoW planning) stays
mesh-oblivious: one table flush commits identically to all ranks.

Layout invariants the device ops rely on:

- **Block 0 is the null block**: never allocated, never read unmasked.
  Free slots' table entries are 0, so a gather through a fresh table
  lands on finite zeros (masked reads must never see NaN — ``0 * NaN``
  would poison the PV matmul where masked probabilities are exact 0).
- Writes are **drop-safe scatters**: a row whose table entry is the
  null block (bucket padding past the allocated frontier) or whose
  position is ``< 0`` (an inactive decode lane's sentinel) or
  ``>= max_len`` redirects to physical index ``num_blocks`` and is
  dropped by the ``mode="drop"`` scatter — unlike the dense cache,
  padding is never written at all, so a stale table can never route a
  garbage row into another stream's live block.
- The host :class:`PagedCacheManager` owns allocation, refcounts, CoW
  planning and the table mirror; the device ``tables`` array is a
  snapshot flushed (one small host->device transfer) only on steps
  whose allocation actually changed — the common decode step inside a
  block crosses no boundary and flushes nothing.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from apex_tpu._logging import get_logger
from apex_tpu.serving.kv_cache import FloatRows, Int8Rows

__all__ = ["PagedCacheConfig", "PagedLayout", "PagedKVCache",
           "QuantPagedKVCache", "BlockPoolExhausted", "PagedCacheManager",
           "blocks_per_slot", "bytes_per_block"]

logger = get_logger("serving.paged_kv_cache")

NULL_BLOCK = 0          # reserved: finite zeros, never allocated


class BlockPoolExhausted(RuntimeError):
    """No free block in the pool (and reclaim, if any, freed none) —
    block-granular out-of-memory backpressure.  Raised, never clamped:
    a clamped write would silently corrupt another stream's block."""


@dataclasses.dataclass(frozen=True)
class PagedCacheConfig:
    """Opt-in knob for the paged cache layout
    (``DecodeEngine(..., paged=PagedCacheConfig(...))``).

    ``block_size``: tokens per pool block.  ``num_blocks``: total pool
    blocks *including* the reserved null block (``None`` — sized for
    dense-capacity parity: ``slots * ceil(max_len / block_size) + 1``,
    so every slot can still fill to ``max_len`` with zero sharing).
    """

    block_size: int = 16
    num_blocks: Optional[int] = None

    def __post_init__(self):
        if self.block_size < 1:
            raise ValueError(
                f"block_size must be >= 1, got {self.block_size}")
        if self.num_blocks is not None and self.num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (the null block plus at least "
                f"one allocatable), got {self.num_blocks}")

    def pool_blocks(self, slots: int, max_len: int) -> int:
        """``num_blocks``, or the dense-capacity parity it defaults to."""
        if self.num_blocks is not None:
            return int(self.num_blocks)
        return slots * blocks_per_slot(max_len, self.block_size) + 1

    def init_cache(self, layers: int, rows, *, slots: int, max_len: int,
                   dtype, int8: bool):
        """Zero-filled pool for ``layers`` layers of ``rows`` (a
        :class:`~apex_tpu.serving.kv_cache.KVRows`) — what
        :func:`apex_tpu.serving.kv_cache.init_cache` builds for this
        layout.  Block 0 is the null block; all table entries start
        there."""
        cls = QuantPagedKVCache if int8 else PagedKVCache
        shape = (layers, self.pool_blocks(slots, max_len), self.block_size,
                 rows.kv_heads, rows.head_dim)
        return cls(
            **cls.zeros(shape, dtype),
            tables=jnp.zeros(
                (slots, blocks_per_slot(max_len, self.block_size)),
                jnp.int32),
            lengths=jnp.zeros((slots,), jnp.int32), max_len=int(max_len))


def blocks_per_slot(max_len: int, block_size: int) -> int:
    """Table width: blocks covering ``max_len`` rows (ceil division)."""
    return -(-int(max_len) // int(block_size))


# ---------------------------------------------------------------------------
# the layout: drop-safe routing for writes, fixed-extent gathers for reads
# ---------------------------------------------------------------------------


class PagedLayout:
    """``[layers, num_blocks, block_size, ...]`` pools read and written
    through ``tables``: the index a logical row becomes and the view a
    read takes (what :class:`~apex_tpu.serving.kv_cache.DenseLayout`
    answers for slot rows), for any storage format."""

    # a lane's rows are wherever its table row says: reads gather
    lane_rows_in_place = False

    @property
    def num_layers(self) -> int:
        return self.k.shape[0]

    @property
    def num_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def block_size(self) -> int:
        return self.k.shape[2]

    @property
    def num_slots(self) -> int:
        return self.tables.shape[0]

    @property
    def blocks_per_slot(self) -> int:
        return self.tables.shape[1]

    def _table_row(self, slot):
        return lax.dynamic_index_in_dim(
            self.tables, jnp.asarray(slot, jnp.int32), axis=0,
            keepdims=False)

    def _route_rows(self, table_row, rows):
        """Map logical slot rows -> ``(physical block id, offset in
        block)``, with every undroppable-unsafe row redirected to
        ``num_blocks`` (out of pool range, dropped by ``mode="drop"``):
        rows ``< 0`` (inactive-lane sentinel), rows ``>= max_len``
        (bucket-padding overhang past capacity), and rows whose table
        entry is the null block (padding past the allocated frontier, or a
        released slot's zeroed table).  Real rows always route to a live
        allocated block — the host manager guarantees the table covers the
        declared write span before the dispatch."""
        bs = self.block_size
        safe = jnp.clip(rows, 0, self.max_len - 1)
        blk = jnp.clip(safe // bs, 0, self.blocks_per_slot - 1)
        if table_row.ndim == 2:
            # batched append: row i must read SLOT i's own table (the
            # diagonal), not every slot's entry at offset blk[i] — a plain
            # take here is an outer product that scatters each lane's token
            # through every other slot's table
            entry = jnp.take_along_axis(table_row, blk[:, None],
                                        axis=-1)[:, 0]
        else:
            entry = jnp.take(table_row, blk, axis=-1)
        ok = (rows >= 0) & (rows < self.max_len) & (entry > NULL_BLOCK)
        phys = jnp.where(ok, entry, self.num_blocks)
        return phys, safe % bs

    def _gathered(self, arr, tables) -> jax.Array:
        """``arr`` (one layer of a pool) gathered through ``tables`` and
        re-laid as contiguous token rows, sliced to exactly ``max_len`` —
        the fixed-extent read every attention caller shares.  The gather
        shape is static (``tables``' shape), so one compiled program
        serves every slot state."""
        g = jnp.take(arr, tables, axis=0)     # [..., bps, bs, kvh(, hd)]
        n = tables.ndim                       # the block-size axis of g
        flat = g.reshape(g.shape[:n - 1] + (g.shape[n - 1] * g.shape[n],)
                         + g.shape[n + 1:])
        return flat[(slice(None),) * (n - 1) + (slice(None, self.max_len),)]

    def chunk_index(self, layer, slot, rows):
        """Where ``rows`` of one slot live: through the slot's own table
        row.  Rows routing to the null block (bucket padding past the
        allocated frontier) or past ``max_len`` are dropped — the paged
        cache never writes padding into a block, so no stale table can
        route one into a live neighbor."""
        return (layer,) + self._route_rows(self._table_row(slot), rows)

    def lane_index(self, layer, positions):
        """Where row ``positions[lane]`` of every lane lives; ``-1`` (an
        idle lane, see :meth:`decode_positions`) is dropped."""
        return (layer,) + self._route_rows(self.tables, positions)

    def lanes_view(self, layer):
        """``view(pool)``: every slot's rows as ``[slots, max_len, ...]`` —
        same shape, same masked-read contract, same reduction extents as
        the dense ``buf[layer]``; unallocated rows are the null block's
        exact finite zeros."""
        return lambda pool: self._gathered(pool[layer], self.tables)

    def slot_view(self, layer, slot):
        """``view(pool)``: one slot's rows as ``[max_len, ...]``."""
        table_row = self._table_row(slot)
        return lambda pool: self._gathered(pool[layer], table_row)

    def decode_positions(self, active):
        """Where a decode step appends: a dense lane parks an idle write
        in its own masked rows, but a table has no private scratch (a
        stale entry could route the row into another stream's live
        block), so idle lanes carry ``-1`` and their writes are
        dropped."""
        return jnp.where(active, self.lengths, jnp.int32(-1))

    def copy_block(self, src, dst):
        """Pool block ``src`` -> ``dst`` across every layer and every
        stored pool (an int8 block's bytes are payload AND scales: a copy
        of one without the other would dequantize the writer's copy
        through the sharers' scales)."""
        s = jnp.asarray(src, jnp.int32)
        d = jnp.asarray(dst, jnp.int32)
        return dataclasses.replace(self, **{
            name: getattr(self, name).at[:, d].set(lax.dynamic_index_in_dim(
                getattr(self, name), s, axis=1, keepdims=False))
            for name in self.stored})


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("k", "v", "tables", "lengths"),
                   meta_fields=("max_len",))
@dataclasses.dataclass(frozen=True)
class PagedKVCache(PagedLayout, FloatRows):
    """Block-pool decode cache.

    ``k`` / ``v``: ``[layers, num_blocks, block_size, kv_heads,
    head_dim]``; ``tables``: ``[slots, blocks_per_slot]`` int32 pool
    block ids (0 = the null block / unallocated); ``lengths``:
    ``[slots]`` int32 valid tokens per slot.  ``max_len`` is pytree
    *metadata* (a static int): the per-slot capacity, which the table
    extent ``blocks_per_slot * block_size`` may slightly exceed when
    ``max_len`` is not a block multiple — reads slice the gathered view
    back to exactly ``max_len`` rows so every reduction extent matches
    the dense cache bit for bit.
    """

    k: jax.Array
    v: jax.Array
    tables: jax.Array
    lengths: jax.Array
    max_len: int


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("k", "v", "k_scale", "v_scale", "tables",
                                "lengths"),
                   meta_fields=("max_len",))
@dataclasses.dataclass(frozen=True)
class QuantPagedKVCache(PagedLayout, Int8Rows):
    """KV-int8 twin of :class:`PagedKVCache`: the same block pool and
    table routing, rows stored as
    :class:`~apex_tpu.serving.kv_cache.Int8Rows` — scales live in a
    parallel pool ``[layers, num_blocks, block_size, kv_heads]`` indexed
    by the SAME block ids, so aliasing, CoW, fork, and release move
    payload and scales together by construction (a shared block shares
    its scales; a CoW copy copies both).  Unallocated rows carry
    q=0/scale=1 and so stay exact finite zeros, preserving the
    masked-read ``0 * NaN``-safety invariant.
    """

    k: jax.Array
    v: jax.Array
    k_scale: jax.Array
    v_scale: jax.Array
    tables: jax.Array
    lengths: jax.Array
    max_len: int


def bytes_per_block(cache) -> int:
    """True resident bytes one pool block pins across every layer and
    stored pool.  For the fp pool that is the k+v payload; for the quant
    pool the fp32 scale pools ride the same block ids, so their bytes
    are part of the block (an accounting that read ``k.dtype.itemsize``
    alone would undercount an int8 pool by its scale overhead)."""
    total = 0
    for arr in (getattr(cache, name) for name in cache.stored):
        shape = arr.shape            # [L, num_blocks, block_size, ...]
        per = int(np.prod((shape[0],) + shape[2:]))
        total += jnp.dtype(arr.dtype).itemsize * per
    return int(total)


# ---------------------------------------------------------------------------
# host-side allocation: refcounts, block tables, CoW planning
# ---------------------------------------------------------------------------


class PagedCacheManager:
    """Host bookkeeping for one :class:`PagedKVCache`: a free-list
    allocator with per-block refcounts, the per-slot table mirror, and
    copy-on-write planning.

    Everything here is pure host state updated at dispatch boundaries;
    the engine flushes the table mirror to the device (one small
    transfer) only when :meth:`consume_dirty` reports a change, and
    runs the CoW copy pairs :meth:`ensure` returns *before* the write
    that needed them.  Refcount semantics: every user of a block holds
    one reference — the owning slot's table, each aliasing slot's
    table, and each prefix-cache entry.  A block frees (returns to the
    LIFO free list — deterministic ids for replayable tests) when its
    count reaches zero; a write into a block with count > 1 must CoW
    first, which is what keeps sharers bit-isolated.

    ``reclaim``: optional callback ``(n_blocks) -> freed`` consulted
    once when the free list runs dry (the scheduler wires prefix-cache
    eviction here); if the pool is still empty afterwards the
    allocation raises :class:`BlockPoolExhausted`.
    """

    def __init__(self, *, slots: int, max_len: int, block_size: int,
                 num_blocks: int):
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if not 1 <= block_size <= max_len:
            raise ValueError(
                f"block_size {block_size} must be in [1, max_len "
                f"{max_len}]")
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (null block + 1), got "
                f"{num_blocks}")
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        self.blocks_per_slot = blocks_per_slot(max_len, block_size)
        self._refs = np.zeros((num_blocks,), np.int64)
        # LIFO free list, block 0 (null) excluded forever
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._tables = np.zeros((self.slots, self.blocks_per_slot),
                                np.int32)
        self._owned = np.zeros((self.slots,), np.int64)
        self._dirty = True          # fresh mirror vs whatever device held
        self.reclaim: Optional[Callable[[int], int]] = None
        # cumulative structural accounting (bench + metrics read these)
        self.allocated_total = 0
        self.freed_total = 0
        self.cow_total = 0
        self.aliased_total = 0

    # ---- introspection ---------------------------------------------------
    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        """Allocated (non-null) blocks — the pool-residency numerator."""
        return self.num_blocks - 1 - len(self._free)

    @property
    def utilization(self) -> float:
        """Allocated blocks / allocatable blocks, in ``[0, 1]``."""
        return self.used_blocks / max(self.num_blocks - 1, 1)

    def refcount(self, block_id: int) -> int:
        return int(self._refs[block_id])

    def slot_block_ids(self, slot: int) -> List[int]:
        """The slot's allocated pool blocks, in token order."""
        return [int(b) for b in self._tables[slot, :self._owned[slot]]]

    def owned_blocks(self, slot: int) -> int:
        """How many table entries the slot holds — O(1), no list
        materialization (the admission gate reads this per active
        stream per step)."""
        return int(self._owned[slot])

    def table_snapshot(self) -> np.ndarray:
        return self._tables.copy()

    def consume_dirty(self) -> bool:
        """True exactly once after any mirror change — the engine's
        flush-only-when-needed signal."""
        dirty, self._dirty = self._dirty, False
        return dirty

    def stats(self) -> dict:
        return {"num_blocks": self.num_blocks,
                "block_size": self.block_size,
                "used_blocks": self.used_blocks,
                "free_blocks": self.free_blocks,
                "allocated_total": self.allocated_total,
                "freed_total": self.freed_total,
                "cow_total": self.cow_total,
                "aliased_total": self.aliased_total}

    # ---- refcounting -----------------------------------------------------
    def ref(self, block_ids: Sequence[int]) -> None:
        """Add one reference per block (a prefix-cache entry, an
        aliasing slot).  All-or-nothing: every id is validated before
        any count moves, so a stale id mid-list (a block freed between
        capture and alias) cannot leak permanent references onto the
        earlier ids."""
        for b in block_ids:
            if not 0 < b < self.num_blocks:
                raise ValueError(f"block id {b} out of pool range "
                                 f"(1, {self.num_blocks})")
            if self._refs[b] < 1:
                raise ValueError(
                    f"ref of free block {b} — a reference must derive "
                    f"from a live owner (alias what exists, never "
                    f"resurrect)")
        for b in block_ids:
            self._refs[b] += 1

    def deref(self, block_ids: Sequence[int]) -> int:
        """Drop one reference per block; blocks reaching zero return to
        the free list.  Returns how many actually freed.
        All-or-nothing like :meth:`ref`: a mispaired id raises before
        any count moves (duplicates in one call count against the
        same refcount)."""
        seen: dict = {}
        for b in block_ids:
            seen[b] = seen.get(b, 0) + 1
            if self._refs[b] < seen[b]:
                raise ValueError(f"deref of unreferenced block {b} — "
                                 f"ref/deref must pair")
        freed = 0
        for b in block_ids:
            self._refs[b] -= 1
            if self._refs[b] == 0:
                self._free.append(int(b))
                self.freed_total += 1
                freed += 1
        return freed

    # ---- allocation + CoW ------------------------------------------------
    def _alloc_one(self) -> int:
        if not self._free and self.reclaim is not None:
            self.reclaim(1)
        if not self._free:
            raise BlockPoolExhausted(
                f"KV block pool exhausted ({self.num_blocks - 1} blocks, "
                f"all referenced) — release streams, evict prefix-cache "
                f"entries, or size num_blocks for the offered load")
        b = self._free.pop()
        self._refs[b] = 1
        self.allocated_total += 1
        return b

    def ensure(self, slot: int, start: int, stop: int
               ) -> List[Tuple[int, int]]:
        """Make rows ``[start, stop)`` of ``slot`` writable in place:
        allocate table entries the span needs, and plan a copy-on-write
        for every already-owned span block whose refcount exceeds one
        (someone else — an aliasing slot or a prefix-cache entry — can
        see its bytes).  Returns ``(src, dst)`` block-id pairs the
        caller must device-copy *before* the write dispatch.  Raises
        :class:`BlockPoolExhausted` (never clamps) when the pool can't
        cover the span."""
        if not 0 <= slot < self.slots:
            raise ValueError(f"slot {slot} out of range [0, {self.slots})")
        if not 0 <= start < stop <= self.max_len:
            raise ValueError(
                f"write span [{start}, {stop}) outside [0, "
                f"{self.max_len}]")
        bs = self.block_size
        cow: List[Tuple[int, int]] = []
        for idx in range(start // bs, -(-stop // bs)):
            if idx >= self._owned[slot]:
                # the span grows the slot: fresh exclusive blocks.
                # Growth is contiguous by construction (writes extend
                # the frontier), but guard it anyway — a gap would
                # leave a null entry under committed rows
                if idx != self._owned[slot]:
                    raise ValueError(
                        f"non-contiguous table growth for slot {slot}: "
                        f"block {idx} past frontier {self._owned[slot]}")
                self._tables[slot, idx] = self._alloc_one()
                self._owned[slot] += 1
                self._dirty = True
            else:
                old = int(self._tables[slot, idx])
                if self._refs[old] > 1:
                    new = self._alloc_one()
                    self._refs[old] -= 1     # the slot's own reference
                    self._tables[slot, idx] = new
                    self._dirty = True
                    self.cow_total += 1
                    cow.append((old, new))
        return cow

    def alias(self, slot: int, block_ids: Sequence[int],
              tokens: int) -> None:
        """Point an empty slot's table at shared blocks (a prefix-cache
        hit): zero device reads, zero copies — each block just gains a
        reference.  ``tokens`` is the valid-row count the ids cover
        (the caller commits it as the slot length)."""
        if not 0 <= slot < self.slots:
            raise ValueError(f"slot {slot} out of range [0, {self.slots})")
        if self._owned[slot]:
            raise ValueError(
                f"alias into slot {slot} which owns "
                f"{int(self._owned[slot])} blocks — release it first")
        ids = [int(b) for b in block_ids]
        if len(ids) > self.blocks_per_slot:
            raise ValueError(
                f"{len(ids)} blocks exceed the table width "
                f"{self.blocks_per_slot}")
        if not 0 < tokens <= len(ids) * self.block_size:
            raise ValueError(
                f"{tokens} tokens not coverable by {len(ids)} blocks of "
                f"{self.block_size}")
        self.ref(ids)                      # validates liveness first
        self._tables[slot, :len(ids)] = ids
        self._owned[slot] = len(ids)
        self._dirty = True
        self.aliased_total += len(ids)

    def fork(self, src: int, dst: int) -> List[int]:
        """Share every block of ``src`` into empty slot ``dst`` (the
        parallel-sampling / n-best branch point).  Both slots' next
        write into any shared block — including the partial tail block
        both are about to append into — triggers CoW, so the streams
        stay bit-isolated.  Returns the shared ids."""
        ids = self.slot_block_ids(src)
        if not ids:
            raise ValueError(f"fork of empty slot {src}")
        self.alias(dst, ids, tokens=len(ids) * self.block_size)
        self.aliased_total -= len(ids)     # alias() counted; fork is not
        return ids                         # a prefix-cache hit

    def release(self, slot: int) -> int:
        """Drop the slot's references (blocks free unless shared) and
        zero its table row.  Returns blocks actually freed."""
        ids = self.slot_block_ids(slot)
        freed = self.deref(ids) if ids else 0
        if ids:
            self._tables[slot, :] = NULL_BLOCK
            self._owned[slot] = 0
            self._dirty = True
        return freed
