"""Slot-indexed decode KV cache with shape-stable, jittable updates.

The serving-side win on TPUs (PAPERS.md: "Fine-Tuning and Serving Gemma
on Google Cloud TPU") comes from never letting XLA see a new shape after
warmup: the cache is **preallocated** at ``[layers, slots, max_len,
kv_heads, head_dim]``, every update is a shape-stable write into that
fixed buffer (a drop-mode row scatter for prefill chunks — overhanging
bucket padding must be dropped, never clamped backward — and one row
per lane, scattered the same way, for decode appends), and attention reads
the *whole* ``max_len`` axis with a per-slot length mask — so one
compiled decode step serves every request mix, every sequence length,
and every slot assignment with zero retraces.

Layout choices:

- One stacked ``k`` / ``v`` array over layers (not a per-layer list):
  layer index is a Python int at trace time, so ``cache.k[i]`` is a
  static slice, while the whole cache stays a single pytree leaf pair —
  cheap to thread functionally through the decoder stack and to donate.
- ``lengths[slot]`` is the number of *valid* tokens in the slot.  Bytes
  past the length are garbage (stale evictions, prompt padding) by
  contract; every reader must mask with :func:`valid_token_mask`.
  Eviction is therefore O(1): zero the length, reuse the slot.
- Updates are pure functions returning a new :class:`KVCache` (the
  arrays are donated/aliased by XLA under jit); nothing here mutates.
- Under tensor-parallel serving (``DecodeEngine(..., tp=...)``) the
  ``kv_heads`` axis is the sharded one — each mesh rank holds
  ``kv_heads / tp`` head groups of every slot, ``[layers, slots,
  max_len, kv_heads/tp, head_dim]`` per rank — while ``lengths`` is
  replicated (every rank must mask identically).  Nothing in this
  module changes: inside ``shard_map`` these ops see the local shard
  as an ordinary cache with fewer heads.

Three decisions, each made in one place of this module and its paged
twin, and one seam a model's attention calls through:

- **Storage format** - how a row is stored: :class:`FloatRows` (the rows,
  cast to the cache's dtype) or :class:`Int8Rows` (int8 payload + one
  float32 scale a (row, head)).  Each is a ``store`` / ``load`` pair;
  every write below is "for each stored buffer, a drop-mode scatter of
  ``store``'s rows", every read "``load`` of a view of each buffer".
- **Layout** - where a row lives: :class:`DenseLayout` (slot rows, here)
  or the block table of :mod:`apex_tpu.serving.paged_kv_cache`.  A layout
  owns the index a logical ``(layer, slot | lanes, rows)`` becomes, the
  view a read takes, and the positions a decode step appends at - and
  nothing about int8.
- **What a model keeps a slot** - ``model.cache_layers()``: one of
  :class:`KVRows`, :class:`RecurrentRows`, :class:`CallCounters` or None
  a layer; :func:`init_cache` builds every cache from that.
- **The seam**: :func:`decode_attend` and :func:`prefill_attend` are the
  whole step an attention layer needs - append or chunk-write, view, cast
  to the query's dtype, the grouped masked read
  (:func:`cached_attention`) - whatever the layout and the format.  A
  model imports those two (and, for a recurrent layer, the state
  functions at the end of this module) and names no cache class.

Masking exactness: masked attention scores sit at ``-1e30`` (the flash
kernels' ``_NEG_INF``), so ``exp(masked - max)`` underflows to exactly
``0.0`` and a padded-to-``max_len`` softmax/PV read is **bit-identical**
to the unpadded computation — the property the serving parity tests
(`tests/test_serving.py`) pin against the uncached forward.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.amp.quant import dequantize_int8, quantize_int8
from apex_tpu.ops._dispatch import record_dispatch
from apex_tpu.ops.cached_decode_attention import (
    block_rows,
    cached_decode_attention,
)
from apex_tpu.ops.flash_attention import _NEG_INF

__all__ = ["KVCache", "QuantKVCache", "FloatRows", "Int8Rows", "DenseLayout",
           "init_cache", "prefill_into_slot", "append_token",
           "commit_slot_length", "release_slot", "valid_token_mask",
           "read_slot_region", "write_slot_region", "decode_read",
           "slot_read", "value_dtype", "gather_slot_rows", "DECODE_QPAD",
           "cached_attention", "decode_attention", "decode_attend",
           "prefill_attend", "KVRows", "RecurrentRows", "CallCounters",
           "RecurrentState", "HybridCache", "slot_state", "write_slot_state",
           "write_lane_state", "add_counts"]


# ---- storage format: how a row is stored -----------------------------------


class FloatRows:
    """K/V rows stored as they are, in the cache's dtype.

    A storage format is four things: ``stored``, the names of the buffers
    rows live in; ``zeros(shape, dtype)``, those buffers empty;
    ``store(k_rows, v_rows)``, the rows to write into each; and
    ``load(view)``, the values read back through ``view(buffer)`` (the
    layout's way to a layer's rows)."""

    stored = ("k", "v")

    @staticmethod
    def zeros(shape, dtype) -> dict:
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    @property
    def dtype(self):
        return self.k.dtype

    @property
    def value_dtype(self):
        return self.k.dtype

    def store(self, k_rows, v_rows) -> dict:
        return {"k": k_rows, "v": v_rows}

    def load(self, view) -> tuple:
        return view(self.k), view(self.v)


class Int8Rows:
    """K/V rows stored as symmetric int8 with one float32 scale per cached
    (position, head) — the per-token-per-head grouping that keeps a
    long-tailed row from crushing its neighbors' resolution while the
    scale overhead stays ``4 / head_dim`` of the fp32 bytes.  The scale
    buffers lack the payload's last axis and ride the same row indices:
    a dropped row drops BOTH or NEITHER, and under tensor parallelism
    they shard head-wise on the payload's axis-3 spec.

    Reads hand out DEQUANTIZED float32 rows, so every host consumer
    (prefix-cache spans, preemption snapshots, fleet stream exports)
    stays quantization-oblivious; a restore requantizes in-program, and
    because the group amax element always requantizes to exactly ±127
    the int8 payload survives that round trip bit for bit and the scales
    to 1 ulp (see serving/quant.py)."""

    stored = ("k", "v", "k_scale", "v_scale")

    @staticmethod
    def zeros(shape, dtype) -> dict:
        """Scales start at 1.0 (the zero-amax convention of
        :func:`apex_tpu.amp.quant.quantize_int8`): an unused row
        dequantizes to exact finite zeros, never NaN."""
        del dtype   # the format owns its storage dtype
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.ones(shape[:-1], jnp.float32),
                "v_scale": jnp.ones(shape[:-1], jnp.float32)}

    @property
    def dtype(self):
        """Payload dtype (int8) — see :func:`value_dtype` for the dtype
        reads dequantize to."""
        return self.k.dtype

    value_dtype = jnp.float32

    def store(self, k_rows, v_rows) -> dict:
        kq, ks = quantize_int8(k_rows, axis=-1)
        vq, vs = quantize_int8(v_rows, axis=-1)
        return {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}

    def load(self, view) -> tuple:
        return (dequantize_int8(view(self.k), view(self.k_scale)),
                dequantize_int8(view(self.v), view(self.v_scale)))


def value_dtype(cache) -> Any:
    """The dtype cache *reads* produce: the payload dtype for fp
    caches, fp32 (the dequant output) for quantized ones — what
    restore/capture plumbing must use for staging buffers instead of
    ``cache.dtype`` (int8 staging would destroy the values before the
    in-program requantize)."""
    return cache.value_dtype


def _write(cache, index, k_rows, v_rows):
    """``k_rows`` / ``v_rows`` at ``index`` of every stored buffer.  Always a
    scatter with ``mode="drop"``, never a ``dynamic_update_slice``: a row
    whose index is out of range (bucket padding overhanging the cache end,
    an idle lane's sentinel, a null block) is DROPPED — a dynamic update
    would clamp the whole block backward onto cached rows."""
    new = {}
    for name, rows in cache.store(k_rows, v_rows).items():
        buf = getattr(cache, name)
        new[name] = buf.at[index].set(rows.astype(buf.dtype), mode="drop")
    return dataclasses.replace(cache, **new)


# ---- layout: where a row lives ---------------------------------------------


class DenseLayout:
    """``[layers, slots, max_len, ...]`` buffers: a slot's rows are its own."""

    # ``buf[layer, lane]`` is the lane's rows: a kernel may index the
    # stored buffers itself
    lane_rows_in_place = True

    @property
    def num_layers(self) -> int:
        return self.k.shape[0]

    @property
    def num_slots(self) -> int:
        return self.k.shape[1]

    @property
    def max_len(self) -> int:
        return self.k.shape[2]

    def chunk_index(self, layer, slot, rows):
        """Where ``rows`` of one slot live."""
        return layer, jnp.asarray(slot, jnp.int32), rows

    def lane_index(self, layer, positions):
        """Where row ``positions[lane]`` of every lane lives.  A negative
        position goes out of range (plain indexing would wrap it to the
        slot's end) so that the write drops it."""
        rows = jnp.where(positions < 0, self.max_len, positions)
        return layer, jnp.arange(positions.shape[0], dtype=jnp.int32), rows

    def lanes_view(self, layer):
        """``view(buf)``: every slot's rows of one layer, ``[slots, max_len,
        ...]`` — the buffer's own rows."""
        return lambda buf: buf[layer]

    def slot_view(self, layer, slot):
        """``view(buf)``: one slot's rows of one layer, ``[max_len, ...]``."""
        s = jnp.asarray(slot, jnp.int32)
        return lambda buf: lax.dynamic_index_in_dim(buf[layer], s, axis=0,
                                                    keepdims=False)

    def decode_positions(self, active):
        """Where a decode step appends: an idle lane writes into its own
        masked rows (its length does not advance, so the row is
        unreadable)."""
        del active
        return self.lengths


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("k", "v", "lengths"), meta_fields=())
@dataclasses.dataclass(frozen=True)
class KVCache(DenseLayout, FloatRows):
    """Preallocated decode cache: one slot per in-flight request.

    ``k`` / ``v``: ``[layers, slots, max_len, kv_heads, head_dim]``;
    ``lengths``: ``[slots]`` int32 — valid tokens per slot (0 = free).
    """

    k: jax.Array
    v: jax.Array
    lengths: jax.Array


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("k", "v", "k_scale", "v_scale", "lengths"),
                   meta_fields=())
@dataclasses.dataclass(frozen=True)
class QuantKVCache(DenseLayout, Int8Rows):
    """KV-int8 twin of :class:`KVCache`: same slot-indexed layout, rows
    stored as :class:`Int8Rows`.

    ``k`` / ``v``: int8 ``[layers, slots, max_len, kv_heads,
    head_dim]``; ``k_scale`` / ``v_scale``: fp32 ``[layers, slots,
    max_len, kv_heads]``; ``lengths``: ``[slots]`` int32.  Every
    masking/length/drop-scatter contract of the fp cache holds
    unchanged.
    """

    k: jax.Array
    v: jax.Array
    k_scale: jax.Array
    v_scale: jax.Array
    lengths: jax.Array


# ---- writes and reads, once for every layout and format --------------------


def prefill_into_slot(cache, layer: int, slot, k_seq, v_seq, start=0):
    """Write one (padded) prompt chunk's K/V into one slot of one layer,
    at offset ``start`` (0 == a fresh prompt; later chunks of a long
    prompt pass the tokens-already-cached count).

    ``k_seq`` / ``v_seq``: ``[chunk_len, kv_heads, head_dim]``; ``slot``
    and ``start`` may be traced scalars, ``layer`` is a Python int.  Does
    NOT touch ``lengths`` — the caller sets the slot's *real* length once
    per model call (chunk padding past it stays masked garbage until the
    next chunk overwrites it).

    A bucket-padded tail chunk near the cache end (``start + chunk_len >
    max_len`` even though every *real* token fits) has its overhanging
    padding rows DROPPED (:func:`_write`); a paged cache also drops rows
    whose table entry is the null block, so padding past the allocated
    frontier is never written at all.
    """
    rows = jnp.asarray(start, jnp.int32) + jnp.arange(
        k_seq.shape[0], dtype=jnp.int32)
    return _write(cache, cache.chunk_index(layer, slot, rows), k_seq, v_seq)


def append_token(cache, layer: int, k_tok, v_tok, positions):
    """Write one token's K/V per slot at that slot's own position.

    ``k_tok`` / ``v_tok``: ``[slots, kv_heads, head_dim]``; ``positions``:
    ``[slots]`` int32 (``cache.decode_positions(active)`` — the next free
    index, or what the layout gives an idle lane).  One row scatter a
    buffer, on the WHOLE ``[layers, slots | blocks, ...]`` array.
    Shape-stable: the batched decode step compiles once no matter how
    slot positions drift apart under continuous batching.

    A position outside ``[0, max_len)`` is DROPPED, never clamped back
    onto a cached row: a lane at ``length == max_len`` leaves its last
    real row alone.

    Why the whole buffer and not the layer's slab (``cache.k[layer]``
    updated and set back): XLA:TPU runs the scatter in place on the
    donated cache, a few rows a layer, where the slab spelling copied
    the layer's ``[slots, max_len, ...]`` slab out, looped over the
    slots and wrote the slab back — 8.6 GB of traffic a step for 1 MB of
    new rows in the Mistral cell (PERF.md §6, PR 28;
    ``tests/test_serving_aot.py`` reads the compiled program for it).
    """
    pos = jnp.asarray(positions, jnp.int32)
    return _write(cache, cache.lane_index(layer, pos), k_tok, v_tok)


def decode_read(cache, layer: int):
    """The batched decode attention read: every slot's K/V for one
    layer as ``[slots, max_len, kv_heads, head_dim]`` — the buffer's rows
    as they are for a dense float cache, gathered through the block
    tables for a paged one, dequantized for an int8 one: same shapes,
    same masked-read contract, same reduction extents."""
    return cache.load(cache.lanes_view(layer))


def slot_read(cache, layer: int, slot):
    """One slot's K/V for one layer as ``[max_len, kv_heads,
    head_dim]`` (``slot`` may be traced) — the chunked-prefill read."""
    return cache.load(cache.slot_view(layer, slot))


def gather_slot_rows(cache, slot, rows):
    """Gather one slot's K/V at explicit (traced) row indices across
    every layer of a dense cache — the row-level read
    :func:`read_slot_region` and the engine's traced-start region-read
    program share.  Returns ``(k, v)`` of shape ``[layers, len(rows),
    kv_heads, head_dim]`` in the cache's :func:`value_dtype`."""
    s = jnp.asarray(slot, jnp.int32)
    return cache.load(lambda buf: buf[:, s, rows])


def read_slot_region(cache, slot, start, stop) -> tuple:
    """Fixed-extent gather of one slot's K/V span across every layer:
    returns ``(k, v)`` with shape ``[layers, stop - start, kv_heads,
    head_dim]`` — fresh owned buffers, NOT views into the cache (an XLA
    gather materializes), so the caller may keep them alive across later
    donated cache updates.  This is the prefix-cache *capture*
    primitive: a completed prompt block is snapshotted from the slot
    that just computed it.

    ``slot`` and ``start`` may be traced scalars; the extent
    ``stop - start`` must be a Python int (the gather shape is a
    compile-time constant — block-granular captures share ONE compiled
    read no matter where in the slot the block sits).  The caller is
    responsible for staying inside the slot's *valid* length — rows past
    ``lengths[slot]`` are masked garbage by contract and a region read
    must never hand them out (``DecodeEngine.read_region`` enforces
    this against its host-side length mirror).
    """
    n = int(stop) - int(start)
    if n < 1:
        raise ValueError(f"empty region [{start}, {stop})")
    rows = jnp.asarray(start, jnp.int32) + jnp.arange(n, dtype=jnp.int32)
    return gather_slot_rows(cache, slot, rows)


def write_slot_region(cache, slot, start, k_region, v_region):
    """Write a K/V span into one slot across every layer of a dense cache
    at offset ``start`` — the dual of :func:`read_slot_region` and the
    prefix-cache *restore* primitive (a previously captured block chain
    is placed back verbatim, so the restored rows are bit-for-bit what
    prefill would have recomputed).

    ``k_region`` / ``v_region``: ``[layers, n, kv_heads, head_dim]``;
    ``slot`` and ``start`` may be traced.  Overhanging padding rows are
    dropped like :func:`prefill_into_slot`'s, and ``lengths`` is
    untouched — the caller commits the slot's real depth via
    :func:`commit_slot_length` once per restore chunk.
    """
    rows = jnp.asarray(start, jnp.int32) + jnp.arange(
        k_region.shape[1], dtype=jnp.int32)
    s = jnp.asarray(slot, jnp.int32)
    return _write(cache, (slice(None), s, rows), k_region, v_region)


def commit_slot_length(cache, slot, length):
    """Set one slot's valid-token count (``slot``/``length`` may be
    traced scalars) — the single length-commit primitive both write
    paths share.

    A prefill chunk commits ``offset + chunk_len`` after writing its
    rows; a speculative **verify** commits ``offset + accepted + 1`` —
    i.e. it *rolls back* past the rejected draft rows, whose K/V were
    written but (because every read masks at ``idx <= length - 1``)
    are unreadable from the moment this commit lands.  Rollback is
    therefore the same O(1) move as eviction: adjust the length, never
    touch the payload.
    """
    return dataclasses.replace(
        cache,
        lengths=cache.lengths.at[jnp.asarray(slot)].set(
            jnp.asarray(length, jnp.int32)))


def release_slot(cache, slot):
    """Free a slot for reuse: O(1) — zero its length, leave the bytes.

    Stale K/V past ``lengths`` are unreadable by contract (every read
    masks with :func:`valid_token_mask`), so eviction never touches the
    cache payload and the next prefill simply overwrites.
    """
    return dataclasses.replace(
        cache, lengths=cache.lengths.at[jnp.asarray(slot)].set(0))


def valid_token_mask(positions, max_len: int):
    """``[slots, max_len]`` bool: True where ``idx <= position``.

    ``positions`` is the index of each slot's *current* token (visible to
    itself), i.e. the pre-append ``cache.lengths``.  This is the decode
    read mask — :func:`cached_attention` applies the same
    ``idx <= bound`` semantics per query row (decode passes one bound
    per slot; a prefill chunk passes ``offset + row``), so masking
    semantics live in one predicate.  (``.astype(jnp.int32)`` turns it
    into segment ids for ``flash_attention(segment_ids=...)`` if a
    kernel path ever wants it.)
    """
    idx = jnp.arange(max_len, dtype=jnp.int32)[None, :]
    return idx <= jnp.asarray(positions, jnp.int32)[:, None]


# ---- the cached read --------------------------------------------------------

# cached-attention query blocks are padded to at least this many rows
# per query head: XLA-CPU lowers an M=1 score "matmul" as a gemv whose
# per-element rounding differs from the gemm the uncached forward's
# [s, s] scores go through; M>=8 keeps both paths in the gemm regime so
# the float32 dot products round identically (pinned by
# tests/test_serving.py bit-parity).  On the chip the pad makes a KV
# head's query block rep * 8 rows: an MXU tile at GQA 4:1
DECODE_QPAD = 8


def cached_attention(qt, kc, vc, bounds):
    """Length-masked attention read over a full KV-cache buffer, as the
    cache stores it.

    ``qt``: ``[b, h, m, hd]`` query rows; ``kc``/``vc``: ``[b, max_len,
    kv_heads, hd]`` — the cache's own layout and head count, in the
    dtype the cache hands back; ``bounds``: ``[b, m]`` int32 — row ``i``
    of batch element ``b`` attends cache positions ``idx <=
    bounds[b, i]``; everything past its bound is masked garbage.  Two
    callers: single-token decode (``m == 1``, one bound per slot) and
    chunked prefill / speculative verification (``m == chunk``,
    ``bounds[0, i] = offset + i`` — the chunk's causal block over the
    previously cached context).

    **Grouped, not repeated.**  Query head ``j`` reads KV head ``j //
    rep`` (``rep = h // kv_heads``, the ``jnp.repeat`` share pattern of
    the uncached branch), so consecutive query heads group: ``q`` is
    viewed as ``[b, kv_heads, rep * m, hd]`` and both contractions run
    batched over ``(b, kv_heads)`` directly on the stored layout.  K/V
    are never repeated, transposed or upcast: no program-visible buffer
    has the size of an expanded cache view (``rep == 1`` is plain MHA
    through the same lines).

    **Arithmetic.**  Operands stay in the cache's dtype, accumulation is
    float32 (``preferred_element_type``); mask, max, exp, sum and divide
    are float32; the probabilities are cast to V's dtype for the second
    contraction — operation for operation what
    ``ops.flash_attention`` does on the training path, except that the
    scale is folded into ``q`` before the first dot (as
    ``mha_reference`` does).  For a float32 cache that is the very op
    sequence of ``mha_reference``, so against an uncached forward **run
    at the same static ``max_len`` extent** every reduction sees
    identical operand extents — masked tails are exact zeros — and the
    result is bit-identical, per step, forever (the no-recompile serving
    contract and the parity acceptance test in one property) — on a
    backend whose gemm rounds a row alike at every row count.  Each
    score row is the same dot product grouped or repeated, but XLA-CPU's
    rounding follows the rows per batch, so at some shapes the grouped
    read is one or two float32 ulps from the repeated one
    (``tests/test_serving.py``; ROADMAP D1).  For a bf16 cache it is the
    precision the model is trained under: bf16 products, float32 sums.
    """
    b, h, m, hd = qt.shape
    max_len, nkv = kc.shape[1], kc.shape[2]
    rep = h // nkv
    scale = 1.0 / hd ** 0.5
    mp = max(m, DECODE_QPAD)
    if m < mp:
        # pad the query block with copies of its last row (same bound):
        # the extra rows are sliced off below, and per-row results are
        # M-extent-invariant in the gemm regime, so padding never moves
        # a real row's bits
        qt = jnp.concatenate(
            [qt, jnp.broadcast_to(qt[:, :, -1:], (b, h, mp - m, hd))],
            axis=2)
        bounds = jnp.concatenate(
            [bounds, jnp.broadcast_to(bounds[:, -1:], (b, mp - m))],
            axis=1)
    # pin the view the contractions read to the layout it is stored in:
    # left free, XLA:TPU gives the WHOLE cache a kv-head-major layout for
    # these two dots and copies it in and out of every decode step
    # (measured: 20 ms a step of 16 layers against 7.5 with the barrier)
    kc, vc = lax.optimization_barrier((kc, vc))
    qg = (qt.astype(jnp.float32) * scale).astype(kc.dtype)
    qg = qg.reshape(b, nkv, rep * mp, hd)
    s = jnp.einsum("bgrd,blgd->bgrl", qg, kc,
                   preferred_element_type=jnp.float32)
    s = s.reshape(b, h, mp, max_len)
    # masked scores sit at the flash kernels' exact _NEG_INF: exp of the
    # masked residual underflows to exactly 0.0 in f32, which is what
    # makes these fixed-extent reductions bit-exact vs a same-extent
    # uncached forward
    idx = jnp.arange(max_len, dtype=jnp.int32)
    valid = idx[None, None, :] <= bounds[:, :, None]   # [b, mp, max]
    s = jnp.where(valid[:, None], s, _NEG_INF)
    mx = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - mx)
    l = jnp.sum(e, axis=-1, keepdims=True)
    p = (e / l).astype(vc.dtype).reshape(b, nkv, rep * mp, max_len)
    out = jnp.einsum("bgrl,blgd->bgrd", p, vc,
                     preferred_element_type=jnp.float32)
    out = out.reshape(b, h, mp, hd)
    return out[:, :, :m].astype(qt.dtype)           # [b, h, m, hd]


def decode_attention(qt, kc, vc, position):
    """Single-token cached read: ``qt [b, h, 1, hd]`` over ``kc``/``vc``
    ``[b, max_len, kv_heads, hd]``, one visibility bound per slot
    (``idx <= position[b]``).  See :func:`cached_attention` for the
    grouped stored-dtype read and its masking/exactness contract."""
    return cached_attention(qt, kc, vc,
                             jnp.asarray(position, jnp.int32)[:, None])



# ---- the seam: what an attention layer calls -------------------------------


def _reads_in_place(cache, q) -> bool:
    """Whether the decode step's read is the Pallas kernel
    (:func:`~apex_tpu.ops.cached_decode_attention.cached_decode_attention`)
    or :func:`cached_attention` over the layout's view - decided by what
    is in hand: rows a lane owns in the stored buffers (the dense layout)
    in the query's own float dtype (so not int8, and nothing to cast), a
    head width of whole lane tiles (at 64 XLA:TPU keeps ``max_len`` in the
    lanes, ``{2,4,3,1,0}``, and the kernel's operand would be a copy of the
    whole cache: compiled for a described v5e, PERF.md §6, PR 30) and a
    ``max_len`` of whole blocks.  The ``kernel_dispatch`` event says
    which."""
    heads, nkv, hd = q.shape[2], cache.k.shape[-2], cache.k.shape[-1]
    block = block_rows(cache.max_len, nkv)
    return record_dispatch(
        "cached_decode_attention",
        cache.lane_rows_in_place and cache.k.dtype == q.dtype
        and hd % 128 == 0 and cache.max_len % block == 0
        and block % 8 == 0,
        kv_heads=nkv, rep=heads // nkv, hd=hd, max_len=cache.max_len,
        block=block)


def decode_attend(cache, layer: int, q, k, v, position):
    """One decode step of one attention layer: append each lane's new K/V
    row at ``position`` (``[lanes]``; post-rope K, like the uncached path
    sees), then attend over the lane's rows ``idx <= position``.  ``q``
    ``[1, lanes, heads, hd]``, ``k`` / ``v`` ``[1, lanes, kv_heads, hd]``,
    the model's own layout.  Returns ``(ctx [lanes, heads, 1, hd], cache)``.

    One read, two implementations (:func:`_reads_in_place` chooses).  On
    a TPU a dense float cache is read where it lies: the kernel takes the
    stored buffers whole, the layer index and the bounds, and stops at
    each lane's last live block.  Everything else - a CPU backend, int8
    rows, a block table, an odd head width - takes the layer's ``[lanes,
    max_len, kv_heads, hd]`` view, cast to the query's dtype, through
    :func:`cached_attention`: the GQA grouping happens on the query side,
    and every layout and format hands back identical values at every
    unmasked position over identical reduction extents - hence
    bit-identical logits dense against paged."""
    cache = append_token(cache, layer, k[0], v[0], jnp.asarray(position))
    if _reads_in_place(cache, q):
        return cached_decode_attention(q.transpose(1, 2, 0, 3), cache.k,
                                       cache.v, layer, position), cache
    kc, vc = decode_read(cache, layer)
    kc = kc.astype(q.dtype)
    vc = vc.astype(q.dtype)
    qt = q.transpose(1, 2, 0, 3)                    # [lanes, heads, 1, hd]
    return decode_attention(qt, kc, vc, position), cache


def prefill_attend(cache, layer: int, slot, q, k, v, offset):
    """One prompt chunk (or speculative verify) of one attention layer:
    write the chunk's K/V into ``slot`` at ``offset``, then attend over
    the whole masked cache — the chunk's own rows AND every previously
    cached token go through one fixed-extent read under per-row bounds
    (``idx <= offset + row``), so splitting a prompt into chunks never
    changes any bit.  ``q`` ``[s, 1, heads, hd]``, ``k`` / ``v`` ``[s, 1,
    kv_heads, hd]``.  Returns ``(ctx [1, heads, s, hd], cache)``."""
    s, b = q.shape[:2]
    if b != 1:
        raise ValueError(
            f"prefill expects one slot per call (b=1), got b={b}")
    cache = prefill_into_slot(cache, layer, slot, k[:, 0], v[:, 0],
                              start=offset)
    kc, vc = slot_read(cache, layer, slot)
    kc = kc.astype(q.dtype)                         # [max, kv_heads, hd]
    vc = vc.astype(q.dtype)
    qt = q.transpose(1, 2, 0, 3)                    # [1, heads, s, hd]
    bounds = (offset + jnp.arange(s, dtype=jnp.int32))[None]      # [1, s]
    return cached_attention(qt, kc[None], vc[None], bounds), cache


# ---- what a model's layers keep a slot --------------------------------------
#
# A model declares, layer by layer, what a slot keeps between calls
# (``model.cache_layers()``: one of the three declarations below, or None, a
# layer): K/V rows that grow with the sequence, a recurrent state of fixed
# size, or counters the layer adds to a call.  :func:`init_cache` builds ONE
# pytree from the declarations; its ``k`` / ``v`` hold the K/V layers only, in
# declaration order, and the model owns the map from its layer index to the
# index on each leading axis.


@dataclasses.dataclass(frozen=True)
class KVRows:
    """A layer that keeps ``[max_len, kv_heads, head_dim]`` K and V rows a
    slot."""

    kv_heads: int
    head_dim: int


@dataclasses.dataclass(frozen=True)
class RecurrentRows:
    """A layer that keeps a fixed-size state a slot: ``ssm`` (float32: it is
    summed into at every token) and ``conv`` (the last inputs of a causal
    convolution, in the weights' type).  Both are shapes without the slot
    axis."""

    ssm: tuple
    conv: tuple


@dataclasses.dataclass(frozen=True)
class CallCounters:
    """A layer that adds ``len(names)`` int32 counts a decode step, read
    back by ``DecodeEngine.moe_stats`` when somebody asks."""

    names: tuple


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("ssm", "conv"), meta_fields=())
@dataclasses.dataclass(frozen=True)
class RecurrentState:
    """``ssm [layers, slots, *RecurrentRows.ssm]`` float32 and ``conv
    [layers, slots, *RecurrentRows.conv]``: what the recurrent layers carry
    from one call to the next, one row a slot.

    Unlike a K/V row, a state cannot be hidden after the fact by a length:
    every write decides at the write what is real.  A chunk that starts a
    prompt reads zeros whatever the slot holds (:func:`slot_state`), so a
    released slot needs no clearing; a decode step writes only the active
    lanes (:func:`write_lane_state`), so an idle lane keeps its state bit
    for bit."""

    ssm: jax.Array
    conv: jax.Array


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("k", "v", "lengths", "state", "counters"),
                   meta_fields=())
@dataclasses.dataclass(frozen=True)
class HybridCache(KVCache):
    """A :class:`KVCache` over the layers that declared :class:`KVRows`,
    plus the :class:`RecurrentState` of the layers that declared
    :class:`RecurrentRows` and ``counters [counting layers, names]`` int32.
    ``lengths`` counts a slot's tokens for every kind of layer alike."""

    state: RecurrentState
    counters: jax.Array


def _one_shape(layers, kind, what: str):
    found = {l for l in layers if isinstance(l, kind)}
    if len(found) > 1:
        raise ValueError(
            f"layers declare different {what}: {sorted(map(str, found))} - "
            f"one stacked array holds one shape")
    return (next(iter(found)) if found else None,
            sum(isinstance(l, kind) for l in layers))


def init_cache(layers, *, slots: int, max_len: int, dtype=jnp.float32,
               int8: bool = False, paged=None):
    """Zero-filled cache for a model's per-layer declarations (``layers``:
    ``model.cache_layers()`` — a :class:`KVRows`, :class:`RecurrentRows`,
    :class:`CallCounters` or None a layer), in the layout and the storage
    format asked for: dense slot rows, or the block pool of ``paged`` (a
    :class:`~apex_tpu.serving.paged_kv_cache.PagedCacheConfig`); floats of
    ``dtype``, or :class:`Int8Rows` with ``int8``.

    Layers that keep K/V rows alone give a :class:`KVCache` /
    :class:`QuantKVCache` (or the paged pair); a recurrent state or
    counters give a :class:`HybridCache`, which is dense floats only."""
    kv, n_kv = _one_shape(layers, KVRows, "K/V rows")
    rec, n_rec = _one_shape(layers, RecurrentRows, "recurrent states")
    cnt, n_cnt = _one_shape(layers, CallCounters, "counters")
    kv = kv or KVRows(0, 0)
    if paged is not None:
        if rec or cnt:
            raise ValueError("a block table pages K/V rows; a recurrent "
                             "state or counters have no rows to page")
        return paged.init_cache(n_kv, kv, slots=slots, max_len=max_len,
                                dtype=dtype, int8=int8)
    shape = (n_kv, slots, max_len, kv.kv_heads, kv.head_dim)
    lengths = jnp.zeros((slots,), jnp.int32)
    if not (rec or cnt):
        cls = QuantKVCache if int8 else KVCache
        return cls(**cls.zeros(shape, dtype), lengths=lengths)
    if int8:
        raise ValueError("the int8 format stores K/V rows; a float32 "
                         "recurrent state is not quantized")
    ssm, conv = (rec.ssm, rec.conv) if rec else ((0,), (0,))
    return HybridCache(
        **HybridCache.zeros(shape, dtype), lengths=lengths,
        state=RecurrentState(
            ssm=jnp.zeros((n_rec, slots) + tuple(ssm), jnp.float32),
            conv=jnp.zeros((n_rec, slots) + tuple(conv), dtype)),
        counters=jnp.zeros((n_cnt, len(cnt.names) if cnt else 0), jnp.int32))


def slot_state(cache: HybridCache, layer: int, slot, offset):
    """One slot's ``(ssm, conv)`` for one recurrent layer as a chunk at
    ``offset`` must see it: zeros at offset 0 - a slot's next request never
    starts from the last one's state, and releasing a slot costs nothing -
    and what the previous chunk left otherwise."""
    s = jnp.asarray(slot, jnp.int32)
    fresh = jnp.asarray(offset, jnp.int32) == 0

    def one(stack):
        # layer and slot in ONE slice: ``stack[layer]`` first makes XLA:TPU
        # copy the layer's state of every slot (268 MB at the cell's 64
        # slots, 0.82 ms a layer a chunk) to read one slot's 4 MB
        # (PERF.md section 6, PR 27; tests/test_serving_aot.py)
        rows = lax.dynamic_slice(
            stack, (layer, s) + (0,) * (stack.ndim - 2),
            (1, 1) + stack.shape[2:])[0, 0]
        return jnp.where(fresh, jnp.zeros_like(rows), rows)

    return one(cache.state.ssm), one(cache.state.conv)


def write_slot_state(cache: HybridCache, layer: int, slot, ssm,
                     conv) -> HybridCache:
    """Store one slot's state after a prefill chunk."""
    s = jnp.asarray(slot, jnp.int32)
    st = cache.state
    return dataclasses.replace(cache, state=RecurrentState(
        ssm=st.ssm.at[layer, s].set(ssm.astype(st.ssm.dtype)),
        conv=st.conv.at[layer, s].set(conv.astype(st.conv.dtype))))


def write_lane_state(cache: HybridCache, layer: int, ssm, conv,
                     active) -> HybridCache:
    """Store every slot's state after a decode step; lanes not ``active``
    keep what they had."""
    st = cache.state

    def keep(new, old):
        on = active.reshape((-1,) + (1,) * (old.ndim - 1))
        return jnp.where(on, new.astype(old.dtype), old)

    return dataclasses.replace(cache, state=RecurrentState(
        ssm=st.ssm.at[layer].set(keep(ssm, st.ssm[layer])),
        conv=st.conv.at[layer].set(keep(conv, st.conv[layer]))))


def add_counts(cache: HybridCache, layer: int, counts) -> HybridCache:
    """Add one call's counts to a counting layer's row."""
    return dataclasses.replace(
        cache, counters=cache.counters.at[layer].add(counts))
