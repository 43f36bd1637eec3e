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
  :class:`KVRows`, :class:`KVWindowRows`, :class:`RecurrentRows`,
  :class:`LatentRows`, :class:`RingRows`, :class:`CallCounters` or None a
  layer; :func:`init_cache` builds every cache from that.
- **The seam**: :func:`decode_attend` and :func:`prefill_attend` are the
  whole step an attention layer needs - append or chunk-write, then the
  read: on a TPU a dense float cache's rows where they lie, through the
  Pallas kernels of :mod:`apex_tpu.ops.cached_decode_attention` and
  :mod:`apex_tpu.ops.kv_chunk_attention`; everything else a view, cast to
  the query's dtype, under the grouped masked read
  (:func:`cached_attention`) - whatever the layout and the format.  A
  model imports those two (for a layer that sees a window of K/V rows
  their window pair :func:`window_decode_attend` /
  :func:`window_prefill_attend`, for a recurrent layer the state functions,
  for a latent-attention layer the latent pair :func:`latent_decode_attend`
  / :func:`latent_prefill_attend` and their window twins, at the end of
  this module) and names no cache class.

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
from jax.experimental.layout import Layout, with_layout_constraint

from apex_tpu.amp.quant import dequantize_int8, quantize_int8
from apex_tpu.obs.scopes import (
    CACHE_READ,
    CACHE_WRITE,
    ROUTER,
    SELECT,
    STATE,
    component,
)
from apex_tpu.ops._dispatch import record_choice, record_dispatch
from apex_tpu.ops.cached_decode_attention import (
    block_rows,
    cached_decode_attention,
)
from apex_tpu.ops.flash_attention import _NEG_INF
from apex_tpu.ops.kv_chunk_attention import kernel_takes as chunk_kernel_takes
from apex_tpu.ops.kv_chunk_attention import kv_chunk_attention
from apex_tpu.ops.latent_chunk_attention import (
    kernel_takes,
    latent_chunk_attention,
)

__all__ = ["KVCache", "QuantKVCache", "FloatRows", "Int8Rows", "DenseLayout",
           "init_cache", "prefill_into_slot", "append_token",
           "commit_slot_length", "release_slot", "valid_token_mask",
           "read_slot_region", "write_slot_region", "decode_read",
           "slot_read", "value_dtype", "gather_slot_rows", "DECODE_QPAD",
           "cached_attention", "decode_attention", "decode_attend",
           "prefill_attend", "KVRows", "RecurrentRows", "CallCounters",
           "RecurrentState", "HybridCache", "slot_state", "write_slot_state",
           "write_lane_state", "add_counts", "LatentRows", "RingRows",
           "LatentCache", "latent_decode_attend", "latent_prefill_attend",
           "ring_decode_attend", "ring_prefill_attend", "other_state",
           "KVWindowRows", "WindowKVCache", "window_decode_attend",
           "window_prefill_attend"]


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


@component(CACHE_WRITE)
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
    with component(CACHE_WRITE):
        cache = append_token(cache, layer, k[0], v[0], jnp.asarray(position))
    with component(CACHE_READ):
        if _reads_in_place(cache, q):
            return cached_decode_attention(q.transpose(1, 2, 0, 3), cache.k,
                                           cache.v, layer, position), cache
        kc, vc = decode_read(cache, layer)
        kc = kc.astype(q.dtype)
        vc = vc.astype(q.dtype)
        qt = q.transpose(1, 2, 0, 3)                # [lanes, heads, 1, hd]
        return decode_attention(qt, kc, vc, position), cache


# the float32 scores of one full-extent chunk read, ``heads x chunk x max_len
# x 4`` bytes, up to which a chunk that the kernel does not take attends the
# whole extent (:func:`cached_attention`) and past which it walks the visible
# blocks in the loop :func:`_kv_chunk_read`: 128 MiB, what 32 heads x 512 rows
# x 2,048 rows come to.  At 32,768 rows every bucket from 64 rows up walks (4
# GiB at 32 x 1,024).  It decides nothing where the kernel runs, and no
# second number of score bytes does: on the chip cut + kernel beat the full
# extent at every bucket of a 2,048-row cache, the 16-row one's 4 MiB of
# scores included (0.78-1.28 ms against 3.17 over 16 layers,
# ``tools/chunk_read_bench.py``; every traced program of ``chat-closed`` is
# shorter, 13.73 -> 10.91 ms the smallest: PERF.md section 6, PR 34)
_FULL_READ_BYTES = 1 << 27


def _prefill_read(cache, q) -> str:
    """Which read a chunk's queries ``q [s, 1, heads, hd]`` take over the
    slot's rows, decided once a trace by what is in hand:

    - ``"kernel"``: the Pallas kernel
      (:func:`~apex_tpu.ops.kv_chunk_attention.kv_chunk_attention`) wherever
      it takes the call, as :func:`_reads_in_place` decides for the decode
      step - kernels enabled (a TPU), rows a slot owns in the stored buffers
      in the queries' own float dtype, a head, a key block and a chunk in
      whole tiles (``kernel_takes``) - **at any extent**: its work follows
      ``offset + length``, a block's scores stay in fast memory;
    - everything else as it was: ``"full_extent"``
      (:func:`cached_attention` over the whole masked ``max_len``) while the
      float32 scores of that read stay within :data:`_FULL_READ_BYTES`,
      ``"loop"`` (:func:`_kv_chunk_read`, the same walk in ``jax.numpy``)
      past it, for float rows a slot owns in whole blocks.

    The ``kernel_dispatch`` event ``kv_chunk_attention`` says kernel or not,
    the ``read_dispatch`` event ``blocked_walk`` (kernel or loop) or
    ``full_extent``."""
    s, heads, hd = q.shape[0], q.shape[2], q.shape[3]
    max_len = cache.max_len
    scores = 4 * heads * s * max_len
    block = _key_block(max_len)
    shape = dict(m=s, hd=hd, block=block, max_len=max_len)
    if record_dispatch(
            "kv_chunk_attention",
            cache.lane_rows_in_place and cache.k.dtype == q.dtype
            and chunk_kernel_takes(**shape),
            heads=heads, kv_heads=cache.k.shape[-2], **shape):
        read = "kernel"
    elif (cache.lane_rows_in_place
          and jnp.issubdtype(cache.k.dtype, jnp.floating)
          and max_len % block == 0 and scores > _FULL_READ_BYTES):
        read = "loop"
    else:
        read = "full_extent"
    record_choice("prefill_attend", "full_extent" if read == "full_extent"
                  else "blocked_walk", heads=heads, chunk=s, max_len=max_len,
                  score_bytes=scores)
    return read


def prefill_attend(cache, layer: int, slot, q, k, v, offset):
    """One prompt chunk (or speculative verify) of one attention layer:
    write the chunk's K/V into ``slot`` at ``offset``, then attend the
    slot's rows - the chunk's own AND every previously cached token under
    per-row bounds (``idx <= offset + row``).  ``q`` ``[s, 1, heads, hd]``,
    ``k`` / ``v`` ``[s, 1, kv_heads, hd]``.  Returns ``(ctx [1, heads, s,
    hd], cache)``.

    One read, three implementations (:func:`_prefill_read` chooses).  On a
    TPU a dense float cache whose shapes the kernel takes is walked a
    visible block at a time by :func:`_chunk_kernel`, short cache or long:
    a chunked and an unchunked prompt then agree to rounding, as decode
    steps do (:func:`decode_attend`).  Everything else - a CPU backend, int8
    rows, a block table, an odd head width, a verify's odd row count - takes
    one fixed-extent masked read (:func:`cached_attention`) where the scores
    of the whole extent are small, so that splitting a prompt into chunks
    never changes any bit, and at long extents the same walk as a loop
    (:func:`_kv_chunk_read`), which stops at the chunk's end and rounds a
    block at a time."""
    s, b = q.shape[:2]
    if b != 1:
        raise ValueError(
            f"prefill expects one slot per call (b=1), got b={b}")
    with component(CACHE_WRITE):
        cache = prefill_into_slot(cache, layer, slot, k[:, 0], v[:, 0],
                                  start=offset)
    read = _prefill_read(cache, q)
    with component(CACHE_READ):
        if read == "kernel":
            return _chunk_kernel(cache, layer, slot, q[:, 0],
                                 offset)[None], cache
        if read == "loop":
            return _kv_chunk_read(q[:, 0].transpose(1, 0, 2), cache.k,
                                  cache.v, layer, slot, offset)[None], cache
        kc, vc = slot_read(cache, layer, slot)
        kc = kc.astype(q.dtype)                     # [max, kv_heads, hd]
        vc = vc.astype(q.dtype)
        qt = q.transpose(1, 2, 0, 3)                # [1, heads, s, hd]
        bounds = (offset + jnp.arange(s, dtype=jnp.int32))[None]  # [1, s]
        return cached_attention(qt, kc[None], vc[None], bounds), cache


def _chunk_kernel(cache, layer: int, slot, q, offset):
    """The kernel's read of :func:`prefill_attend`: ``q [s, heads, hd]`` over
    the visible blocks of ``slot``'s rows through
    :func:`~apex_tpu.ops.kv_chunk_attention.kv_chunk_attention`, which keeps
    a block's scores in fast memory and takes the slot's rows head-major -
    cut out of the stored layout here, once a call (4 MB each for K and V at
    2,048 rows of 8 heads, 33 MB at 32,768 of 4); ``[heads, s, hd]`` in
    ``q``'s dtype."""
    s = q.shape[0]
    max_len = cache.max_len
    block = _key_block(max_len)
    slot = jnp.asarray(slot, jnp.int32)
    offset = jnp.asarray(offset, jnp.int32)
    # the slot's rows pinned to the layout they are stored in, then turned
    # head-major: left free, XLA:TPU turns the WHOLE stacked buffer instead,
    # once for all its layers - 1.07 GB each way for K and for V a chunk
    # where the slot's rows are 33 MB (compiled for a described v5e and
    # measured: 6 ms of a 40 ms chunk; PERF.md section 6, PR 33).  A fence
    # (``optimization_barrier``) does not hold it: the layout of the cut
    # is assigned through it
    stored = Layout(major_to_minor=(0, 1, 2))
    kt, vt = (with_layout_constraint(lax.dynamic_slice(
        buf, (layer, slot, 0, 0, 0), (1, 1) + buf.shape[2:])[0, 0],
        stored).transpose(1, 0, 2)
        for buf in (cache.k, cache.v))                # [kv_heads, max, hd]
    blocks = jnp.minimum((offset + s - 1) // block + 1, max_len // block)
    ctx = kv_chunk_attention(q, kt, vt, offset, blocks, block=block)
    return ctx.astype(q.dtype).transpose(1, 0, 2)


def _kv_chunk_read(qt, k, v, layer: int, slot, offset):
    """The chunk read of :func:`prefill_attend` as a walk in plain
    ``jax.numpy``: the flash recurrence - running max, sum and values - over
    the blocks of ``k`` / ``v`` ``[layers, slots, max_len, kv_heads, hd]``
    at ``[layer, slot]`` that hold a row the chunk sees, ``idx <= offset +
    row``, and no further: its work follows ``offset + s``, never
    ``max_len``.  ``qt [heads, s, hd]``; returns ``[heads, s, hd]`` in its
    dtype.  Grouped as :func:`cached_attention` groups: query head ``j``
    reads KV head ``j // rep`` on the stored layout, operands in the rows'
    dtype, sums float32.  Rows past the chunk's end are garbage by
    contract: masked out of the scores and zeroed in V (``0 * nan`` is not
    ``0``)."""
    heads, s, hd = qt.shape
    max_len, nkv = k.shape[2], k.shape[3]
    rep = heads // nkv
    block = _key_block(max_len)
    slot = jnp.asarray(slot, jnp.int32)
    offset = jnp.asarray(offset, jnp.int32)
    qg = (qt.astype(jnp.float32) * (1.0 / hd ** 0.5)).astype(k.dtype)
    qg = qg.reshape(nkv, rep * s, hd)
    # a KV head's query rows are (head of the group, row of the chunk)
    bound = jnp.tile(offset + jnp.arange(s, dtype=jnp.int32), rep)

    def read(i, carry):
        top, total, acc = carry
        kb, vb = (lax.dynamic_slice(
            buf, (layer, slot, i * block, 0, 0),
            (1, 1, block, nkv, hd))[0, 0] for buf in (k, v))
        idx = i * block + jnp.arange(block, dtype=jnp.int32)
        sc = jnp.einsum("grd,ngd->grn", qg, kb,
                        preferred_element_type=jnp.float32)
        sc = jnp.where(idx[None, None] <= bound[None, :, None], sc, _NEG_INF)
        new_top = jnp.maximum(top, sc.max(-1))
        e = jnp.exp(sc - new_top[..., None])
        keep = jnp.exp(top - new_top)
        vb = jnp.where((idx < offset + s)[:, None, None], vb,
                       jnp.zeros_like(vb))
        acc = acc * keep[..., None] + jnp.einsum(
            "grn,ngd->grd", e.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return new_top, total * keep + e.sum(-1), acc

    blocks = jnp.minimum((offset + s - 1) // block + 1, max_len // block)
    _, total, acc = lax.fori_loop(
        0, blocks, read,
        (jnp.full((nkv, rep * s), _NEG_INF, jnp.float32),
         jnp.zeros((nkv, rep * s), jnp.float32),
         jnp.zeros((nkv, rep * s, hd), jnp.float32)))
    return (acc / total[..., None]).reshape(heads, s, hd).astype(qt.dtype)


# ---- what a model's layers keep a slot --------------------------------------
#
# A model declares, layer by layer, what a slot keeps between calls
# (``model.cache_layers()``: one of the declarations below, or None, a
# layer): K/V rows that grow with the sequence, a ring of the last K/V rows, a
# recurrent state of fixed size, or counters the layer adds to a call.  :func:`init_cache` builds ONE
# pytree from the declarations; its ``k`` / ``v`` hold the K/V layers only, in
# declaration order, and the model owns the map from its layer index to the
# index on each leading axis.


@dataclasses.dataclass(frozen=True)
class KVRows:
    """A layer that keeps ``[max_len, kv_heads, head_dim]`` K and V rows a
    slot."""

    kv_heads: int
    head_dim: int


# rows of a window ring come in whole sublane tiles of the stored type
RING_ROWS = 16


@dataclasses.dataclass(frozen=True)
class KVWindowRows:
    """A layer whose queries see ``window`` positions, their own among them:
    a ring of ``rows`` K and V rows a slot (``[rows, kv_heads, head_dim]``
    each), position ``p`` at row ``p mod rows``, whatever ``max_len`` is."""

    kv_heads: int
    head_dim: int
    window: int
    what = "a ring of window K/V rows"

    @property
    def rows(self) -> int:
        return -(-self.window // RING_ROWS) * RING_ROWS

    def rows_read(self, live) -> dict:
        """As :meth:`RingRows.rows_read`: the window's rows of each active
        lane's ``live`` rows, and beside them the live rows themselves: what
        a read at full extent would walk."""
        return {"window_rows": int(live.clip(max=self.window).sum()),
                "window_live_rows": int(live.sum())}


@dataclasses.dataclass(frozen=True)
class LatentRows:
    """A latent-attention layer with a key selector: ``[max_len, width]``
    latent rows a slot (the compressed K/V and the rope key all heads
    share) and ``[max_len, index_width]`` selector keys, of which a query
    attends the ``top_k`` rows that score highest."""

    width: int
    index_width: int
    top_k: int
    what = "latent rows with selector keys"

    @property
    def stored_width(self) -> int:
        """``width`` in whole lane tiles.  XLA:TPU stores ``[max_len, 576]``
        bfloat16 with ``max_len`` in the lanes (no padding that way) and
        then copies the whole buffer, 1.2 GB at 16 x 32,768 rows, into and
        out of every program that reads rows of it (compiled for a described
        v5e, PR 31); at 640 it is stored by rows."""
        return -(-self.width // 128) * 128

    def rows_read(self, live) -> dict:
        """What one decode step of this layer reads, from the live rows
        ``live [lanes]`` of its active lanes (the appended one among them):
        every selector key, and the latent rows the selection leaves."""
        return {"index_rows": int(live.sum()),
                "attended_rows": int(live.clip(max=self.top_k).sum())}


@dataclasses.dataclass(frozen=True)
class RingRows:
    """A latent-attention layer that sees ``window`` positions, the query's
    own among them: a ring of ``rows`` latent rows a slot, position ``p`` at
    row ``p mod rows``, whatever ``max_len`` is."""

    width: int
    window: int
    what = "a ring of window rows"

    @property
    def rows(self) -> int:
        return -(-self.window // RING_ROWS) * RING_ROWS

    def rows_read(self, live) -> dict:
        """As :meth:`LatentRows.rows_read`: the window's rows."""
        return {"window_rows": int(live.clip(max=self.window).sum())}


@dataclasses.dataclass(frozen=True)
class RecurrentRows:
    """A layer that keeps a fixed-size state a slot: ``ssm`` (float32: it is
    summed into at every token) and ``conv`` (the last inputs of a causal
    convolution, in the weights' type).  Both are shapes without the slot
    axis."""

    ssm: tuple
    conv: tuple
    what = "a recurrent state"


@dataclasses.dataclass(frozen=True)
class CallCounters:
    """A layer that adds ``len(names)`` int32 counts a decode step, read
    back by ``DecodeEngine.moe_stats`` when somebody asks."""

    names: tuple
    what = "call counters"


def other_state(layers) -> list:
    """``["RecurrentRows: a recurrent state", ...]``: the kinds of per-layer
    state in ``layers`` (``model.cache_layers()``) that are not K/V rows, by
    declaration - what everything that pages, shards, quantizes, copies or
    rolls back K/V rows has to refuse."""
    kinds = {type(l) for l in layers
             if l is not None and not isinstance(l, KVRows)}
    return sorted(f"{k.__name__}: {k.what}" for k in kinds)


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


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("k", "v", "lengths", "ring_k", "ring_v",
                                "counters"), meta_fields=())
@dataclasses.dataclass(frozen=True)
class WindowKVCache(KVCache):
    """A :class:`KVCache` over the layers that declared :class:`KVRows`,
    plus ``ring_k`` / ``ring_v [window layers, slots, rows, kv_heads,
    head_dim]`` for those that declared :class:`KVWindowRows` (position
    ``p`` at row ``p mod rows``: a slot keeps a window, whatever
    ``max_len``) and ``counters`` as :class:`HybridCache` has them.
    ``lengths`` counts a slot's tokens for every kind of layer alike: a
    ring row holds position ``p`` only if ``p`` is below it."""

    ring_k: jax.Array
    ring_v: jax.Array
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
    ``model.cache_layers()`` — a :class:`KVRows`, :class:`KVWindowRows`,
    :class:`RecurrentRows`, :class:`LatentRows`, :class:`RingRows`,
    :class:`CallCounters` or None a layer), in the layout and the storage
    format asked for: dense slot rows, or the block pool of ``paged`` (a
    :class:`~apex_tpu.serving.paged_kv_cache.PagedCacheConfig`); floats of
    ``dtype``, or :class:`Int8Rows` with ``int8``.

    Layers that keep K/V rows alone give a :class:`KVCache` /
    :class:`QuantKVCache` (or the paged pair); a recurrent state or
    counters give a :class:`HybridCache`, rings of window K/V rows (beside
    K/V rows or alone) a :class:`WindowKVCache`, latent rows or their window
    rings a :class:`LatentCache`: all three dense floats only."""
    kv, n_kv = _one_shape(layers, KVRows, "K/V rows")
    rec, n_rec = _one_shape(layers, RecurrentRows, "recurrent states")
    cnt, n_cnt = _one_shape(layers, CallCounters, "counters")
    lat, n_lat = _one_shape(layers, LatentRows, "latent rows")
    ring, n_ring = _one_shape(layers, RingRows, "window rings")
    win, n_win = _one_shape(layers, KVWindowRows, "K/V window rings")
    if win:
        if rec or lat or ring or paged is not None or int8:
            raise ValueError(
                "rings of window K/V rows are dense floats beside K/V rows "
                "and counters: no block table, no int8 rows, no recurrent "
                "state and no latent rows beside them")
        kv = kv or KVRows(win.kv_heads, win.head_dim)
        return WindowKVCache(
            **KVCache.zeros((n_kv, slots, max_len, kv.kv_heads, kv.head_dim),
                            dtype),
            lengths=jnp.zeros((slots,), jnp.int32),
            ring_k=jnp.zeros((n_win, slots, win.rows, win.kv_heads,
                              win.head_dim), dtype),
            ring_v=jnp.zeros((n_win, slots, win.rows, win.kv_heads,
                              win.head_dim), dtype),
            counters=jnp.zeros((n_cnt, len(cnt.names) if cnt else 0),
                               jnp.int32))
    if lat or ring:
        if kv or rec or paged is not None or int8:
            raise ValueError(
                "latent rows and window rings are dense floats, and a model "
                "that keeps them keeps no K/V rows and no recurrent state "
                "beside them (no model here mixes them)")
        lat, ring = lat or LatentRows(0, 0, 0), ring or RingRows(0, 0)
        return LatentCache(
            latent=jnp.zeros((n_lat, slots, max_len, lat.stored_width),
                             dtype),
            index=jnp.zeros((n_lat, slots, max_len, lat.index_width), dtype),
            ring=jnp.zeros((n_ring, slots, ring.rows, ring.width), dtype),
            lengths=jnp.zeros((slots,), jnp.int32),
            counters=jnp.zeros((n_cnt, len(cnt.names) if cnt else 0),
                               jnp.int32))
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


@component(STATE)
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


@component(CACHE_WRITE)
def write_slot_state(cache: HybridCache, layer: int, slot, ssm,
                     conv) -> HybridCache:
    """Store one slot's state after a prefill chunk."""
    s = jnp.asarray(slot, jnp.int32)
    st = cache.state
    return dataclasses.replace(cache, state=RecurrentState(
        ssm=st.ssm.at[layer, s].set(ssm.astype(st.ssm.dtype)),
        conv=st.conv.at[layer, s].set(conv.astype(st.conv.dtype))))


@component(CACHE_WRITE)
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


@component(ROUTER)
def add_counts(cache, layer: int, counts):
    """Add one call's counts to a counting layer's row."""
    return dataclasses.replace(
        cache, counters=cache.counters.at[layer].add(counts))


# ---- a window of K/V rows: the window pair of the seam ----------------------
#
# A layer whose queries see the last ``window`` positions keeps a ring of that
# many K and V rows a slot: rope went into K before the store and a softmax
# does not care in which order its rows lie, so a read needs to know which
# ring rows hold a position in the window, never where the window starts.


def _masked_read(qt, kc, vc, seen):
    """Masked grouped softmax read, all keys at once: ``qt [b, h, m, hd]``
    over ``kc`` / ``vc [b, n, kv_heads, hd]`` under ``seen [b, m, n]``;
    returns ``[b, h, m, hd]`` in ``qt``'s dtype.  :func:`cached_attention`'s
    arithmetic (query head ``j`` reads KV head ``j // rep`` on the stored
    layout, the scale folded into ``q``, operands in the rows' dtype, sums
    float32) under a mask that is no bound.  Every row must see a key; a key
    no row sees is garbage by contract and is zeroed in V (``0 * nan`` is not
    ``0``)."""
    b, h, m, hd = qt.shape
    n, nkv = kc.shape[1], kc.shape[2]
    rep = h // nkv
    qg = (qt.astype(jnp.float32) * (1.0 / hd ** 0.5)).astype(kc.dtype)
    qg = qg.reshape(b, nkv, rep * m, hd)
    s = jnp.einsum("bgrd,blgd->bgrl", qg, kc,
                   preferred_element_type=jnp.float32).reshape(b, h, m, n)
    s = jnp.where(seen[:, None], s, _NEG_INF)
    e = jnp.exp(s - s.max(-1, keepdims=True))
    p = (e / e.sum(-1, keepdims=True)).astype(vc.dtype)
    vc = jnp.where(seen.any(1)[:, :, None, None], vc, jnp.zeros_like(vc))
    out = jnp.einsum("bgrl,blgd->bgrd", p.reshape(b, nkv, rep * m, n), vc,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, m, hd).astype(qt.dtype)


def _ring_reads_in_place(cache, q, window: int) -> bool:
    """Whether a window layer's decode read is the Pallas kernel of
    :func:`decode_attend` on the ring buffers or :func:`_masked_read` over
    the ring under a mask - decided as :func:`_reads_in_place` decides, with
    one more condition: a ring of exactly ``window`` rows (after the append
    every row a bound admits is then in the window; a ring rounded up to
    whole tiles also holds rows that have left it).  The
    ``kernel_dispatch`` event says which."""
    heads, hd = q.shape[2], q.shape[3]
    ring, nkv = cache.ring_k.shape[2], cache.ring_k.shape[3]
    block = block_rows(ring, nkv)
    return record_dispatch(
        "cached_decode_attention",
        ring == window and cache.ring_k.dtype == q.dtype and hd % 128 == 0
        and ring % block == 0 and block % 8 == 0,
        kv_heads=nkv, rep=heads // nkv, hd=hd, max_len=ring, block=block,
        window=window)


def window_decode_attend(cache, layer: int, q, k, v, position, *,
                         window: int):
    """:func:`decode_attend` for a layer that sees ``window`` positions: put
    each lane's new K/V row at ring row ``position mod rows``, then attend
    the rows of positions ``position - window < p <= position``.  Shapes as
    :func:`decode_attend`; ``layer`` counts the window layers.

    One read, two implementations (:func:`_ring_reads_in_place` chooses):
    the kernel that reads a dense cache in place, handed the ring buffers
    and the bound ``min(position, rows - 1)``, or the ring as it lies under
    the mask of the rows that hold a position in the window.  Either way a
    step reads at most the ring, never ``max_len`` rows."""
    position = jnp.asarray(position, jnp.int32)
    ring = cache.ring_k.shape[2]
    with component(CACHE_WRITE):
        at = (layer, jnp.arange(position.shape[0], dtype=jnp.int32),
              position % ring)
        cache = dataclasses.replace(
            cache,
            ring_k=cache.ring_k.at[at].set(k[0].astype(cache.ring_k.dtype)),
            ring_v=cache.ring_v.at[at].set(v[0].astype(cache.ring_v.dtype)))
    with component(CACHE_READ):
        qt = q.transpose(1, 2, 0, 3)                # [lanes, heads, 1, hd]
        if _ring_reads_in_place(cache, q, window):
            return cached_decode_attention(
                qt, cache.ring_k, cache.ring_v, layer,
                jnp.minimum(position, ring - 1)), cache
        # ring row r holds the last position <= position that is r mod rows
        row = jnp.arange(ring, dtype=jnp.int32)
        held = position[:, None] - (position[:, None] - row[None]) % ring
        seen = (held >= 0) & (held > position[:, None] - window)
        return _masked_read(qt, cache.ring_k[layer].astype(q.dtype),
                            cache.ring_v[layer].astype(q.dtype),
                            seen[:, None]), cache


def window_prefill_attend(cache, layer: int, slot, q, k, v, offset, length,
                          *, window: int):
    """:func:`prefill_attend` for a layer that sees ``window`` positions:
    the chunk's queries read the ``window - 1`` rows before the chunk from
    the ring and the chunk's own rows from the ones in hand, under ``offset
    + row - window < p <= offset + row``; then the chunk's last real rows
    (``length`` of them are real) go into the ring.  Shapes as
    :func:`prefill_attend`; returns ``(ctx [1, heads, s, hd], cache)``."""
    s, b = q.shape[:2]
    if b != 1:
        raise ValueError(
            f"prefill expects one slot per call (b=1), got b={b}")
    slot = jnp.asarray(slot, jnp.int32)
    offset = jnp.asarray(offset, jnp.int32)
    ring = cache.ring_k.shape[2]
    before = -(-(window - 1) // 8) * 8
    p_before = offset - before + jnp.arange(before, dtype=jnp.int32)
    mine = offset + jnp.arange(s, dtype=jnp.int32)
    with component(CACHE_READ):
        kc, vc = (jnp.concatenate(
            [buf[layer, slot, p_before % ring].astype(q.dtype), rows[:, 0]])
            for buf, rows in ((cache.ring_k, k), (cache.ring_v, v)))
        heads, hd = q.shape[2:]
        block = min(512, -(-(before + s) // 128) * 128)
        shape = dict(m=s, hd=hd, block=block, max_len=block)
        if record_dispatch("kv_chunk_attention", chunk_kernel_takes(**shape),
                           heads=heads, kv_heads=k.shape[2], window=window,
                           **shape):
            # the same walk as a full layer's chunk, over this short extent
            # in whole blocks: key j holds position offset - before + j
            pad = -(before + s) % block
            kt, vt = (jnp.pad(rows, ((0, pad), (0, 0), (0, 0))).transpose(
                1, 0, 2) for rows in (kc, vc))
            ctx = kv_chunk_attention(
                q[:, 0], kt, vt, before, (before + s + pad) // block,
                block=block, window=window,
                first=jnp.maximum(before - offset, 0))
            ctx = ctx.astype(q.dtype).transpose(1, 0, 2)[None]
        else:
            at = jnp.concatenate([p_before, mine])
            seen = ((at[None] >= 0) & (at[None] <= mine[:, None])
                    & (at[None] > mine[:, None] - window))
            ctx = _masked_read(q.transpose(1, 2, 0, 3), kc[None], vc[None],
                               seen[None])
    # only real rows, and of more than a ring's worth only the last: one
    # scatter writes no ring row twice
    with component(CACHE_WRITE):
        n = jnp.arange(s, dtype=jnp.int32)
        keep = (n < length) & (n >= length - ring)
        to = (layer, slot, jnp.where(keep, mine % ring, ring))
        return ctx, dataclasses.replace(
            cache,
            ring_k=cache.ring_k.at[to].set(
                k[:, 0].astype(cache.ring_k.dtype), mode="drop"),
            ring_v=cache.ring_v.at[to].set(
                v[:, 0].astype(cache.ring_v.dtype), mode="drop"))


# ---- latent rows: what a latent-attention layer keeps, and its seam --------
#
# A latent-attention layer keeps, a token, the compressed row every head's K
# and V are expanded from and the rope key all heads share - hundreds of
# values where expanded K and V are tens of thousands - and, when it selects
# its keys, a selector key.  A layer that sees a window keeps a ring of the
# window's rows.  The four functions below are the latent pair of the seam
# and its window twin: append or chunk-write, score, select, read.  A model
# hands them queries, new rows and - for a chunk - ``expand``, what its map
# from stored rows to per-head K and V is made of; it names no cache class.


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("latent", "index", "ring", "lengths",
                                "counters"), meta_fields=())
@dataclasses.dataclass(frozen=True)
class LatentCache:
    """``latent [selecting layers, slots, max_len, stored_width]`` (a row's
    ``width`` values, then zeros up to whole lane tiles) and ``index
    [selecting layers, slots, max_len, index_width]`` for the layers that
    declared :class:`LatentRows`; ``ring [window layers, slots, rows,
    width]`` for those that declared :class:`RingRows` (position ``p`` at
    row ``p mod rows``: a slot keeps a window, whatever ``max_len``);
    ``counters`` as :class:`HybridCache` has them.  ``lengths`` counts a
    slot's tokens for every kind of layer alike: rows at or past it are
    garbage, and a ring row holds position ``p`` only if ``p`` is below
    it."""

    latent: jax.Array
    index: jax.Array
    ring: jax.Array
    lengths: jax.Array
    counters: jax.Array

    @property
    def dtype(self):
        return self.latent.dtype

    @property
    def num_slots(self) -> int:
        return self.lengths.shape[0]

    @property
    def max_len(self) -> int:
        return self.latent.shape[2]

    def decode_positions(self, active):
        """As :meth:`DenseLayout.decode_positions`: an idle lane writes the
        row its length hides."""
        del active
        return self.lengths


def _key_block(max_len: int) -> int:
    """Rows a step of the blocked loops below reads: 512, or a quarter of a
    short cache (the tests' sizes walk several blocks too)."""
    block = min(512, max(8, max_len // 4))
    return block if max_len % block == 0 else max_len


def _index_scores(q, w, keys, scale: float):
    """The selector's score of every key for every query: ``sum_j w[m, j]
    relu(q[m, j] . keys[n]) scale``; ``q [..., m, J, d]``, ``w [..., m, J]``
    float32, ``keys [..., n, d]``.  Products in the stored type, sums in
    float32."""
    dots = jnp.einsum("...mjd,...nd->...mjn", q, keys,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("...mjn,...mj->...mn", jax.nn.relu(dots),
                      w.astype(jnp.float32)) * scale


def _attend(q, k, v, mask, scale: float):
    """Masked softmax read, all keys at once: ``q [b, m, H, dk]``; ``k [b,
    n, Hk, dk]`` / ``v [b, n, Hk, dv]`` with ``Hk`` ``H`` (per-head K and V)
    or 1 (rows every head reads as they are stored: the absorbed form);
    ``mask [b, m, n]``.  Returns ``[b, m, H, dv]`` float32.  Products in
    ``k``'s type, sums float32."""
    qs = (q.astype(jnp.float32) * scale).astype(k.dtype)
    if k.shape[2] == 1:
        s = jnp.einsum("bmhd,bnd->bhmn", qs, k[:, :, 0],
                       preferred_element_type=jnp.float32)
    else:
        s = jnp.einsum("bmhd,bnhd->bhmn", qs, k,
                       preferred_element_type=jnp.float32)
    s = jnp.where(mask[:, None], s, _NEG_INF)
    e = jnp.exp(s - s.max(-1, keepdims=True))
    p = (e / e.sum(-1, keepdims=True)).astype(v.dtype)
    if v.shape[2] == 1:
        return jnp.einsum("bhmn,bnd->bmhd", p, v[:, :, 0],
                          preferred_element_type=jnp.float32)
    return jnp.einsum("bhmn,bnhd->bmhd", p, v,
                      preferred_element_type=jnp.float32)


def _select(scores, visible, top_k: int):
    """The ``top_k`` largest ``scores [m, n]`` among each row's ``visible``
    keys (all of them while a row sees fewer), as ``lax.top_k`` chooses
    them - of equal scores the lower index first.  Returns ``(index [m, k],
    chosen [m, k] bool)``: what a decode step gathers by."""
    scores = jnp.where(visible, scores, -jnp.inf)
    values, index = lax.top_k(scores, min(int(top_k), scores.shape[-1]))
    return index, values > -jnp.inf


def _select_mask(scores, visible, top_k: int):
    """:func:`_select`'s choice as a mask ``[m, n]``, without the sort a
    ``top_k`` of thousands is on the chip (25 ms for ``[1024, 32768]``, a
    quarter of a chunk: PERF.md section 6, PR 31): the ``top_k``-th largest
    score of each row is found bit by bit - scores as integers that order
    alike, one compare-and-count pass over the row a bit - and of the scores
    equal to it the lowest indices are kept, found the same way, as
    ``lax.top_k`` keeps them."""
    n = scores.shape[-1]
    k = min(int(top_k), n)
    bits = lax.bitcast_convert_type(
        jnp.where(visible, scores, -jnp.inf).astype(jnp.float32), jnp.uint32)
    # float32 bit patterns in unsigned order: negatives reversed below
    # the positives (-0.0 below 0.0, the total order ``lax.top_k`` sorts by)
    keys = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def kth(i, found):
        trial = found | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = (keys >= trial).sum(-1, keepdims=True) >= k
        return jnp.where(enough, trial, found)

    last = lax.fori_loop(0, 32, kth, jnp.zeros(keys.shape[:-1] + (1,),
                                               jnp.uint32))
    above = keys > last
    equal = (keys == last) & visible
    wanted = k - above.sum(-1, keepdims=True)
    col = jnp.arange(n, dtype=jnp.int32)
    width = max(n - 1, 1).bit_length()

    def cut(i, found):
        # the largest p with fewer than ``wanted`` equal scores before it:
        # the column of the last one kept
        trial = found | (1 << (width - 1 - i))
        few = (equal & (col < trial)).sum(-1, keepdims=True) < wanted
        return jnp.where(few, trial, found)

    until = lax.fori_loop(0, width, cut,
                          jnp.zeros(keys.shape[:-1] + (1,), jnp.int32))
    return visible & (above | (equal & (col <= until)))


def _as_stored(rows, buf):
    """``rows [n, width]`` as ``buf`` stores them: its type, zeros up to its
    last axis."""
    return jnp.pad(rows.astype(buf.dtype),
                   ((0, 0), (0, buf.shape[-1] - rows.shape[-1])))


def _lane_write(buf, layer: int, rows_at, rows):
    """``rows [lanes, width]`` at row ``rows_at[lane]`` of every lane; a row
    out of range is dropped."""
    lanes = jnp.arange(rows_at.shape[0], dtype=jnp.int32)
    at = jnp.where(rows_at < 0, buf.shape[2], rows_at)
    return buf.at[layer, lanes, at].set(_as_stored(rows, buf), mode="drop")


def latent_decode_attend(cache, layer: int, q, row, position, *,
                         scale: float, rank: int, select: dict):
    """One decode step of one selecting latent layer: append each lane's
    new ``row [lanes, width]`` and selector key at ``position [lanes]``,
    score the selector's keys of the lane's rows ``idx <= position``, take
    the ``select["top_k"]`` largest (all of them while a lane has fewer),
    gather those latent rows and read them in the absorbed form: ``q [lanes,
    H, width]`` against the rows as stored, values the rows' first ``rank``
    columns.  ``select`` = ``{"q" [lanes, J, d], "w" [lanes, J], "key"
    [lanes, d], "top_k", "scale"}``.  Returns ``(ctx [lanes, H, rank]
    float32, cache)``.

    What the step touches in proportion to ``max_len`` is the selector's
    keys (blocks up to the longest lane's length) and one float32 score a
    row; the latent rows it reads are the ``top_k`` it gathers."""
    position = jnp.asarray(position, jnp.int32)
    with component(CACHE_WRITE):
        cache = dataclasses.replace(
            cache, latent=_lane_write(cache.latent, layer, position, row),
            index=_lane_write(cache.index, layer, position, select["key"]))
    lanes, max_len = position.shape[0], cache.max_len
    block = _key_block(max_len)
    width = cache.index.shape[-1]

    def score(i, scores):
        keys = lax.dynamic_slice(cache.index, (layer, 0, i * block, 0),
                                 (1, lanes, block, width))[0]
        part = _index_scores(select["q"][:, None], select["w"][:, None],
                             keys.astype(select["q"].dtype),
                             select["scale"])[:, 0]
        return lax.dynamic_update_slice(scores, part, (0, i * block))

    with component(SELECT):
        scores = lax.fori_loop(
            0, jnp.minimum(jnp.max(position) // block + 1, max_len // block),
            score,
            jnp.full((lanes, max_len), -jnp.inf, jnp.float32))
        col = jnp.arange(max_len, dtype=jnp.int32)
        index, chosen = _select(scores, col[None] <= position[:, None],
                                select["top_k"])
        rows = cache.latent[layer, jnp.arange(lanes)[:, None], index]
    with component(CACHE_READ):
        rows = rows.astype(q.dtype)[:, :, None]      # [lanes, k, 1, stored]
        # the query takes the stored row's zeros rather than the rows a slice
        q = jnp.pad(q, ((0, 0), (0, 0), (0, rows.shape[-1] - q.shape[-1])))
        ctx = _attend(q[:, None], rows, rows[..., :rank], chosen[:, None],
                      scale)
        return ctx[:, 0], cache


def ring_decode_attend(cache, layer: int, q, row, position, *, scale: float,
                       rank: int, window: int):
    """One decode step of one window layer: put each lane's new ``row`` at
    ring row ``position mod rows``, gather the rows of positions ``position
    - window < p <= position`` and read them in the absorbed form (``q
    [lanes, H, width]``).  Returns ``(ctx [lanes, H, rank] float32,
    cache)``."""
    position = jnp.asarray(position, jnp.int32)
    ring = cache.ring.shape[2]
    with component(CACHE_WRITE):
        cache = dataclasses.replace(
            cache, ring=_lane_write(cache.ring, layer, position % ring, row))
    with component(CACHE_READ):
        lanes = position.shape[0]
        n = -(-window // 8) * 8
        at = position[:, None] - (n - 1) + jnp.arange(n, dtype=jnp.int32)
        seen = (at >= 0) & (at > position[:, None] - window)
        rows = cache.ring[layer, jnp.arange(lanes)[:, None], at % ring]
        rows = rows.astype(q.dtype)[:, :, None]
        ctx = _attend(q[:, None], rows, rows[..., :rank], seen[:, None],
                      scale)
        return ctx[:, 0], cache


def _expand(expand: dict, stored):
    """Stored rows ``[n, rank + rope]`` to each head's ``(k [n, H, nope +
    rope], v [n, H, dv])``: ``expand`` = ``{"w" [rank, H, nope + dv],
    "nope"}``, a row's first ``rank`` columns through the matrix (products
    in the rows' type, sums float32, rounded to the rows' type), the rope
    key - the row's other columns - the same for every head."""
    w, nope = expand["w"], expand["nope"]
    rank, heads = w.shape[:2]
    kv = jnp.einsum("nr,rhd->nhd", stored[:, :rank], w,
                    preferred_element_type=jnp.float32).astype(stored.dtype)
    k_rope = jnp.broadcast_to(
        stored[:, None, rank:],
        (stored.shape[0], heads, stored.shape[-1] - rank))
    return (jnp.concatenate([kv[..., :nope], k_rope], axis=-1),
            kv[..., nope:])


def _reads_chunk_in_place(cache, q, expand: dict, block: int) -> bool:
    """Whether a chunk's read of a selecting latent layer is the Pallas
    kernel (:func:`~apex_tpu.ops.latent_chunk_attention.
    latent_chunk_attention`) or the blocked loop of
    :func:`latent_prefill_attend` - decided by what is in hand, as
    :func:`_reads_in_place` decides the decode step's: rows stored in the
    queries' own float dtype, and every slice the kernel takes on whole
    tiles (:func:`~apex_tpu.ops.latent_chunk_attention.kernel_takes`: the
    stored width, the rank and a head's halves in lanes, the bucket in
    sublanes, ``max_len`` in blocks of at least 128 rows).  The
    ``kernel_dispatch`` event says which."""
    rank, _, wide = expand["w"].shape
    shape = dict(m=q.shape[0], stored=cache.latent.shape[-1], rank=rank,
                 nope=expand["nope"], dv=wide - expand["nope"], block=block,
                 max_len=cache.max_len)
    return record_dispatch(
        "latent_chunk_attention",
        cache.latent.dtype == q.dtype == expand["w"].dtype
        and jnp.issubdtype(q.dtype, jnp.floating) and kernel_takes(**shape),
        heads=q.shape[1], **shape)


def latent_prefill_attend(cache, layer: int, slot, q, rows, offset, *,
                          scale: float, expand: dict, select: dict):
    """One prompt chunk of one selecting latent layer: write the chunk's
    ``rows [s, width]`` and selector keys into ``slot`` at ``offset``, score
    the selector's keys of rows ``idx <= offset + row`` - earlier chunks'
    and the chunk's own, under one rule, so that splitting a prompt changes
    no selection - take each query's ``top_k`` and read the selected rows
    with ``q [s, H, dk]`` through each head's K and V, expanded from the
    stored rows by ``expand`` = ``{"w" [rank, H, nope + dv], "nope"}``
    (:func:`_expand`).  Returns ``(ctx [s, H, dv] float32, cache)``.

    Both walks are over blocks of rows up to the chunk's end, never over
    ``max_len``: one block of selector scores a head and one block of
    expanded K and V exist at a time.  The read is masked by the selection
    and dense over the visible blocks: one recurrence, two implementations
    (:func:`_reads_chunk_in_place` chooses) - on a TPU the kernel that
    takes the stored rows whole and keeps a block's scores in fast memory,
    everywhere else the loop below."""
    s = q.shape[0]
    slot = jnp.asarray(slot, jnp.int32)
    offset = jnp.asarray(offset, jnp.int32)
    at = offset + jnp.arange(s, dtype=jnp.int32)
    with component(CACHE_WRITE):
        cache = dataclasses.replace(
            cache,
            latent=cache.latent.at[layer, slot, at].set(
                _as_stored(rows, cache.latent), mode="drop"),
            index=cache.index.at[layer, slot, at].set(
                select["key"].astype(cache.index.dtype), mode="drop"))
    max_len = cache.max_len
    block = _key_block(max_len)
    blocks = jnp.minimum((offset + s - 1) // block + 1, max_len // block)

    def stored(buf, i):
        return lax.dynamic_slice(buf, (layer, slot, i * block, 0),
                                 (1, 1, block, buf.shape[-1]))[0, 0]

    def score(i, scores):
        part = _index_scores(select["q"], select["w"],
                             stored(cache.index, i).astype(q.dtype),
                             select["scale"])
        return lax.dynamic_update_slice(scores, part, (0, i * block))

    with component(SELECT):
        scores = lax.fori_loop(0, blocks, score,
                               jnp.full((s, max_len), -jnp.inf, jnp.float32))
        col = jnp.arange(max_len, dtype=jnp.int32)
        selected = _select_mask(scores, col[None] <= at[:, None],
                                select["top_k"])
    with component(CACHE_READ):
        qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
        if _reads_chunk_in_place(cache, q, expand, block):
            ctx = latent_chunk_attention(
                qs, cache.latent, selected, expand["w"], layer, slot, blocks,
                nope=expand["nope"], block=block)
        else:
            ctx = _chunk_read(qs, cache.latent, selected, expand, layer, slot,
                              blocks, block=block, width=rows.shape[-1])
    return ctx, cache


def _chunk_read(qs, latent, selected, expand: dict, layer, slot, blocks, *,
                block: int, width: int):
    """The read of :func:`latent_prefill_attend` as a loop in plain
    ``jax.numpy``: the flash recurrence - running max, sum and values - over
    the first ``blocks`` key blocks of ``latent[layer, slot]``, a block's
    rows (their first ``width`` columns) expanded for every head at a time.
    ``qs [s, H, dk]`` scaled, ``selected [s, max_len]``; returns ``[s, H,
    dv]`` float32."""
    s, heads = qs.shape[:2]

    def read(i, carry):
        top, total, acc = carry
        stored = lax.dynamic_slice(latent, (layer, slot, i * block, 0),
                                   (1, 1, block, latent.shape[-1]))[0, 0]
        k, v = _expand(expand, stored[:, :width].astype(qs.dtype))
        sc = jnp.einsum("mhd,nhd->hmn", qs, k,
                        preferred_element_type=jnp.float32)
        mask = lax.dynamic_slice(selected, (0, i * block), (s, block))
        sc = jnp.where(mask[None], sc, _NEG_INF)
        new_top = jnp.maximum(top, sc.max(-1))
        e = jnp.exp(sc - new_top[..., None])
        keep = jnp.exp(top - new_top)
        acc = acc * keep[..., None] + jnp.einsum(
            "hmn,nhd->hmd", e.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return new_top, total * keep + e.sum(-1), acc

    dv = expand["w"].shape[-1] - expand["nope"]
    _, total, acc = lax.fori_loop(
        0, blocks, read,
        (jnp.full((heads, s), _NEG_INF, jnp.float32),
         jnp.zeros((heads, s), jnp.float32),
         jnp.zeros((heads, s, dv), jnp.float32)))
    return (acc / total[..., None]).transpose(1, 0, 2)


def ring_prefill_attend(cache, layer: int, slot, q, rows, offset, length, *,
                        scale: float, expand: dict, window: int):
    """One prompt chunk of one window layer: the chunk's queries read the
    ``window - 1`` rows before the chunk from the ring and the chunk's own
    rows from the ones in hand, under ``offset + row - window < p <= offset
    + row``; then the chunk's last real rows (``length`` of them are real)
    go into the ring.  ``q [s, H, dk]``, ``rows [s, width]``, ``expand`` as
    :func:`latent_prefill_attend` takes it.  Returns ``(ctx [s, H, dv]
    float32, cache)``."""
    s = q.shape[0]
    slot = jnp.asarray(slot, jnp.int32)
    offset = jnp.asarray(offset, jnp.int32)
    ring = cache.ring.shape[2]
    before = -(-(window - 1) // 8) * 8
    p_before = offset - before + jnp.arange(before, dtype=jnp.int32)
    mine = offset + jnp.arange(s, dtype=jnp.int32)
    with component(CACHE_READ):
        old = cache.ring[layer, slot, p_before % ring].astype(q.dtype)
        at = jnp.concatenate([p_before, mine])
        seen = ((at[None] >= 0) & (at[None] <= mine[:, None])
                & (at[None] > mine[:, None] - window))
        k, v = _expand(expand, jnp.concatenate([old, rows.astype(q.dtype)]))
        ctx = _attend(q[None], k[None], v[None], seen[None], scale)[0]
    # only real rows, and of more than a ring's worth only the last: one
    # scatter writes no ring row twice
    with component(CACHE_WRITE):
        n = jnp.arange(s, dtype=jnp.int32)
        keep = (n < length) & (n >= length - ring)
        cache = dataclasses.replace(cache, ring=cache.ring.at[
            layer, slot, jnp.where(keep, mine % ring, ring)].set(
            rows.astype(cache.ring.dtype), mode="drop"))
    return ctx, cache
