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

__all__ = ["KVCache", "QuantKVCache", "init_cache", "init_quant_cache",
           "prefill_into_slot", "append_token", "commit_slot_length",
           "release_slot", "valid_token_mask", "read_slot_region",
           "write_slot_region", "decode_read", "slot_read", "value_dtype",
           "gather_slot_rows", "KVRows", "RecurrentRows", "CallCounters",
           "RecurrentState", "HybridCache", "init_hybrid_cache",
           "slot_state", "write_slot_state", "write_lane_state",
           "add_counts"]


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("k", "v", "lengths"), meta_fields=())
@dataclasses.dataclass(frozen=True)
class KVCache:
    """Preallocated decode cache: one slot per in-flight request.

    ``k`` / ``v``: ``[layers, slots, max_len, kv_heads, head_dim]``;
    ``lengths``: ``[slots]`` int32 — valid tokens per slot (0 = free).
    """

    k: jax.Array
    v: jax.Array
    lengths: jax.Array

    @property
    def num_layers(self) -> int:
        return self.k.shape[0]

    @property
    def num_slots(self) -> int:
        return self.k.shape[1]

    @property
    def max_len(self) -> int:
        return self.k.shape[2]

    @property
    def dtype(self):
        return self.k.dtype


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("k", "v", "k_scale", "v_scale", "lengths"),
                   meta_fields=())
@dataclasses.dataclass(frozen=True)
class QuantKVCache:
    """KV-int8 twin of :class:`KVCache`: same slot-indexed layout, the
    payload stored as symmetric int8 with one fp32 scale per cached
    (position, head) — the per-token-per-head grouping that keeps a
    long-tailed row from crushing its neighbors' resolution while the
    scale overhead stays ``4 / head_dim`` of the fp32 bytes.

    ``k`` / ``v``: int8 ``[layers, slots, max_len, kv_heads,
    head_dim]``; ``k_scale`` / ``v_scale``: fp32 ``[layers, slots,
    max_len, kv_heads]``; ``lengths``: ``[slots]`` int32.  Every
    masking/length/drop-scatter contract of the fp cache holds
    unchanged — the scale arrays ride the same row indices as the
    payload, and under tensor parallelism they shard head-wise on the
    SAME axis-3 spec (``P(None, None, None, 'tp')``) because kv_heads
    sits at axis 3 in both layouts.
    """

    k: jax.Array
    v: jax.Array
    k_scale: jax.Array
    v_scale: jax.Array
    lengths: jax.Array

    @property
    def num_layers(self) -> int:
        return self.k.shape[0]

    @property
    def num_slots(self) -> int:
        return self.k.shape[1]

    @property
    def max_len(self) -> int:
        return self.k.shape[2]

    @property
    def dtype(self):
        """Payload dtype (int8) — see :func:`value_dtype` for the dtype
        reads dequantize to."""
        return self.k.dtype


def value_dtype(cache) -> Any:
    """The dtype cache *reads* produce: the payload dtype for fp
    caches, fp32 (the dequant output) for quantized ones — what
    restore/capture plumbing must use for staging buffers instead of
    ``cache.dtype`` (int8 staging would destroy the values before the
    in-program requantize)."""
    return jnp.float32 if isinstance(cache, QuantKVCache) else cache.dtype


def init_cache(config: Any, *, slots: int, max_len: int,
               dtype=jnp.float32) -> KVCache:
    """Zero-filled cache for ``config`` (a :class:`LlamaConfig`-shaped
    object: ``num_hidden_layers``, ``kv_heads``, ``hidden_size``,
    ``num_attention_heads``)."""
    head_dim = config.hidden_size // config.num_attention_heads
    shape = (config.num_hidden_layers, slots, max_len, config.kv_heads,
             head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   lengths=jnp.zeros((slots,), jnp.int32))


def init_quant_cache(config: Any, *, slots: int,
                     max_len: int) -> QuantKVCache:
    """Zero-filled KV-int8 cache.  Scales start at 1.0 (the zero-amax
    convention of :func:`apex_tpu.amp.quant.quantize_int8`): an unused
    row dequantizes to exact finite zeros, never NaN."""
    head_dim = config.hidden_size // config.num_attention_heads
    shape = (config.num_hidden_layers, slots, max_len, config.kv_heads,
             head_dim)
    return QuantKVCache(
        k=jnp.zeros(shape, jnp.int8), v=jnp.zeros(shape, jnp.int8),
        k_scale=jnp.ones(shape[:-1], jnp.float32),
        v_scale=jnp.ones(shape[:-1], jnp.float32),
        lengths=jnp.zeros((slots,), jnp.int32))


def prefill_into_slot(cache: KVCache, layer: int, slot, k_seq, v_seq,
                      start=0) -> KVCache:
    """Write one (padded) prompt chunk's K/V into one slot of one layer,
    at offset ``start`` (0 == a fresh prompt; later chunks of a long
    prompt pass the tokens-already-cached count).

    ``k_seq`` / ``v_seq``: ``[chunk_len, kv_heads, head_dim]``; ``slot``
    and ``start`` may be traced scalars, ``layer`` is a Python int.  Does
    NOT touch ``lengths`` — the caller sets the slot's *real* length once
    per model call (chunk padding past it stays masked garbage until the
    next chunk overwrites it).

    The write is a per-row scatter with ``mode="drop"``, NOT a
    ``dynamic_update_slice``: a bucket-padded tail chunk near the cache
    end (``start + chunk_len > max_len`` even though every *real* token
    fits) must have its overhanging padding rows DROPPED — a
    dynamic-update would silently clamp the whole block backward and
    overwrite previously cached real K/V.
    """
    rows = jnp.asarray(start, jnp.int32) + jnp.arange(
        k_seq.shape[0], dtype=jnp.int32)
    s = jnp.asarray(slot, jnp.int32)
    if isinstance(cache, QuantKVCache):
        # per-(row, head) symmetric int8: the scale rows ride the same
        # drop-safe scatter indices as the payload, so an overhanging
        # padding row drops BOTH or NEITHER
        kq, ks = quantize_int8(k_seq, axis=-1)
        vq, vs = quantize_int8(v_seq, axis=-1)
        return dataclasses.replace(
            cache,
            k=cache.k.at[layer, s, rows].set(kq, mode="drop"),
            v=cache.v.at[layer, s, rows].set(vq, mode="drop"),
            k_scale=cache.k_scale.at[layer, s, rows].set(ks, mode="drop"),
            v_scale=cache.v_scale.at[layer, s, rows].set(vs, mode="drop"))
    return dataclasses.replace(
        cache,
        k=cache.k.at[layer, s, rows].set(k_seq.astype(cache.dtype),
                                         mode="drop"),
        v=cache.v.at[layer, s, rows].set(v_seq.astype(cache.dtype),
                                         mode="drop"))


def append_token(cache: KVCache, layer: int, k_tok, v_tok,
                 positions) -> KVCache:
    """Write one token's K/V per slot at that slot's own position.

    ``k_tok`` / ``v_tok``: ``[slots, kv_heads, head_dim]``; ``positions``:
    ``[slots]`` int32 (normally ``cache.lengths`` — the next free index).
    One row scatter a buffer, on the WHOLE ``[layers, slots, max_len,
    ...]`` array at ``(layer, lane, positions[lane])`` — the spelling of
    :func:`prefill_into_slot` and ``paged_append``.  Shape-stable: the
    batched decode step compiles once no matter how slot positions drift
    apart under continuous batching.

    A position outside ``[0, max_len)`` is DROPPED (``mode="drop"``; a
    negative one too, which plain indexing would wrap to the slot's
    end), never clamped back onto a cached row: a lane at ``length ==
    max_len`` leaves its last real row alone.

    Why the whole buffer and not the layer's slab (``cache.k[layer]``
    updated and set back): XLA:TPU runs the scatter in place on the
    donated cache, a few rows a layer, where the slab spelling copied
    the layer's ``[slots, max_len, ...]`` slab out, looped over the
    slots and wrote the slab back — 8.6 GB of traffic a step for 1 MB of
    new rows in the Mistral cell (PERF.md §6, PR 28;
    ``tests/test_serving_aot.py`` reads the compiled program for it).
    """
    pos = jnp.asarray(positions, jnp.int32)
    rows = jnp.where(pos < 0, cache.max_len, pos)
    lanes = jnp.arange(pos.shape[0], dtype=jnp.int32)

    def put(buf, tok):
        return buf.at[layer, lanes, rows].set(tok.astype(buf.dtype),
                                              mode="drop")

    if isinstance(cache, QuantKVCache):
        # the scale rows ride the payload's indices: a dropped lane
        # drops BOTH
        kq, ks = quantize_int8(k_tok, axis=-1)    # [slots, kvh, hd] -> ..
        vq, vs = quantize_int8(v_tok, axis=-1)    # .. + scale [slots, kvh]
        return dataclasses.replace(
            cache, k=put(cache.k, kq), v=put(cache.v, vq),
            k_scale=put(cache.k_scale, ks), v_scale=put(cache.v_scale, vs))
    return dataclasses.replace(cache, k=put(cache.k, k_tok),
                               v=put(cache.v, v_tok))


def read_slot_region(cache: KVCache, slot, start, stop) -> tuple:
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
    s = jnp.asarray(slot, jnp.int32)
    if isinstance(cache, QuantKVCache):
        # capture hands out DEQUANTIZED fp32 rows: every host consumer
        # (prefix-cache spans, preemption snapshots, fleet stream
        # exports) stays quantization-oblivious, and the matching
        # restore requantizes in-program — the int8 payload survives
        # that roundtrip exactly (see serving/quant.py)
        return (dequantize_int8(cache.k[:, s, rows],
                                cache.k_scale[:, s, rows]),
                dequantize_int8(cache.v[:, s, rows],
                                cache.v_scale[:, s, rows]))
    return cache.k[:, s, rows], cache.v[:, s, rows]


def write_slot_region(cache: KVCache, slot, start, k_region,
                      v_region) -> KVCache:
    """Write a K/V span into one slot across every layer at offset
    ``start`` — the dynamic-update dual of :func:`read_slot_region` and
    the prefix-cache *restore* primitive (a previously captured block
    chain is placed back verbatim, so the restored rows are bit-for-bit
    what prefill would have recomputed).

    ``k_region`` / ``v_region``: ``[layers, n, kv_heads, head_dim]``;
    ``slot`` and ``start`` may be traced.  Like
    :func:`prefill_into_slot`, the write is a per-row scatter with
    ``mode="drop"`` (a bucket-padded restore chunk near the cache end
    must have its overhanging padding rows DROPPED, never clamped
    backward onto cached tokens), and ``lengths`` is untouched — the
    caller commits the slot's real depth via
    :func:`commit_slot_length` once per restore chunk.
    """
    rows = jnp.asarray(start, jnp.int32) + jnp.arange(
        k_region.shape[1], dtype=jnp.int32)
    s = jnp.asarray(slot, jnp.int32)
    if isinstance(cache, QuantKVCache):
        # requantize the (dequantized-fp32) span in-program: the group
        # amax element always requantizes to exactly ±127, so the int8
        # payload is reproduced bit-for-bit and the scales to 1 ulp —
        # restore-after-capture stays agreement-tier-exact
        kq, ks = quantize_int8(k_region, axis=-1)
        vq, vs = quantize_int8(v_region, axis=-1)
        return dataclasses.replace(
            cache,
            k=cache.k.at[:, s, rows].set(kq, mode="drop"),
            v=cache.v.at[:, s, rows].set(vq, mode="drop"),
            k_scale=cache.k_scale.at[:, s, rows].set(ks, mode="drop"),
            v_scale=cache.v_scale.at[:, s, rows].set(vs, mode="drop"))
    return dataclasses.replace(
        cache,
        k=cache.k.at[:, s, rows].set(k_region.astype(cache.dtype),
                                     mode="drop"),
        v=cache.v.at[:, s, rows].set(v_region.astype(cache.dtype),
                                     mode="drop"))


def commit_slot_length(cache: KVCache, slot, length) -> KVCache:
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


def release_slot(cache: KVCache, slot) -> KVCache:
    """Free a slot for reuse: O(1) — zero its length, leave the bytes.

    Stale K/V past ``lengths`` are unreadable by contract (every read
    masks with :func:`valid_token_mask`), so eviction never touches the
    cache payload and the next prefill simply overwrites.
    """
    return dataclasses.replace(
        cache, lengths=cache.lengths.at[jnp.asarray(slot)].set(0))


def gather_slot_rows(cache, slot, rows):
    """Gather one slot's K/V at explicit (traced) row indices across
    every layer — the row-level read :func:`read_slot_region` and the
    engine's traced-start region-read program share.  Returns
    ``(k, v)`` of shape ``[layers, len(rows), kv_heads, head_dim]``;
    a :class:`QuantKVCache` hands back DEQUANTIZED fp32 rows (host
    consumers stay quantization-oblivious; the matching restore
    requantizes in-program and the int8 payload survives the roundtrip
    exactly)."""
    s = jnp.asarray(slot, jnp.int32)
    if isinstance(cache, QuantKVCache):
        return (dequantize_int8(cache.k[:, s, rows],
                                cache.k_scale[:, s, rows]),
                dequantize_int8(cache.v[:, s, rows],
                                cache.v_scale[:, s, rows]))
    return cache.k[:, s, rows], cache.v[:, s, rows]


def decode_read(cache, layer: int):
    """The batched decode attention read: every slot's K/V for one
    layer as ``[slots, max_len, kv_heads, head_dim]``.  An fp cache
    hands back its buffer rows as-is; a :class:`QuantKVCache`
    dequantizes through the per-(position, head) scales — same shapes,
    same masked-read contract, fp32 values."""
    if isinstance(cache, QuantKVCache):
        return (dequantize_int8(cache.k[layer], cache.k_scale[layer]),
                dequantize_int8(cache.v[layer], cache.v_scale[layer]))
    return cache.k[layer], cache.v[layer]


def slot_read(cache, layer: int, slot):
    """One slot's K/V for one layer as ``[max_len, kv_heads,
    head_dim]`` (``slot`` may be traced) — the chunked-prefill read,
    dequantized for a :class:`QuantKVCache` exactly like
    :func:`decode_read`."""
    s = jnp.asarray(slot, jnp.int32)
    k = lax.dynamic_index_in_dim(cache.k[layer], s, axis=0,
                                 keepdims=False)
    v = lax.dynamic_index_in_dim(cache.v[layer], s, axis=0,
                                 keepdims=False)
    if isinstance(cache, QuantKVCache):
        ks = lax.dynamic_index_in_dim(cache.k_scale[layer], s, axis=0,
                                      keepdims=False)
        vs = lax.dynamic_index_in_dim(cache.v_scale[layer], s, axis=0,
                                      keepdims=False)
        return dequantize_int8(k, ks), dequantize_int8(v, vs)
    return k, v


def valid_token_mask(positions, max_len: int):
    """``[slots, max_len]`` bool: True where ``idx <= position``.

    ``positions`` is the index of each slot's *current* token (visible to
    itself), i.e. the pre-append ``cache.lengths``.  This is the decode
    read mask — ``models.llama._cached_attention`` applies the same
    ``idx <= bound`` semantics per query row (decode passes one bound
    per slot; a prefill chunk passes ``offset + row``), so masking
    semantics live in one predicate.  (``.astype(jnp.int32)`` turns it
    into segment ids for ``flash_attention(segment_ids=...)`` if a
    kernel path ever wants it.)
    """
    idx = jnp.arange(max_len, dtype=jnp.int32)[None, :]
    return idx <= jnp.asarray(positions, jnp.int32)[:, None]


# ---- per-layer state of a model whose layers are not all attention --------
#
# A model that is not a stack of identical attention layers declares, layer
# by layer, what a slot keeps between calls (``model.cache_layers()``: one
# of the three declarations below, or None, a layer): K/V rows that grow
# with the sequence, a recurrent state of fixed size, or counters the layer
# adds to a call.  :func:`init_hybrid_cache` builds ONE pytree from the
# declarations; the K/V primitives above work on it unchanged (its ``k`` /
# ``v`` hold the K/V layers only, in declaration order), and the model owns
# the map from its layer index to the index on each leading axis.


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


def init_hybrid_cache(layers, *, slots: int, max_len: int,
                      dtype=jnp.float32) -> HybridCache:
    """Zero-filled cache for a model's per-layer declarations (``layers``:
    a :class:`KVRows`, :class:`RecurrentRows`, :class:`CallCounters` or None
    a layer)."""
    kv, n_kv = _one_shape(layers, KVRows, "K/V rows")
    rec, n_rec = _one_shape(layers, RecurrentRows, "recurrent states")
    cnt, n_cnt = _one_shape(layers, CallCounters, "counters")
    kv_shape = (n_kv, slots, max_len) + (
        (kv.kv_heads, kv.head_dim) if kv else (0, 0))
    ssm, conv = (rec.ssm, rec.conv) if rec else ((0,), (0,))
    return HybridCache(
        k=jnp.zeros(kv_shape, dtype), v=jnp.zeros(kv_shape, dtype),
        lengths=jnp.zeros((slots,), jnp.int32),
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
