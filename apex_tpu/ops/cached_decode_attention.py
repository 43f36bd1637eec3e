"""The batched decode step's K/V read — one Pallas TPU kernel over the
cache as it is stored.

``serving.kv_cache.cached_attention`` is the reference (and the read of
prefill, verify, the int8 format and the paged layout): a masked grouped
softmax over a layer's ``[lanes, max_len, kv_heads, hd]`` rows.  Handed
``cache.k[layer]`` it costs the decode program two things the algorithm
does not need (PERF.md §5, PR 28): XLA:TPU cuts the layer's slab out of
the stacked buffer before the dots read it, and the dots and the softmax
run over all ``max_len`` rows where a slot holds a few hundred.  This
kernel takes the buffers **whole** (``[layers, lanes, max_len, kv_heads,
hd]``, no view, no cast), the layer as a runtime scalar and each lane's
bound, and walks a lane's rows in blocks:

- the grid is a work list: one step a live block (one that holds a row
  ``idx <= position``), lane after lane, its length - the sum of the
  lanes' live blocks - a runtime value.  ``layer``, ``position`` and the
  list (``lane_of[step]``, ``block_of[step]``) ride as scalar prefetch, so
  the K/V index maps fetch the ``[block, kv_heads, hd]`` tile at
  ``(layer, lane, block)`` straight from the stored buffer, each while the
  tile before it is multiplied: no DMA, no arithmetic and no grid step
  past the bound.  (Over a static ``(lanes, max_len // block)`` grid whose
  dead steps skip their body the same read took 1.23 ms where this takes
  0.89: a dead step costs 0.17 us and there are 1,450 of them a decode
  step at ``chat-closed``'s lengths; PERF.md §6, PR 30.)
- all KV heads of a block in one step.  The tile is viewed as
  ``[block * kv_heads, hd]`` (row-major ``(row, kv head)``: the stored
  order, so the view moves nothing) and every query head is multiplied
  with every column; a query head keeps the columns of its own KV head
  (``col % kv_heads == head // rep``) under the same mask that hides rows
  past the bound.  The products a head discards ride in MXU rows that a
  ``rep``-row product would leave empty, and no per-head strided copy of
  the tile is made.  A step takes ``COLUMNS`` columns whatever the head
  count (256 rows of 8 KV heads, 1,024 of 2): on the v5e the 1 MB tile's
  DMA then hides the arithmetic at 8 KV heads, and half or twice the
  tile is no faster (``tools/decode_read_bench.py``; PERF.md §6, PR 30).
- the arithmetic of ``cached_attention``: operands in the cache's dtype
  (``q`` arrives scaled in float32 and cast), float32 scores, running
  max, running sum and accumulator (online softmax across blocks),
  masked scores at the flash kernels' ``_NEG_INF``, probabilities cast to
  V's dtype for the second product.  Rows past the bound are masked out
  of V too: by contract they are garbage, and ``0 * nan`` is not ``0``.

Blockwise sums round differently from one ``max_len``-wide reduction, so
the result is close to the reference, not bit-equal to it
(``tests/test_decode_read_kernel.py`` states the tolerances).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from apex_tpu.ops._dispatch import use_interpret
from apex_tpu.ops.flash_attention import _NEG_INF

# (row, kv head) columns of one lane a grid step multiplies: 256 rows of
# Mistral's 8 KV heads, 1024 of Nemotron-H's 2
COLUMNS = 2048
# the query heads are padded to whole sublane tiles of either dtype, in
# VMEM: [heads, hd] of q is the only operand smaller than a tile
_SUBLANES = 16


def block_rows(max_len: int, kv_heads: int) -> int:
    """Rows a grid step reads from a cache of ``max_len`` rows a lane."""
    return min(max(COLUMNS // kv_heads, 8), max_len)


def _live_blocks(bound, block: int, num_blocks: int):
    """Blocks of a lane that hold a row ``idx <= bound``: at least one (a
    softmax over nothing has no value) and at most all of them (an idle
    lane at ``lengths == max_len`` has ``bound == max_len``)."""
    return jnp.clip(bound // block + 1, 1, num_blocks)


def _kernel(layer_ref, bound_ref, lane_ref, block_ref, q_ref, k_ref, v_ref,
            o_ref, q_scr, m_scr, l_scr, acc_scr, *, rep: int,
            num_blocks: int):
    del layer_ref                   # read by the index maps
    step = pl.program_id(0)
    block, nkv, hd = k_ref.shape
    heads = q_ref.shape[1]
    padded = q_scr.shape[0]
    cols = block * nkv
    i = block_ref[step]             # this step's block of its lane
    bound = bound_ref[lane_ref[step]]

    @pl.when(i == 0)
    def _init():
        q_scr[...] = jnp.zeros_like(q_scr)
        q_scr[:heads, :] = q_ref[0]
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # columns are (row, kv head) pairs in stored order; those of rows
    # idx <= bound come first
    seen = (bound + 1 - i * block) * nkv
    k = k_ref[...].reshape(cols, hd)
    v = v_ref[...].reshape(cols, hd)
    s = lax.dot_general(q_scr[...], k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    # a query head keeps the columns of its own KV head; a column past
    # the bound belongs to no head
    col = lax.broadcasted_iota(jnp.int32, (1, cols), 1)
    kv_head = jnp.where(col < seen, lax.rem(col, nkv), -1)
    group = lax.div(lax.broadcasted_iota(jnp.int32, (padded, 1), 0), rep)
    s = jnp.where(group == kv_head, s, _NEG_INF)
    m_prev = m_scr[:, :1]
    m_cur = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), m_prev)
    corr = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)          # masked: exp(-1e30 - m) == 0.0
    l_cur = corr * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
    # by contract rows past the bound are garbage, and 0 * nan is not 0
    row = lax.broadcasted_iota(jnp.int32, (cols, 1), 0)
    v = jnp.where(row < seen, v, jnp.zeros_like(v))
    pv = lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32)
    acc = acc_scr[...] * corr + pv
    acc_scr[...] = acc
    m_scr[...] = jnp.broadcast_to(m_cur, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_cur, l_scr.shape)

    @pl.when(i == _live_blocks(bound, block, num_blocks) - 1)
    def _finish():
        o_ref[0] = (acc / l_cur)[:heads].astype(o_ref.dtype)


def cached_decode_attention(qt, k, v, layer, position):
    """``qt [lanes, heads, 1, hd]`` over layer ``layer`` of the stored
    ``k`` / ``v`` ``[layers, lanes, max_len, kv_heads, hd]``; lane ``b``
    attends rows ``idx <= position[b]``.  Returns ``[lanes, heads, 1,
    hd]`` in ``qt``'s dtype.  ``k.dtype == v.dtype``; ``max_len`` is a
    multiple of :func:`block_rows`."""
    from jax.experimental.pallas import tpu as pltpu

    lanes, heads, _, hd = qt.shape
    max_len, nkv = k.shape[2], k.shape[3]
    block = block_rows(max_len, nkv)
    num_blocks = max_len // block
    padded = -(-heads // _SUBLANES) * _SUBLANES
    # the scale goes into q in float32, then q takes the cache's dtype:
    # cached_attention's first two lines
    q = (qt[:, :, 0].astype(jnp.float32) * (1.0 / hd ** 0.5)).astype(k.dtype)

    # the work list: lane after lane, each lane's live blocks in order
    position = jnp.asarray(position, jnp.int32)
    live = _live_blocks(position, block, num_blocks)
    steps = jnp.arange(lanes * num_blocks, dtype=jnp.int32)
    lane_of = jnp.repeat(jnp.arange(lanes, dtype=jnp.int32), live,
                         total_repeat_length=lanes * num_blocks)
    ends = jnp.cumsum(live)
    block_of = steps - (ends - live)[lane_of]

    def rows(step, layer_ref, bound_ref, lane_ref, block_ref):
        return layer_ref[0], lane_ref[step], block_ref[step], 0, 0

    def per_lane(step, layer_ref, bound_ref, lane_ref, block_ref):
        return lane_ref[step], 0, 0

    tile = pl.BlockSpec((None, None, block, nkv, hd), rows)
    out = pl.pallas_call(
        functools.partial(_kernel, rep=heads // nkv, num_blocks=num_blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(ends[-1],),
            in_specs=[pl.BlockSpec((1, heads, hd), per_lane), tile, tile],
            out_specs=pl.BlockSpec((1, heads, hd), per_lane),
            scratch_shapes=[pltpu.VMEM((padded, hd), k.dtype),
                            pltpu.VMEM((padded, 128), jnp.float32),
                            pltpu.VMEM((padded, 128), jnp.float32),
                            pltpu.VMEM((padded, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((lanes, heads, hd), qt.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=use_interpret(),
        name="cached_decode_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1), position, lane_of, block_of,
      q, k, v)
    return out[:, :, None]
