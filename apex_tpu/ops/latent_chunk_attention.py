"""A prompt chunk's read of a selecting latent layer - one Pallas TPU kernel
over the latent rows as they are stored.

``serving.kv_cache.latent_prefill_attend``'s blocked loop is the reference
(and the read of the CPU, of toy widths and of an odd bucket): a key block
at a time it expands every head's K and V from the block's stored rows,
scores the chunk's queries against them, masks the scores by the selection
and carries a running max, sum and accumulator.  As plain ``jax.numpy`` the
block's ``[heads, chunk, block]`` float32 scores go to HBM and back four
times, 1.07 GB a 512-row block at the long-document cell's widths, for
products the MXU does in a quarter of that time (PERF.md §5, PR 31).  This
kernel runs the same recurrence with scores, probabilities and expanded K
and V in VMEM only:

- operands whole and in place: ``latent [layers, slots, max_len, stored]``
  with ``layer``, ``slot`` and the number of visible key blocks as
  prefetched scalars, the chunk's scaled queries, the selection as int8,
  the expansion's matrix.  The grid is (group of heads, visible key block),
  its second extent a runtime value: no tile past the chunk's last row is
  fetched, and no step runs for one.
- a step takes one ``[block, stored]`` tile of rows and the block's columns
  of the selection, and for each head of the group expands K-nope and V
  from the rows' first ``rank`` columns on the MXU, then a tile of ``TILE``
  queries at a time scores ``[q_nope | q_rope] . [k_nope | k_rope]`` (the
  rope key is the rows' tail, the same tile for every head; the query's
  rope half is padded with zeros to the tail's width, which the stored row
  pads with zeros too), masks, and updates the head's running max, sum and
  ``[chunk, dv]`` float32 accumulator, which is the output block itself.
- the arithmetic of the loop it replaces: operands in the stored dtype,
  expanded K and V rounded to it, float32 scores, sums and accumulator,
  masked scores at the flash kernels' ``_NEG_INF``, probabilities cast to
  V's dtype for the second product.  A row no query of the chunk selects -
  every row past the chunk's end among them - is zeroed before it is
  expanded: by contract it may be garbage, and ``0 * nan`` is not ``0``.
- what a call holds in VMEM stays under the 16 MiB XLA:TPU gives a kernel
  that asks for nothing (``VMEM_BUDGET``): the heads a step takes follow
  from it (2 at the 1,024-row bucket, 8 under 256 rows), and operands and
  result are pinned to HBM.  A first version with 8 heads and all 1,024
  queries at once asked for 56 MB; it ran alone and in a two-layer engine,
  and hung the long-document engine's warm-up on the chip at the 512-row
  bucket (PERF.md §6, PR 32).

Dots in another order than XLA's, so the result is close to the loop's, not
bit-equal to it (``tests/test_latent_chunk_kernel.py`` states the
tolerances).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from apex_tpu.ops._dispatch import use_interpret
from apex_tpu.ops.flash_attention import _NEG_INF

# most heads a grid step expands, scores and sums: the rows' tile and the
# selection's are fetched once a group, the group's queries, matrix columns
# and accumulator stay in VMEM over its blocks
GROUP = 8
# queries a head scores at a time: a tile's [TILE, block] float32 scores are
# what is in flight
TILE = 256
# what a call may hold in VMEM: under XLA:TPU's own 16 MiB a kernel, which
# no ``vmem_limit_bytes`` raises here (the module's last point)
VMEM_BUDGET = 14 << 20
# the selection's int8 rows come in whole tiles of 32
_MASK_ROWS = 32
_LANES = 128


def kernel_takes(*, m: int, stored: int, rank: int, nope: int, dv: int,
                 block: int, max_len: int) -> bool:
    """Whether the shapes are ones the kernel compiles for: every slice it
    takes of a row, a head's columns or a block falls on whole lane tiles,
    the chunk on whole sublane tiles, ``max_len`` on whole blocks."""
    return (all(d % _LANES == 0 for d in (stored, rank, nope, dv, block))
            and rank < stored and max_len % block == 0 and m % 8 == 0)


def _vmem(group: int, *, m: int, tile: int, block: int, stored: int,
          rank: int, dk: int, wide: int, dv: int, item: int) -> int:
    """Bytes a call holds in VMEM with ``group`` heads a step: the pipeline's
    two buffers of every operand and of the accumulator, the scratch, a
    tile's scores in flight."""
    return (2 * m * group * (dk * item + dv * 4)
            + 2 * rank * group * wide * item
            + 2 * block * (stored * item + m)
            + 2 * group * m * _LANES * 4
            + m * block * 4 + block * (dk + dv) * item
            + 4 * tile * block * 4)


def plan(m: int, heads: int, **shape) -> tuple:
    """``(rows, tile, group)`` for a chunk of ``m`` queries: its rows in
    whole tiles of the selection's int8 and of ``TILE`` queries, the queries
    a head scores at a time, and the heads a step - the most of ``GROUP``
    that divide ``heads`` and fit ``VMEM_BUDGET`` (``shape``: what
    :func:`_vmem` takes beside them)."""
    step = _MASK_ROWS if m <= TILE else TILE
    rows = -(-m // step) * step
    tile = min(rows, TILE)
    group = min(GROUP, heads)
    while group > 1 and (heads % group or _vmem(
            group, m=rows, tile=tile, **shape) > VMEM_BUDGET):
        group -= 1
    return rows, tile, group


def _kernel(layer_ref, slot_ref, blocks_ref, q_ref, rows_ref, mask_ref, w_ref,
            o_ref, sel_scr, k_scr, v_scr, m_scr, l_scr, *, group: int,
            rank: int, nope: int, dv: int, tile: int):
    del layer_ref, slot_ref         # read by the index maps
    i = pl.program_id(1)
    block = rows_ref.shape[0]
    dk = k_scr.shape[1]
    tiles = [slice(t, t + tile) for t in range(0, q_ref.shape[0], tile)]

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        o_ref[...] = jnp.zeros_like(o_ref)

    # the selection once a step, for every head of the group
    sel = mask_ref[...].astype(jnp.float32)
    sel_scr[...] = sel
    # a row no query selects is zeroed: [1, block] of sums down the chunk,
    # turned into the rows' own orientation
    live = jnp.sum(sel, axis=0, keepdims=True)
    live = jnp.broadcast_to(live, (_LANES, block)).T[:, :1] > 0.0
    rows = rows_ref[...]
    rows = jnp.where(live, rows, jnp.zeros_like(rows))
    latent = rows[:, :rank]
    k_scr[:, nope:] = rows[:, rank:]

    for h in range(group):
        kv = jnp.dot(latent, w_ref[:, h * (nope + dv):(h + 1) * (nope + dv)],
                     preferred_element_type=jnp.float32).astype(rows.dtype)
        k_scr[:, :nope] = kv[:, :nope]
        v_scr[...] = kv[:, nope:]
        out = slice(h * dv, (h + 1) * dv)
        for r in tiles:
            s = lax.dot_general(q_ref[r, h * dk:(h + 1) * dk], k_scr[...],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            s = jnp.where(sel_scr[r, :] > 0.0, s, _NEG_INF)
            m_prev = m_scr[h, r, :1]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur)  # masked: exp(-1e30 - m) == 0.0
            l_cur = corr * l_scr[h, r, :1] + jnp.sum(p, axis=-1,
                                                     keepdims=True)
            pv = jnp.dot(p.astype(v_scr.dtype), v_scr[...],
                         preferred_element_type=jnp.float32)
            o_ref[r, out] = o_ref[r, out] * corr + pv
            m_scr[h, r, :] = jnp.broadcast_to(m_cur, (tile, _LANES))
            l_scr[h, r, :] = jnp.broadcast_to(l_cur, (tile, _LANES))

    @pl.when(i == blocks_ref[0] - 1)
    def _finish():
        for h in range(group):
            out = slice(h * dv, (h + 1) * dv)
            o_ref[:, out] = o_ref[:, out] / l_scr[h, :, :1]


def latent_chunk_attention(q, latent, selected, w, layer, slot, blocks, *,
                           nope: int, block: int):
    """The chunk's ``q [m, heads, nope + rope]`` (scaled, in the stored
    dtype) over rows ``[0, blocks * block)`` of ``latent[layer, slot]``
    (``latent [layers, slots, max_len, stored]``, a row its ``rank`` latent
    values, the rope key, zeros), query ``i`` attending the rows ``selected
    [m, max_len]`` (bool) marks; ``w [rank, heads, nope + dv]`` expands a
    row's latent values to a head's K-nope and V.  ``layer``, ``slot`` and
    ``blocks`` (>= 1) are runtime scalars.  Returns ``[m, heads, dv]``
    float32.  The shapes are ones :func:`kernel_takes` accepts."""
    from jax.experimental.pallas import tpu as pltpu

    m, heads, _ = q.shape
    rank, _, wide = w.shape
    dv = wide - nope
    stored = latent.shape[-1]
    dk = nope + stored - rank
    padded, tile, group = plan(
        m, heads, block=block, stored=stored, rank=rank, dk=dk, wide=wide,
        dv=dv, item=jnp.dtype(latent.dtype).itemsize)
    # the query's rope half beside the rows' tail: zeros against its zeros
    q = jnp.pad(q, ((0, padded - m), (0, 0), (0, dk - q.shape[-1])))
    mask = jnp.pad(selected.astype(jnp.int8), ((0, padded - m), (0, 0)))

    def rows(g, i, layer_ref, slot_ref, blocks_ref):
        return layer_ref[0], slot_ref[0], i, 0

    def of_group(g, i, *_):
        return 0, g

    def in_hbm(x):
        # left to it, XLA:TPU keeps a fresh operand or the result in VMEM
        # where it finds room (the selection, the 512-row bucket's 32 MB
        # result); the kernel's pipeline is from and to HBM.  The
        # interpreter knows no memory spaces
        return x if use_interpret() else pltpu.with_memory_space_constraint(
            x, pltpu.HBM)

    out = pl.pallas_call(
        functools.partial(_kernel, group=group, rank=rank, nope=nope, dv=dv,
                          tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(heads // group, blocks),
            in_specs=[
                pl.BlockSpec((padded, group * dk), of_group),
                pl.BlockSpec((None, None, block, stored), rows),
                pl.BlockSpec((padded, block), lambda g, i, *_: (0, i)),
                pl.BlockSpec((rank, group * wide), of_group)],
            out_specs=pl.BlockSpec((padded, group * dv), of_group),
            scratch_shapes=[pltpu.VMEM((padded, block), jnp.float32),
                            pltpu.VMEM((block, dk), latent.dtype),
                            pltpu.VMEM((block, dv), latent.dtype),
                            pltpu.VMEM((group, padded, _LANES), jnp.float32),
                            pltpu.VMEM((group, padded, _LANES), jnp.float32)]),
        out_shape=(jax.ShapeDtypeStruct if use_interpret() else pltpu.HBM)(
            (padded, heads * dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=use_interpret(),
        name="latent_chunk_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.asarray(slot, jnp.int32).reshape(1),
      jnp.asarray(blocks, jnp.int32).reshape(1),
      *map(in_hbm, (q.reshape(padded, heads * dk), latent, mask,
                    w.reshape(rank, heads * wide))))
    return out[:m].reshape(m, heads, dv)
