"""A prompt chunk's read of a slot's K/V rows - one Pallas TPU kernel that
walks the visible key blocks.

``serving.kv_cache._kv_chunk_read`` is the reference (and the read of the
CPU, of odd widths and of an odd bucket): a key block at a time it scores
the chunk's queries against the block's K, masks by the causal bound and
carries a running max, sum and accumulator.  As plain ``jax.numpy`` the
block's ``[heads, chunk, block]`` float32 scores go to HBM and back four or
five times - 270 MB and a third of a millisecond a 512-row block at 32 heads
x 1,024 queries, for 8.6 GFLOP of products the MXU does in a seventh of that
(PERF.md §6, PR 33).  This kernel runs the same recurrence with scores and
probabilities in VMEM only:

- operands: the chunk's queries, scaled, ``[m, heads x hd]``, and the slot's
  rows **head-major**, ``[kv_heads, max_len, hd]`` - the seam cuts them out
  of the stored ``[max_len, kv_heads, hd]`` once a call (33 MB each way at
  32,768 rows of 4 heads: a tenth of a millisecond, 4 MB at 2,048 rows of 8:
  a hundredth, where a tile of one KV head's rows taken from the stored
  layout would be a strided half-word gather) - with ``offset`` and the
  number of visible key blocks as prefetched scalars.  The grid is (group
  of query heads, visible key block), its second extent a runtime value: no
  tile past the chunk's last row is fetched, and no step runs for one.
- a step takes one ``[block, hd]`` tile of K and of V of the group's KV
  head (query head ``j`` reads KV head ``j // rep``: a group never spans
  two) and, a tile of ``TILE`` queries at a time, scores, masks ``idx <=
  offset + row`` and updates each head's running max, sum and ``[m, hd]``
  float32 accumulator, which is the output block itself.
- a window layer's chunk is the same walk over other rows: the seam hands
  it the ``window - 1`` rows before the chunk (out of the layer's ring) and
  the chunk's own as one short extent, with ``window`` and the first row
  that holds a position; the mask then has a lower bound too.
- the arithmetic of the loop it replaces: operands in the stored dtype,
  float32 scores, sums and accumulator, masked scores at the flash kernels'
  ``_NEG_INF``, probabilities cast to V's dtype for the second product.
  Rows past the chunk's end are zeroed in V: by contract they may be
  garbage, and ``0 * nan`` is not ``0``.
- what a call holds in VMEM stays under the 16 MiB XLA:TPU gives a kernel
  that asks for nothing (``VMEM_BUDGET``; ``ops/latent_chunk_attention.py``
  says why), and operands and result are pinned to HBM.

Dots in another order than XLA's, so the result is close to the loop's, not
bit-equal to it (``tests/test_kv_chunk_kernel.py`` states the tolerances).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from apex_tpu.obs.scopes import CACHE_READ, component
from apex_tpu.ops._dispatch import use_interpret
from apex_tpu.ops.flash_attention import _NEG_INF

# most query heads a grid step scores: K's and V's tiles are fetched once a
# group, the group's queries and accumulator stay in VMEM over its blocks
GROUP = 4
# queries a head scores at a time: a tile's [TILE, block] float32 scores are
# what is in flight
TILE = 256
VMEM_BUDGET = 14 << 20
_LANES = 128


def kernel_takes(*, m: int, hd: int, block: int, max_len: int) -> bool:
    """Whether the shapes are ones the kernel compiles for: a head and a key
    block in whole lane tiles, the chunk in whole sublane tiles of either
    dtype and whole query tiles, ``max_len`` in whole blocks."""
    return (hd % _LANES == 0 and block % _LANES == 0
            and max_len % block == 0 and m % 16 == 0
            and m % min(m, TILE) == 0)


def _vmem(group: int, *, m: int, hd: int, block: int, item: int) -> int:
    """Bytes a call holds in VMEM with ``group`` heads a step: the
    pipeline's two buffers of every operand and of the accumulator, the
    scratch, a tile's scores in flight."""
    tile = min(m, TILE)
    return (2 * m * group * hd * (item + 4) + 2 * 2 * block * hd * item
            + 2 * group * m * _LANES * 4 + 4 * tile * block * 4)


def plan(m: int, rep: int, **shape) -> int:
    """The query heads a step takes: the most of ``GROUP`` that divide the
    ``rep`` heads of a KV head and fit ``VMEM_BUDGET``."""
    group = min(GROUP, rep)
    while group > 1 and (rep % group
                         or _vmem(group, m=m, **shape) > VMEM_BUDGET):
        group -= 1
    return group


def _kernel(offset_ref, blocks_ref, first_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, *, group: int, hd: int, tile: int, window: int):
    i = pl.program_id(1)
    rows, block = q_ref.shape[0], k_ref.shape[0]
    offset, first = offset_ref[0], first_ref[0]

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        o_ref[...] = jnp.zeros_like(o_ref)

    k = k_ref[...]
    # by contract rows past the chunk's end are garbage, and 0 * nan is not 0
    at = i * block + lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    v = jnp.where((at >= first) & (at < offset + rows), v_ref[...],
                  jnp.zeros_like(v_ref))
    col = i * block + lax.broadcasted_iota(jnp.int32, (1, block), 1)
    for start in range(0, rows, tile):
        r = slice(start, start + tile)
        bound = offset + start + lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
        seen = col <= bound
        if window:
            seen &= (col > bound - window) & (col >= first)
        for h in range(group):
            out = slice(h * hd, (h + 1) * hd)
            s = lax.dot_general(q_ref[r, out], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            s = jnp.where(seen, s, _NEG_INF)
            m_prev = m_scr[h, r, :1]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur)  # masked: exp(-1e30 - m) == 0.0
            if window:
                # a row may see nothing of the blocks before its window:
                # its running max is still _NEG_INF there and exp(0) is 1
                p = jnp.where(seen, p, 0.0)
            l_cur = corr * l_scr[h, r, :1] + jnp.sum(p, axis=-1,
                                                     keepdims=True)
            pv = jnp.dot(p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
            o_ref[r, out] = o_ref[r, out] * corr + pv
            m_scr[h, r, :] = jnp.broadcast_to(m_cur, (tile, _LANES))
            l_scr[h, r, :] = jnp.broadcast_to(l_cur, (tile, _LANES))

    @pl.when(i == blocks_ref[0] - 1)
    def _finish():
        for h in range(group):
            out = slice(h * hd, (h + 1) * hd)
            o_ref[:, out] = o_ref[:, out] / l_scr[h, :, :1]


def kv_chunk_attention(q, k, v, offset, blocks, *, block: int,
                       window: int = 0, first=0):
    """The chunk's ``q [m, heads, hd]`` (in the rows' dtype; the scale
    ``hd ** -0.5`` goes into it here, in float32, as the loop's does) over
    rows ``[0, blocks * block)`` of one slot's ``k`` / ``v [kv_heads,
    max_len, hd]``, query ``i`` attending rows ``idx <= offset + i`` of the
    KV head its head groups under - with a ``window``, of them the rows
    ``idx > offset + i - window`` from row ``first`` on (every query must
    see one).  ``offset``, ``blocks`` (>= 1, and enough to hold row ``offset
    + m - 1`` or ``max_len // block``) and ``first`` are runtime scalars.
    Returns ``[m, heads, hd]`` float32.  The shapes are ones
    :func:`kernel_takes` accepts."""
    return _call(q, k, v, jnp.asarray(offset, jnp.int32).reshape(1),
                 jnp.asarray(blocks, jnp.int32).reshape(1),
                 jnp.asarray(first, jnp.int32).reshape(1), block=block,
                 window=int(window), interpret=use_interpret())


# a function of its own under ``jit``: a program calls the kernel once a
# layer with the same shapes, and traces and lowers it once - sixteen traces
# of the unrolled body a program, six programs an engine, were 9 s of a
# warm start-up (PERF.md section 6, PR 34).  Lowered once, its instructions
# carry the first call site's scope path: the component is opened in here as
# well as around the call, so that it never depends on who called first
@functools.partial(jax.jit, static_argnames=("block", "window", "interpret"))
@component(CACHE_READ)
def _call(q, k, v, offset, blocks, first, *, block: int, window: int,
          interpret: bool):
    from jax.experimental.pallas import tpu as pltpu

    m, heads, hd = q.shape
    q = (q.astype(jnp.float32) * (1.0 / hd ** 0.5)).astype(q.dtype)
    rep = heads // k.shape[0]
    group = plan(m, rep, hd=hd, block=block,
                 item=jnp.dtype(k.dtype).itemsize)
    tile = min(m, TILE)

    def of_group(g, i, *_):
        return 0, g

    def rows(g, i, *_):
        return g * group // rep, i, 0

    def in_hbm(x):
        # left to it, XLA:TPU keeps a fresh operand or the result in VMEM
        # where it finds room; the kernel's pipeline is from and to HBM
        # (ops/latent_chunk_attention.py).  The interpreter knows no
        # memory spaces
        return x if interpret else pltpu.with_memory_space_constraint(
            x, pltpu.HBM)

    out = pl.pallas_call(
        functools.partial(_kernel, group=group, hd=hd, tile=tile,
                          window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(heads // group, blocks[0]),
            in_specs=[pl.BlockSpec((m, group * hd), of_group),
                      pl.BlockSpec((None, block, hd), rows),
                      pl.BlockSpec((None, block, hd), rows)],
            out_specs=pl.BlockSpec((m, group * hd), of_group),
            scratch_shapes=[pltpu.VMEM((group, m, _LANES), jnp.float32),
                            pltpu.VMEM((group, m, _LANES), jnp.float32)]),
        out_shape=(jax.ShapeDtypeStruct if interpret else pltpu.HBM)(
            (m, heads * hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kv_chunk_attention",
    )(offset, blocks, first, *map(in_hbm, (q.reshape(m, heads * hd), k, v)))
    return out.reshape(m, heads, hd)
