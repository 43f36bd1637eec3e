"""Pair-bias flash attention — the Evoformer attention core as one kernel.

Parity target: ``apex.contrib.openfold_triton.mha`` (mha.py:131-460) — the
Triton fused attention with pair bias + mask that the reference built
because framework-level fusion materializes the score matrix.  The same
is true of XLA: a microbenchmark (PERF_NOTES.md, r2) measured the one-jit jnp
``attention_core`` at the *materialized* bandwidth roofline (the
[r, h, s, s] fp32 scores round-trip HBM).  This module is the Pallas
kernel the r2 verdict asked for — with the honest caveat the same
microbench produced: at Evoformer scale (s=256, d=32) the materialized
XLA path wins outright (4.5 ms vs 89 ms — tiny tiles drown in per-step
grid overhead), so ``attention_core`` only routes here for s >= 1024,
where the s^2 score materialization actually hurts.  The kernel is the
long-sequence pair-biased attention story (and the dbias-reduction
pattern other kernels can reuse); both paths are parity-tested.

Shapes (Evoformer MSA-row pattern):

- q, k, v: ``[R, h, s, d]`` where ``R = r * b`` flattens (rows, batch)
  **rows-major** — the bias's batch must be the inner factor so the
  kernel can recover it as ``(g // h) % b``.
- bias: ``[b, h, s, s]`` pair bias, shared by all ``r`` MSA rows of a
  batch element, differentiable (the pair stack trains through it).
- mask: optional ``[R, s]`` bool kv-validity (True = attend).  Fully
  masked rows emit zeros (cleaner than the reference's NaN-prone
  softmax-over--inf).

Design: the forward is the flash online-softmax loop with a bias tile
added to each score block.  Backward recomputes score blocks from the
saved lse in a dq kernel (k innermost), a dkv kernel (q innermost), and a
dbias kernel whose grid puts the broadcast row dimension innermost so
``dbias = sum_r ds`` accumulates in VMEM scratch — the only cross-``g``
reduction, impossible to express as a revisited output in the other
kernels' grids.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.ops._dispatch import record_dispatch, use_interpret

__all__ = ["pair_bias_flash_attention", "pair_bias_reference"]

_NEG_INF = -1e30


def pair_bias_reference(q, k, v, bias, mask=None, scale=None):
    """Materialized reference with identical semantics (and the jnp
    fallback for unsupported shapes)."""
    R, h, s, d = q.shape
    b = bias.shape[0]
    r = R // b
    scale = 1.0 if scale is None else scale
    sc = jax.lax.dot_general(
        q.astype(jnp.float32) * scale, k.astype(jnp.float32),
        (((3,), (3,)), ((0, 1), (0, 1))))            # [R, h, s, s]
    # rows-major [r, b] flatten: g = t * b + b_idx → bias index = g % b,
    # i.e. the bias TILES over the row dim (concatenate, not repeat)
    big = jnp.concatenate([bias.astype(jnp.float32)] * r, axis=0)
    sc = sc + big
    if mask is not None:
        sc = jnp.where(mask[:, None, None, :], sc, _NEG_INF)
    m = jnp.max(sc, axis=-1, keepdims=True)
    p = jnp.exp(sc - m)
    if mask is not None:
        p = jnp.where(mask[:, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = jnp.where(l > 0, p / jnp.where(l > 0, l, 1.0), 0.0)
    return jax.lax.dot_general(
        p, v.astype(jnp.float32),
        (((3,), (2,)), ((0, 1), (0, 1)))).astype(q.dtype)


# ---------------------------------------------------------------------------
# kernels: grid (R*h, nq, nk[, r]) — bias block index = ((g // h) % b, g % h)
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, mask_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, has_mask):
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = s + bias_ref[0].astype(jnp.float32)
    if has_mask:
        kvalid = mask_ref[0][:, :1].reshape(1, -1) != 0
        s = jnp.where(kvalid, s, _NEG_INF)
    m_prev = m_scr[:, :1]
    l_prev = l_scr[:, :1]
    m_cur = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), m_prev)
    corr = jnp.where(m_prev == -jnp.inf, 0.0, jnp.exp(m_prev - m_cur))
    p = jnp.exp(s - m_cur)
    if has_mask:
        p = jnp.where(kvalid, p, 0.0)  # fully-masked rows stay zero
    l_cur = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[0]
    pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * corr + pv
    m_scr[...] = jnp.broadcast_to(m_cur, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_cur, l_scr.shape)

    @pl.when(j == nj - 1)
    def _finish():
        l = l_scr[:, :1]
        m = m_scr[:, :1]
        o = jnp.where(l > 0, acc_scr[...] / jnp.where(l > 0, l, 1.0), 0.0)
        o_ref[0] = o.astype(o_ref.dtype)
        lse = jnp.where(l > 0, m + jnp.log(jnp.where(l > 0, l, 1.0)),
                        jnp.inf)
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _recompute_p(q_ref, k_ref, bias_ref, mask_ref, lse_ref, *, scale,
                 has_mask):
    s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = s + bias_ref[0].astype(jnp.float32)
    if has_mask:
        kvalid = mask_ref[0][:, :1].reshape(1, -1) != 0
        s = jnp.where(kvalid, s, _NEG_INF)
    lse = lse_ref[0][:, :1]
    return jnp.exp(s - lse)  # lse=+inf on dead rows → p = 0


def _dq_kernel(q_ref, k_ref, v_ref, bias_ref, mask_ref, do_ref, lse_ref,
               delta_ref, dq_ref, dq_scr, *, scale, has_mask):
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    p = _recompute_p(q_ref, k_ref, bias_ref, mask_ref, lse_ref,
                     scale=scale, has_mask=has_mask)
    dp = jax.lax.dot_general(do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0][:, :1])
    k = k_ref[0]
    dq_scr[...] += scale * jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, bias_ref, mask_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, scale,
                has_mask):
    i = pl.program_id(2)
    ni = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    p = _recompute_p(q_ref, k_ref, bias_ref, mask_ref, lse_ref,
                     scale=scale, has_mask=has_mask)
    do = do_ref[0]
    dv_scr[...] += jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0][:, :1])
    q = q_ref[0]
    dk_scr[...] += scale * jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == ni - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dbias_kernel(q_ref, k_ref, v_ref, bias_ref, mask_ref, do_ref, lse_ref,
                  delta_ref, db_ref, db_scr, *, scale, has_mask):
    t = pl.program_id(3)           # the broadcast row dim, innermost
    nt = pl.num_programs(3)

    @pl.when(t == 0)
    def _init():
        db_scr[...] = jnp.zeros_like(db_scr)

    p = _recompute_p(q_ref, k_ref, bias_ref, mask_ref, lse_ref,
                     scale=scale, has_mask=has_mask)
    dp = jax.lax.dot_general(do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    db_scr[...] += p * (dp - delta_ref[0][:, :1])   # ds: d(s+bias)/dbias = 1

    @pl.when(t == nt - 1)
    def _finish():
        db_ref[0] = db_scr[...].astype(db_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing + custom_vjp
# ---------------------------------------------------------------------------


def _lane(x):
    """[R, s] -> [R, s, 128] lane-tiled copies."""
    return jnp.broadcast_to(x[:, :, None], (*x.shape, 128))


def _pallas_fwd(q, k, v, bias, mask, scale, bq, bk):
    from jax.experimental.pallas import tpu as pltpu

    R, h, s, d = q.shape
    b = bias.shape[0]
    has_mask = mask is not None
    m3 = (_lane(mask.astype(jnp.int32)) if has_mask
          else jnp.zeros((1, 1, 128), jnp.int32))
    q3 = q.reshape(R * h, s, d)
    k3 = k.reshape(R * h, s, d)
    v3 = v.reshape(R * h, s, d)
    b3 = bias.reshape(b * h, s, s)
    mspec_idx = (lambda g, i, j: (g // h, j, 0)) if has_mask else \
        (lambda g, i, j: (0, 0, 0))
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, has_mask=has_mask),
        grid=(R * h, s // bq, s // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, bq, bk),
                         lambda g, i, j: (((g // h) % b) * h + g % h, i, j)),
            pl.BlockSpec((1, bk, 128) if has_mask else (1, 1, 128),
                         mspec_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda g, i, j: (g, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R * h, s, d), q.dtype),
            jax.ShapeDtypeStruct((R * h, s, 128), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        interpret=use_interpret(),
        name="pair_bias_attention_fwd",
    )(q3, k3, v3, b3, m3)
    return o.reshape(R, h, s, d), lse[:, :, 0].reshape(R, h, s)


def _pallas_bwd(q, k, v, bias, mask, o, lse, do, scale, bq, bk):
    from jax.experimental.pallas import tpu as pltpu

    R, h, s, d = q.shape
    b = bias.shape[0]
    r = R // b
    has_mask = mask is not None
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lse3 = _lane(lse.reshape(R * h, s))
    delta3 = _lane(delta.reshape(R * h, s))
    m3 = (_lane(mask.astype(jnp.int32)) if has_mask
          else jnp.zeros((1, 1, 128), jnp.int32))
    q3 = q.reshape(R * h, s, d)
    k3 = k.reshape(R * h, s, d)
    v3 = v.reshape(R * h, s, d)
    do3 = do.reshape(R * h, s, d)
    b3 = bias.reshape(b * h, s, s)

    bias_idx = lambda g, i, j: (((g // h) % b) * h + g % h, i, j)
    mask_idx = (lambda g, i, j: (g // h, j, 0)) if has_mask else \
        (lambda g, i, j: (0, 0, 0))
    mshape = (1, bk, 128) if has_mask else (1, 1, 128)

    def call(kernel, name, grid, out_specs, out_shape, scratch, swap=False):
        # swap=True: grid is (g, k block, q block) — index maps flip i/j
        def fix(f):
            return (lambda g, j, i: f(g, i, j)) if swap else f

        return pl.pallas_call(
            functools.partial(kernel, scale=scale, has_mask=has_mask),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, d), fix(lambda g, i, j: (g, i, 0))),
                pl.BlockSpec((1, bk, d), fix(lambda g, i, j: (g, j, 0))),
                pl.BlockSpec((1, bk, d), fix(lambda g, i, j: (g, j, 0))),
                pl.BlockSpec((1, bq, bk), fix(bias_idx)),
                pl.BlockSpec(mshape, fix(mask_idx)),
                pl.BlockSpec((1, bq, d), fix(lambda g, i, j: (g, i, 0))),
                pl.BlockSpec((1, bq, 128), fix(lambda g, i, j: (g, i, 0))),
                pl.BlockSpec((1, bq, 128), fix(lambda g, i, j: (g, i, 0))),
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=use_interpret(),
            name=name,
        )(q3, k3, v3, b3, m3, do3, lse3, delta3)

    dq = call(_dq_kernel, "pair_bias_attention_dq",
              (R * h, s // bq, s // bk),
              pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
              jax.ShapeDtypeStruct((R * h, s, d), q.dtype),
              [pltpu.VMEM((bq, d), jnp.float32)])
    dk, dv = call(_dkv_kernel, "pair_bias_attention_dkv",
                  (R * h, s // bk, s // bq),
                  [pl.BlockSpec((1, bk, d), lambda g, j, i: (g, j, 0)),
                   pl.BlockSpec((1, bk, d), lambda g, j, i: (g, j, 0))],
                  [jax.ShapeDtypeStruct((R * h, s, d), k.dtype),
                   jax.ShapeDtypeStruct((R * h, s, d), v.dtype)],
                  [pltpu.VMEM((bk, d), jnp.float32),
                   pltpu.VMEM((bk, d), jnp.float32)], swap=True)

    # dbias: grid (b*h, nq, nk, r) with the broadcast row dim innermost;
    # g for (bias graph index g2, row t) is (t*b + g2//h)*h + g2%h
    g_of = lambda g2, t: (t * b + g2 // h) * h + g2 % h
    db = pl.pallas_call(
        functools.partial(_dbias_kernel, scale=scale, has_mask=has_mask),
        grid=(b * h, s // bq, s // bk, r),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda g2, i, j, t: (g_of(g2, t), i, 0)),
            pl.BlockSpec((1, bk, d), lambda g2, i, j, t: (g_of(g2, t), j, 0)),
            pl.BlockSpec((1, bk, d), lambda g2, i, j, t: (g_of(g2, t), j, 0)),
            pl.BlockSpec((1, bq, bk), lambda g2, i, j, t: (g2, i, j)),
            pl.BlockSpec(mshape,
                         (lambda g2, i, j, t: (g_of(g2, t) // h, j, 0))
                         if has_mask else
                         (lambda g2, i, j, t: (0, 0, 0))),
            pl.BlockSpec((1, bq, d), lambda g2, i, j, t: (g_of(g2, t), i, 0)),
            pl.BlockSpec((1, bq, 128),
                         lambda g2, i, j, t: (g_of(g2, t), i, 0)),
            pl.BlockSpec((1, bq, 128),
                         lambda g2, i, j, t: (g_of(g2, t), i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, bk), lambda g2, i, j, t: (g2, i, j)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, s), bias.dtype),
        scratch_shapes=[pltpu.VMEM((bq, bk), jnp.float32)],
        interpret=use_interpret(),
        name="pair_bias_attention_dbias",
    )(q3, k3, v3, b3, m3, do3, lse3, delta3)

    return (dq.reshape(R, h, s, d), dk.reshape(R, h, s, d),
            dv.reshape(R, h, s, d), db.reshape(b, h, s, s))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash(q, k, v, bias, mask, scale, bq, bk):
    o, _ = _pallas_fwd(q, k, v, bias, mask, scale, bq, bk)
    return o


def _flash_fwd(q, k, v, bias, mask, scale, bq, bk):
    o, lse = _pallas_fwd(q, k, v, bias, mask, scale, bq, bk)
    return o, (q, k, v, bias, mask, o, lse)


def _flash_bwd(scale, bq, bk, res, do):
    q, k, v, bias, mask, o, lse = res
    dq, dk, dv, db = _pallas_bwd(q, k, v, bias, mask, o, lse, do, scale,
                                 bq, bk)
    return dq, dk, dv, db, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def pair_bias_flash_attention(q, k, v, bias, mask=None,
                              scale: Optional[float] = None,
                              block_q: int = 128, block_k: int = 128):
    """softmax(q kᵀ · scale + bias [+ mask]) v without materializing scores.

    Args:
      q, k, v: ``[R, h, s, d]`` with ``R = r * b`` rows-major (see module
        docstring); OpenFold passes q already scaled, so ``scale``
        defaults to 1.
      bias: ``[b, h, s, s]`` differentiable pair bias shared across rows.
      mask: optional ``[R, s]`` bool kv validity (True = attend).
      block_q / block_k: tile sizes (clamped to s).

    Returns ``[R, h, s, d]`` in q's dtype; fully-masked rows give zeros.
    """
    R, h, s, d = q.shape
    b = bias.shape[0]
    scale = 1.0 if scale is None else float(scale)
    bq, bk = min(block_q, s), min(block_k, s)
    if record_dispatch(
            "pair_bias_attention",
            R % b == 0 and d % 8 == 0 and s % bq == 0 and s % bk == 0
            and s % 128 == 0, rows=R, s=s, d=d):
        return _flash(q, k, v, bias, mask, scale, bq, bk)
    return pair_bias_reference(q, k, v, bias, mask, scale)
