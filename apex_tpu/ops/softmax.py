"""Scaled (masked) softmax family — Pallas TPU kernels + jnp fallback.

Parity targets (the four Megatron softmax extensions, SURVEY.md §2.1):

- ``scaled_upper_triang_masked_softmax_cuda`` — causal, in-kernel triangular
  mask (csrc/megatron/scaled_upper_triang_masked_softmax.h).
- ``scaled_masked_softmax_cuda`` — arbitrary [b,1,sq,sk] boolean mask
  (csrc/megatron/scaled_masked_softmax.h:71-110).
- ``generic_scaled_masked_softmax_cuda`` — fallback for arbitrary sizes.
- ``scaled_softmax_cuda`` — scale+softmax, no mask.

The CUDA kernels exist to fuse scale→mask→softmax into one pass and to keep
the sk-length row in registers (warp softmax).  The Pallas equivalents keep a
(rows, sk) tile in VMEM, do the reduction in fp32, and generate the causal
mask with iota instead of loading one.  The kernel path routes on alignment
and a VMEM-budget cap (``_MAX_SK``); everything else — including the CUDA
kernels' un-servable shapes (sk > 2048, non-pow2) — takes the jnp path, which
XLA still fuses into one pass.

Masked-out semantics match the reference: masked positions get -10000 before
softmax (mask==True means "mask out"), and fully-masked rows produce zeros
(the CUDA kernel writes 0 for rows with no valid element).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.ops._dispatch import lane_aligned, record_dispatch, use_interpret

_MASK_VALUE = -10000.0  # matches scaled_masked_softmax.h additive fill
_BLOCK_ROWS = 128


# ---------------------------------------------------------------------------
# jnp reference path
# ---------------------------------------------------------------------------


def _jnp_softmax(x, scale, mask=None, causal=False):
    x32 = x.astype(jnp.float32) * scale
    if causal:
        sq, sk = x.shape[-2], x.shape[-1]
        tri = jnp.tril(jnp.ones((sq, sk), jnp.bool_), k=sk - sq)
        x32 = jnp.where(tri, x32, _MASK_VALUE)
    if mask is not None:
        x32 = jnp.where(mask, _MASK_VALUE, x32)
    m = jnp.max(x32, axis=-1, keepdims=True)
    e = jnp.exp(x32 - m)
    s = jnp.sum(e, axis=-1, keepdims=True)
    y = e / s
    # rows that are entirely masked: every element sits at _MASK_VALUE and
    # softmax would be uniform; the CUDA kernels emit zeros instead.
    if mask is not None:
        all_masked = jnp.all(mask, axis=-1, keepdims=True)
        y = jnp.where(all_masked, 0.0, y)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------


def _fwd_kernel(x_ref, mask_ref, y_ref, *, scale, causal, has_mask, sq):
    x = x_ref[0].astype(jnp.float32) * scale  # (block_rows, sk)
    rows, sk = x.shape
    valid = None
    if causal:
        i = pl.program_id(1)
        row = i * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, sk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, sk), 1)
        valid = col <= row + (sk - sq)
    if has_mask:
        keep = jnp.logical_not(mask_ref[0])
        valid = keep if valid is None else jnp.logical_and(valid, keep)
    if valid is not None:
        x = jnp.where(valid, x, _MASK_VALUE)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    s = jnp.sum(e, axis=-1, keepdims=True)
    y = e / s
    if valid is not None:
        any_valid = jnp.any(valid, axis=-1, keepdims=True)
        y = jnp.where(any_valid, y, 0.0)
    y_ref[0] = y.astype(y_ref.dtype)


def _bwd_kernel(y_ref, dy_ref, dx_ref, *, scale):
    y = y_ref[0].astype(jnp.float32)
    dy = dy_ref[0].astype(jnp.float32)
    inner = jnp.sum(y * dy, axis=-1, keepdims=True)
    dx_ref[0] = (scale * y * (dy - inner)).astype(dx_ref.dtype)


def _pallas_forward(x, scale, mask, causal):
    b, h, sq, sk = x.shape
    x3 = x.reshape(b * h, sq, sk)
    rows = min(_BLOCK_ROWS, sq)
    has_mask = mask is not None
    if has_mask:
        # [b, 1, sq, sk] → broadcast over heads at index-map level
        mask3 = jnp.broadcast_to(mask, (b, 1, sq, sk)).reshape(b, sq, sk)
    else:
        mask3 = jnp.zeros((1, 1, 1), jnp.bool_)
    grid = (b * h, sq // rows)
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          has_mask=has_mask, sq=sq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, rows, sk), lambda g, i: (g, i, 0)),
            (pl.BlockSpec((1, rows, sk), lambda g, i: (g // h, i, 0))
             if has_mask else pl.BlockSpec((1, 1, 1), lambda g, i: (0, 0, 0))),
        ],
        out_specs=pl.BlockSpec((1, rows, sk), lambda g, i: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, sk), x.dtype),
        interpret=use_interpret(),
        name="scaled_softmax_fwd",
    )(x3, mask3)
    return y.reshape(b, h, sq, sk)


def _pallas_backward(y, dy, scale):
    b, h, sq, sk = y.shape
    rows = min(_BLOCK_ROWS, sq)
    grid = (b * h, sq // rows)
    dx = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, rows, sk), lambda g, i: (g, i, 0)),
            pl.BlockSpec((1, rows, sk), lambda g, i: (g, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, sk), lambda g, i: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, sk), dy.dtype),
        interpret=use_interpret(),
        name="scaled_softmax_bwd",
    )(y.reshape(b * h, sq, sk), dy.reshape(b * h, sq, sk))
    return dx.reshape(b, h, sq, sk)


# Each grid step keeps (1, block_rows, sk) fp32 tiles for x/mask/y (fwd) or
# y/dy/dx (bwd) in VMEM, so sk is capped at 4096 (~2 MiB per tile).  Longer
# rows fall back to jnp — and genuinely long sequences belong to the flash
# attention path (apex_tpu.contrib.fmha), not a materialized softmax.
_MAX_SK = 4096


def _kernel_ok(op: str, x) -> bool:
    if x.ndim != 4:
        return record_dispatch(op, False, ndim=x.ndim)
    sq, sk = x.shape[-2], x.shape[-1]
    return record_dispatch(
        op, lane_aligned(sk) and sk <= _MAX_SK
        and (sq % min(_BLOCK_ROWS, sq) == 0) and sq >= 8,
        sq=sq, sk=sk)


# ---------------------------------------------------------------------------
# custom_vjp entry points
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _softmax(x, mask, scale, causal):
    return _softmax_fwd(x, mask, scale, causal)[0]


def _softmax_fwd(x, mask, scale, causal):
    if _kernel_ok("softmax_fwd", x):
        y = _pallas_forward(x, scale, mask, causal)
    else:
        y = _jnp_softmax(x, scale, mask=mask, causal=causal)
    return y, y


def _softmax_bwd(scale, causal, y, dy):
    # dx = scale * y * (dy - sum(y*dy)); masked rows have y == 0 so their
    # gradient is exactly 0, matching the CUDA backward.
    if _kernel_ok("softmax_bwd", y):
        dx = _pallas_backward(y, dy, scale)
    else:
        y32 = y.astype(jnp.float32)
        dy32 = dy.astype(jnp.float32)
        inner = jnp.sum(y32 * dy32, axis=-1, keepdims=True)
        dx = (scale * y32 * (dy32 - inner)).astype(dy.dtype)
    return dx, None


_softmax.defvjp(_softmax_fwd, _softmax_bwd)


# Public API ----------------------------------------------------------------


def scaled_softmax(x, scale: float = 1.0):
    """scale+softmax, no mask (``scaled_softmax_cuda``). x: [b, np, sq, sk]."""
    return _softmax(x, None, float(scale), False)


def scaled_masked_softmax(x, mask, scale: float = 1.0):
    """Scaled softmax with additive-style boolean mask (True = mask out).

    Parity: ``scaled_masked_softmax_cuda`` — mask is [b, 1, sq, sk] (or
    broadcastable); fully-masked rows yield zeros.
    """
    return _softmax(x, mask.astype(jnp.bool_), float(scale), False)


def scaled_upper_triang_masked_softmax(x, scale: float = 1.0):
    """Causal scaled softmax (``scaled_upper_triang_masked_softmax_cuda``).

    x: [b*np or b, np, sq, sk] with sq == sk in the reference; we allow
    sq <= sk (mask aligned to the last query).
    """
    return _softmax(x, None, float(scale), True)


def scaled_causal_masked_softmax(x, mask, scale: float = 1.0):
    """Causal triangle AND an explicit [b, 1, sq, sk] padding mask.

    The reference's upper-triang kernel asserts the mask is None; its
    dispatcher therefore can never combine the two.  TPU-side both are just
    predicates on the same VMEM tile, so the combined path exists and the
    dispatcher (transformer.functional.FusedScaleMaskSoftmax) uses it instead
    of silently dropping the triangle.
    """
    return _softmax(x, mask.astype(jnp.bool_), float(scale), True)


def generic_scaled_masked_softmax(x, mask, scale: float = 1.0):
    """Arbitrary-size fallback (``generic_scaled_masked_softmax_cuda``)."""
    return _jnp_custom(x, mask, float(scale))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _jnp_custom(x, mask, scale):
    return _jnp_softmax(x, scale, mask=mask)


def _jnp_custom_fwd(x, mask, scale):
    y = _jnp_softmax(x, scale, mask=mask)
    return y, y


def _jnp_custom_bwd(scale, y, dy):
    y32 = y.astype(jnp.float32)
    dy32 = dy.astype(jnp.float32)
    inner = jnp.sum(y32 * dy32, axis=-1, keepdims=True)
    return (scale * y32 * (dy32 - inner)).astype(dy.dtype), None


_jnp_custom.defvjp(_jnp_custom_fwd, _jnp_custom_bwd)
