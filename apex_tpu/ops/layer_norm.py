"""Fused LayerNorm / RMSNorm forward+backward (Pallas TPU + jnp fallback).

Parity target: the reference's ``fused_layer_norm_cuda`` extension
(csrc/layer_norm_cuda.cpp:446-459, csrc/layer_norm_cuda_kernel.cu:13-212):
LayerNorm *and* RMSNorm, affine / non-affine, mixed input/weight dtype
(Megatron-compatible), and the ``memory_efficient`` variant that saves the
*output* instead of the input and reconstructs the normalized activations in
backward.

TPU design: statistics are a row reduction — a natural VPU job.  The Pallas
forward computes mean/rstd per row and writes (y, mean, rstd); the backward
kernel accumulates dgamma/dbeta across the sequential TPU grid.  Internals are
fp32 regardless of I/O dtype, matching the CUDA kernels' Welford-in-fp32
accumulation.  When shapes don't meet the lane constraints (trailing dim not a
multiple of 128) we fall back to jnp — XLA fuses that path well; the Pallas
kernel exists to keep the activation in VMEM across the two passes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.ops._dispatch import lane_aligned, record_dispatch, use_interpret

# Rows per grid step; amortizes the per-step overhead while keeping the
# (block_rows, H) tile + fp32 temps within VMEM for H up to ~16k.
_BLOCK_ROWS = 128


# ---------------------------------------------------------------------------
# jnp reference path (also the CPU fallback, like the reference's
# torch.nn.functional.layer_norm fallback in fused_layer_norm.py:16-472)
# ---------------------------------------------------------------------------


def _norm_stats(x32: jax.Array, rms_only: bool, eps: float):
    if rms_only:
        mean = jnp.zeros(x32.shape[:-1], jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1)
    else:
        mean = jnp.mean(x32, axis=-1)
        var = jnp.mean(jnp.square(x32 - mean[..., None]), axis=-1)
    rstd = jax.lax.rsqrt(var + eps)
    return mean, rstd


def _jnp_forward(x, weight, bias, eps, rms_only):
    x32 = x.astype(jnp.float32)
    mean, rstd = _norm_stats(x32, rms_only, eps)
    xhat = (x32 - mean[..., None]) * rstd[..., None] if not rms_only else x32 * rstd[..., None]
    y = xhat
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype), mean, rstd


def _jnp_backward(dy, xhat, rstd, weight, rms_only):
    """Shared math for dx given normalized activations xhat (fp32)."""
    h = xhat.shape[-1]
    dy32 = dy.astype(jnp.float32)
    wdy = dy32 * weight.astype(jnp.float32) if weight is not None else dy32
    c2 = jnp.sum(wdy * xhat, axis=-1, keepdims=True) / h
    if rms_only:
        dx = (wdy - xhat * c2) * rstd[..., None]
    else:
        c1 = jnp.sum(wdy, axis=-1, keepdims=True) / h
        dx = (wdy - c1 - xhat * c2) * rstd[..., None]
    return dx


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------


def _fwd_kernel(x_ref, w_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps, rms_only, affine, has_bias):
    x = x_ref[:].astype(jnp.float32)
    h = x.shape[-1]
    if rms_only:
        mean = jnp.zeros((x.shape[0],), jnp.float32)
        var = jnp.sum(x * x, axis=-1) / h
        xhat = x * jax.lax.rsqrt(var + eps)[:, None]
    else:
        mean = jnp.sum(x, axis=-1) / h
        xc = x - mean[:, None]
        var = jnp.sum(xc * xc, axis=-1) / h
        xhat = xc * jax.lax.rsqrt(var + eps)[:, None]
    rstd = jax.lax.rsqrt(var + eps)
    y = xhat
    if affine:
        y = y * w_ref[0].astype(jnp.float32)[None, :]
        if has_bias:
            y = y + b_ref[0].astype(jnp.float32)[None, :]
    y_ref[:] = y.astype(y_ref.dtype)
    # stats live in a (grid, _BLOCK_ROWS) matrix: row g holds the stats of the
    # g-th row block.  Keeps every Pallas operand 2-D with a 128-lane trailing
    # dim (1-D f32 outputs get XLA's T(1024) tiling, which Mosaic rejects).
    # The stats arrays are tiny, so they ride along as full-array blocks and
    # are indexed by grid step here.
    g = pl.program_id(0)
    mean_ref[g, :] = mean
    rstd_ref[g, :] = rstd


def _bwd_kernel(dy_ref, xin_ref, mean_ref, rstd_ref, w_ref, b_ref,
                dx_ref, dw_ref, db_ref, *, rms_only, affine, has_bias, mem_eff):
    """One grid step: dx for this row block; accumulate dw/db across steps.

    The TPU grid is sequential, so accumulating into dw_ref/db_ref across
    steps is race-free — this replaces the CUDA kernel's two-stage partial
    dgamma/dbeta reduction (csrc/layer_norm_cuda_kernel.cu part2 kernels).
    """
    dy = dy_ref[:].astype(jnp.float32)
    g = pl.program_id(0)
    rstd = rstd_ref[g]  # (block_rows,) — row g of the (grid, block_rows) stats
    xin = xin_ref[:].astype(jnp.float32)
    h = dy.shape[-1]
    if mem_eff:
        # xin is the *output* y; invert the affine to recover xhat
        # (layer_norm_cuda_kernel.cu memory-efficient path semantics).
        xhat = xin
        if affine:
            if has_bias:
                xhat = xhat - b_ref[0].astype(jnp.float32)[None, :]
            xhat = xhat / w_ref[0].astype(jnp.float32)[None, :]
    else:
        if rms_only:
            xhat = xin * rstd[:, None]
        else:
            xhat = (xin - mean_ref[g][:, None]) * rstd[:, None]

    wdy = dy * w_ref[0].astype(jnp.float32)[None, :] if affine else dy
    c2 = jnp.sum(wdy * xhat, axis=-1, keepdims=True) / h
    if rms_only:
        dx = (wdy - xhat * c2) * rstd[:, None]
    else:
        c1 = jnp.sum(wdy, axis=-1, keepdims=True) / h
        dx = (wdy - c1 - xhat * c2) * rstd[:, None]
    dx_ref[:] = dx.astype(dx_ref.dtype)

    if affine:
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _init():
            dw_ref[:] = jnp.zeros_like(dw_ref)
            if has_bias:
                db_ref[:] = jnp.zeros_like(db_ref)

        dw_ref[0] += jnp.sum(dy * xhat, axis=0).astype(dw_ref.dtype)
        if has_bias:
            db_ref[0] += jnp.sum(dy, axis=0).astype(db_ref.dtype)


def _pad_rows(n):
    return (-n) % _BLOCK_ROWS


def _pallas_forward(x2d, weight, bias, eps, rms_only):
    n, h = x2d.shape
    pad = _pad_rows(n)
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    np_ = x2d.shape[0]
    affine = weight is not None
    has_bias = bias is not None
    w = (weight if affine else jnp.zeros((h,), x2d.dtype)).reshape(1, h)
    b = (bias if has_bias else jnp.zeros((h,), x2d.dtype)).reshape(1, h)
    grid = np_ // _BLOCK_ROWS
    y, mean, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, rms_only=rms_only,
                          affine=affine, has_bias=has_bias),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((_BLOCK_ROWS, h), lambda i: (i, 0)),
            pl.BlockSpec((grid, _BLOCK_ROWS), lambda i: (0, 0)),
            pl.BlockSpec((grid, _BLOCK_ROWS), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, h), x2d.dtype),
            jax.ShapeDtypeStruct((grid, _BLOCK_ROWS), jnp.float32),
            jax.ShapeDtypeStruct((grid, _BLOCK_ROWS), jnp.float32),
        ],
        interpret=use_interpret(),
        name="rms_norm_fwd" if rms_only else "layer_norm_fwd",
    )(x2d, w, b)
    mean, rstd = mean.reshape(np_), rstd.reshape(np_)
    if pad:
        y, mean, rstd = y[:n], mean[:n], rstd[:n]
    return y, mean, rstd


def _pallas_backward(dy2d, xin2d, mean, rstd, weight, bias, rms_only, mem_eff):
    n, h = dy2d.shape
    pad = _pad_rows(n)
    if pad:
        dy2d = jnp.pad(dy2d, ((0, pad), (0, 0)))
        xin2d = jnp.pad(xin2d, ((0, pad), (0, 0)))
        if mem_eff and bias is not None:
            # padded rows of y must still invert the affine cleanly; adding
            # bias there makes xhat zero instead of -b/w.
            xin2d = xin2d.at[n:].set(jnp.broadcast_to(bias.astype(xin2d.dtype), (pad, h)))
        mean = jnp.pad(mean, (0, pad))
        rstd = jnp.pad(rstd, (0, pad))
    np_ = dy2d.shape[0]
    affine = weight is not None
    has_bias = bias is not None
    w = (weight if affine else jnp.zeros((h,), dy2d.dtype)).reshape(1, h)
    b = (bias if has_bias else jnp.zeros((h,), dy2d.dtype)).reshape(1, h)
    wdtype = weight.dtype if affine else dy2d.dtype
    grid = np_ // _BLOCK_ROWS
    mean2 = mean.reshape(grid, _BLOCK_ROWS)
    rstd2 = rstd.reshape(grid, _BLOCK_ROWS)
    dx, dw, db = pl.pallas_call(
        functools.partial(_bwd_kernel, rms_only=rms_only, affine=affine,
                          has_bias=has_bias, mem_eff=mem_eff),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, h), lambda i: (i, 0)),
            pl.BlockSpec((_BLOCK_ROWS, h), lambda i: (i, 0)),
            pl.BlockSpec((grid, _BLOCK_ROWS), lambda i: (0, 0)),
            pl.BlockSpec((grid, _BLOCK_ROWS), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((_BLOCK_ROWS, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, h), dy2d.dtype),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
        ],
        interpret=use_interpret(),
        name="rms_norm_bwd" if rms_only else "layer_norm_bwd",
    )(dy2d, xin2d, mean2, rstd2, w, b)
    if pad:
        dx = dx[:n]
    dw = dw.reshape(h).astype(wdtype) if affine else None
    db = db.reshape(h).astype(bias.dtype) if has_bias else None
    return dx, dw, db


# VMEM budget for the kernel path: each grid step holds a few
# (_BLOCK_ROWS, H) fp32 tiles (x/y/temps fwd; dy/xin/dx bwd), so H is capped
# at 4096 (~2 MiB per tile); the full-array stats blocks are (rows/128, 128)
# fp32, so the row count is capped to keep them small.  Larger shapes take
# the jnp fallback, which XLA handles fine.
_MAX_H = 4096
_MAX_ROWS = 256 * 1024


def _kernel_ok(op: str, n: int, h: int) -> bool:
    return record_dispatch(
        op, lane_aligned(h) and h <= _MAX_H and n <= _MAX_ROWS,
        rows=n, h=h)


# ---------------------------------------------------------------------------
# custom_vjp entry points
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _norm(x, weight, bias, eps, rms_only, memory_efficient):
    return _norm_fwd(x, weight, bias, eps, rms_only, memory_efficient)[0]


def _norm_fwd(x, weight, bias, eps, rms_only, memory_efficient):
    shape = x.shape
    h = shape[-1]
    x2d = x.reshape(-1, h)
    if _kernel_ok("norm_fwd", x2d.shape[0], h):
        y2d, mean, rstd = _pallas_forward(x2d, weight, bias, eps, rms_only)
    else:
        y2d, mean, rstd = _jnp_forward(x2d, weight, bias, eps, rms_only)
    y = y2d.reshape(shape)
    saved = y2d if memory_efficient else x2d
    return y, (saved, mean, rstd, weight, bias)


def _norm_bwd(eps, rms_only, memory_efficient, res, dy):
    saved, mean, rstd, weight, bias = res
    shape = dy.shape
    h = shape[-1]
    dy2d = dy.reshape(-1, h)
    if _kernel_ok("norm_bwd", dy2d.shape[0], h):
        dx2d, dw, db = _pallas_backward(dy2d, saved, mean, rstd, weight, bias,
                                        rms_only, memory_efficient)
    else:
        s32 = saved.astype(jnp.float32)
        if memory_efficient:
            xhat = s32
            if weight is not None:
                if bias is not None:
                    xhat = xhat - bias.astype(jnp.float32)
                xhat = xhat / weight.astype(jnp.float32)
        else:
            xhat = s32 * rstd[..., None] if rms_only else (s32 - mean[..., None]) * rstd[..., None]
        dx2d = _jnp_backward(dy2d, xhat, rstd, weight, rms_only).astype(dy.dtype)
        dy32 = dy2d.astype(jnp.float32)
        dw = jnp.sum(dy32 * xhat, axis=0).astype(weight.dtype) if weight is not None else None
        db = jnp.sum(dy32, axis=0).astype(bias.dtype) if bias is not None else None
    return dx2d.reshape(shape), dw, db


_norm.defvjp(_norm_fwd, _norm_bwd)


# Public functional API (apex.normalization functional forms,
# apex/normalization/fused_layer_norm.py fused_layer_norm{,_affine}, fused_rms_norm{,_affine}).


def fused_layer_norm(x, normalized_shape, eps: float = 1e-5, *,
                     memory_efficient: bool = False):
    _check_shape(x, normalized_shape)
    h = _numel(normalized_shape)
    y = _norm(x.reshape(*_lead(x, normalized_shape), h), None, None, eps, False,
              memory_efficient)
    return y.reshape(x.shape)


def fused_layer_norm_affine(x, weight, bias, normalized_shape, eps: float = 1e-5, *,
                            memory_efficient: bool = False):
    _check_shape(x, normalized_shape)
    h = _numel(normalized_shape)
    y = _norm(x.reshape(*_lead(x, normalized_shape), h), weight.reshape(h),
              bias.reshape(h), eps, False, memory_efficient)
    return y.reshape(x.shape)


def fused_rms_norm(x, normalized_shape, eps: float = 1e-5, *,
                   memory_efficient: bool = False):
    _check_shape(x, normalized_shape)
    h = _numel(normalized_shape)
    y = _norm(x.reshape(*_lead(x, normalized_shape), h), None, None, eps, True,
              memory_efficient)
    return y.reshape(x.shape)


def fused_rms_norm_affine(x, weight, normalized_shape, eps: float = 1e-5, *,
                          memory_efficient: bool = False):
    _check_shape(x, normalized_shape)
    h = _numel(normalized_shape)
    y = _norm(x.reshape(*_lead(x, normalized_shape), h), weight.reshape(h),
              None, eps, True, memory_efficient)
    return y.reshape(x.shape)


def _numel(shape) -> int:
    out = 1
    for s in tuple(shape):
        out *= int(s)
    return out


def _lead(x, normalized_shape):
    nd = len(tuple(normalized_shape))
    return x.shape[: x.ndim - nd]


def _check_shape(x, normalized_shape):
    ns = tuple(int(s) for s in tuple(normalized_shape))
    if tuple(x.shape[x.ndim - len(ns):]) != ns:
        raise ValueError(
            f"input trailing shape {x.shape[x.ndim - len(ns):]} != normalized_shape {ns}")
