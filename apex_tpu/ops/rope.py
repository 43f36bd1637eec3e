"""Fused rotary positional embedding — all four reference layouts.

Parity target: ``fused_rotary_positional_embedding``
(csrc/megatron/fused_rotary_positional_embedding.h, .cpp:243 bindings) via
``apex.transformer.functional.fused_rope`` (fused_rope.py:19-280):

- sbhd layout, on-the-fly sincos from a freqs tensor  (forward/backward)
- sbhd layout, cached cos/sin                         (forward/backward_cached)
- thd packed-varlen layout with cu_seqlens            (forward/backward_thd)
- 2d image layout with separate height/width freqs    (forward/backward_2d)

RoPE is pure elementwise math with a broadcast — on TPU this is a VPU job that
XLA fuses into the surrounding GEMMs/attention in one pass, so the "fused
kernel" here is a jitted jnp expression (the CUDA kernel exists to avoid torch
dispatching per-op; XLA has no such overhead).  Gradients come from autodiff
and fuse identically: d/dt of (t*cos + rotate(t)*sin) is (g*cos + rotate⁻¹(g)*sin),
the same kernel the reference hand-writes.

Only the first ``d2 = freqs.shape[-1]`` channels are rotated; the rest pass
through (matching the CUDA kernels' d2 < d handling).
"""

from __future__ import annotations

import math

import jax.numpy as jnp

__all__ = [
    "yarn_inv_freq",
    "fused_apply_rotary_pos_emb",
    "fused_apply_rotary_pos_emb_cached",
    "fused_apply_rotary_pos_emb_thd",
    "fused_apply_rotary_pos_emb_2d",
]


def yarn_inv_freq(dim: int, theta: float, *, factor: float,
                  original_max_position_embeddings: int, beta_fast: float,
                  beta_slow: float):
    """The ``dim // 2`` rotary frequencies of a head under YaRN (Peng et
    al. 2023, "NTK-by-parts"; the ``rope_type`` ``yarn`` of published
    configs), float32::

        extra_i = theta^(-2i/dim)             the trained frequencies
        inter_i = extra_i / factor            the same, positions interpolated
        dim(r) = dim ln(original / (2 pi r)) / (2 ln theta)
        low = floor(dim(beta_fast)),  high = ceil(dim(beta_slow))
        ramp_i = clip((i - low) / (high - low), 0, 1)
        inv_freq_i = inter_i ramp_i + extra_i (1 - ramp_i)

    ``dim(r)`` is the channel pair that turns ``r`` times over the original
    context: pairs that turn more than ``beta_fast`` times keep their
    frequency, pairs that turn less than ``beta_slow`` times are
    interpolated, the ramp between.  The ``attention_factor`` that goes with
    it (``0.1 ln(factor) + 1``) multiplies cos and sin and is the caller's."""

    def pair_of(turns: float) -> float:
        return (dim * math.log(original_max_position_embeddings
                               / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    pair = jnp.arange(dim // 2, dtype=jnp.float32)
    extra = 1.0 / theta ** (2 * pair / dim)
    ramp = jnp.clip((pair - low) / max(high - low, 1e-3), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def _rotate_half(x):
    """(x1, x2) -> (-x2, x1) over the last dim (the reference's v_src_rotate)."""
    half = x.shape[-1] // 2
    x1 = x[..., :half]
    x2 = x[..., half:]
    return jnp.concatenate([-x2, x1], axis=-1)


def _apply(t, cos, sin):
    """Rotate the first d2 channels of t by (cos, sin); pass the rest through."""
    d2 = cos.shape[-1]
    t_rot = t[..., :d2]
    rotated = (t_rot.astype(jnp.float32) * cos.astype(jnp.float32)
               + _rotate_half(t_rot).astype(jnp.float32) * sin.astype(jnp.float32)
               ).astype(t.dtype)
    if d2 == t.shape[-1]:
        return rotated
    return jnp.concatenate([rotated, t[..., d2:]], axis=-1)


def fused_apply_rotary_pos_emb(t, freqs, transpose_output_memory: bool = False):
    """RoPE on sbhd input ([s, b, h, d]); freqs is [s, 1, 1, d2], float.

    ``transpose_output_memory`` is a CUDA memory-layout hint
    (fused_rope.py:59-82); XLA owns layout on TPU so it is accepted and
    ignored.
    """
    del transpose_output_memory
    return _apply(t, jnp.cos(freqs), jnp.sin(freqs))


def fused_apply_rotary_pos_emb_cached(t, cos_, sin_, transpose_output_memory: bool = False):
    """RoPE on sbhd input with precomputed cos/sin of shape [s, 1, 1, d2]."""
    del transpose_output_memory
    return _apply(t, cos_, sin_)


def fused_apply_rotary_pos_emb_thd(t, cu_seqlens, freqs):
    """RoPE on thd packed-varlen input ([total_t, h, d]).

    ``cu_seqlens`` is [b+1] int32 cumulative sequence lengths; each packed
    sequence restarts at position 0 (fused_rope.py:191-211 semantics).  The
    position of token i is i - cu_seqlens[seq_of(i)], computed with a
    searchsorted instead of the CUDA kernel's per-block binary search.
    """
    total = t.shape[0]
    idx = jnp.arange(total, dtype=jnp.int32)
    seq_id = jnp.searchsorted(cu_seqlens.astype(jnp.int32), idx, side="right") - 1
    pos = idx - jnp.take(cu_seqlens.astype(jnp.int32), seq_id)
    f = jnp.squeeze(freqs, axis=(1, 2))  # [max_s, d2]
    f_t = jnp.take(f, pos, axis=0)  # [total_t, d2]
    cos = jnp.cos(f_t)[:, None, :]  # [total_t, 1, d2]
    sin = jnp.sin(f_t)[:, None, :]
    return _apply(t, cos, sin)


def fused_apply_rotary_pos_emb_2d(t, img_h, img_w, cos_h, sin_h, cos_w, sin_w):
    """2D (image) RoPE on bshd input ([b, s, h, d]) with s == img_h * img_w.

    First d/2 channels rotate by the height freqs, second d/2 by the width
    freqs (fused_rope.py:263-330, kernel .h:276-296).  cos_h/sin_h are
    [1, H, 1, d//2] with H >= img_h; cos_w/sin_w are [1, W, 1, d//2].
    """
    b, s, h, d = t.shape
    if s != img_h * img_w:
        raise ValueError(f"sequence length {s} != img_h*img_w = {img_h * img_w}")
    t5 = t.reshape(b, img_h, img_w, h, d)
    t_h, t_w = t5[..., : d // 2], t5[..., d // 2:]
    # height half: cos_h indexed by row → broadcast over columns
    ch = cos_h[:, :img_h, None, :, :]  # [1, img_h, 1, 1, d//2]
    sh = sin_h[:, :img_h, None, :, :]
    cw = cos_w[:, None, :img_w, :, :]  # [1, 1, img_w, 1, d//2]
    sw = sin_w[:, None, :img_w, :, :]
    out_h = _apply(t_h, ch, sh)
    out_w = _apply(t_w, cw, sw)
    return jnp.concatenate([out_h, out_w], axis=-1).reshape(b, s, h, d)
