"""OpenFold acceleration kernels (apex.contrib.openfold_triton parity).

Parity target: ``apex.contrib.openfold_triton`` — Triton kernels for the
AlphaFold/OpenFold Evoformer: the fused attention core with pair bias
(mha.py:131-460), small-shape LayerNorm (layer_norm.py:26-140), and the
FusedAdamSWA optimizer (fused_adam_swa.py:209-470) that applies Adam and
stochastic-weight-averaging in one sweep.

TPU design notes:
- ``attention_core``: one jnp expression — XLA fuses the
  scale/bias/mask/softmax chain into the two MXU matmuls, which is the
  whole job of the Triton kernel.  The reference's ``CanSchTriMHA`` shape
  allowlist (mha.py:36-88, a hand-tuned table of Evoformer shapes the
  Triton kernel handles) is a Triton scheduling constraint with no TPU
  meaning: every shape takes the fused path, so it returns True.
- ``LayerNormSmallShapeOptImpl``: the Pallas fused LN already handles
  small trailing shapes; re-exported under the reference name.
- ``FusedAdamSWA``: Adam step + EMA/SWA average in one update, built on
  the repo's FusedAdam with the swa buffer carried in the optimizer state.
- The Triton autotune-cache plumbing (``_save/_load_triton_auto_tune_cache``,
  ``sync_triton_auto_tune_cache_across_gpus``) is GPU-compile machinery;
  XLA's persistent compilation cache plays that role and needs no
  per-kernel sync, so those helpers are no-ops kept for script parity.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from apex_tpu.contrib.openfold_triton.fused_adam_swa import (
    AdamMathType,
    FusedAdamSWA,
)
from apex_tpu.ops.layer_norm import fused_layer_norm_affine

__all__ = ["attention_core", "AttnBiasJIT", "AttnNoBiasJIT", "CanSchTriMHA",
           "LayerNormSmallShapeOptImpl", "FusedAdamSWA", "AdamMathType",
           "sync_triton_auto_tune_cache_across_gpus"]


def CanSchTriMHA(in_shape, has_bias=True, inf=1e9, training=True):
    """Shape allowlist gate (mha.py:36-88) — always schedulable on TPU."""
    del in_shape, has_bias, inf, training
    return True


def attention_core(q, k, v, mask=None, bias=None, inf=1e9,
                   is_training=True):
    """Evoformer attention: softmax(q·kᵀ + bias + mask_fill) · v
    (mha.py FusedAttenionCoreFunc.forward:133-246).

    q/k/v: [..., H, S, D] with q pre-scaled by the caller (OpenFold passes
    q already divided by sqrt(d)); ``mask`` is a broadcastable 0/1 tensor
    (0 = masked, filled with -inf); ``bias`` is the pair-bias term.

    The 5-D MSA-row pattern ([b, r, h, s, d] with [b, 1, h, s, s] pair
    bias and [b, r, 1, 1, s] kv mask) dispatches to the Pallas pair-bias
    flash kernel (:mod:`apex_tpu.ops.pair_bias_attention` — scores never
    materialize; dbias reduces over rows in-kernel) for s >= 1024; other
    layouts and Evoformer-scale sequences take the materialized jnp path
    below (measured faster there — see the routing gate).  Two contract
    differences on the kernel path: ``inf`` is ignored (fixed -1e30
    fill), and FULLY-masked query rows emit exact zeros with zero
    gradients, where the materialized path produces the softmax-over--inf
    uniform average.  OpenFold never fully masks a row in practice.
    """
    del is_training
    routed = _route_pair_bias(q, k, v, mask, bias)
    if routed is not None:
        return routed
    scores = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32)
    if bias is not None:
        scores = scores + bias.astype(jnp.float32)
    if mask is not None:
        scores = jnp.where(mask.astype(bool), scores, -float(inf))
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", probs.astype(q.dtype), v)


def _route_pair_bias(q, k, v, mask, bias):
    """Dispatch the Evoformer 5-D layout to the Pallas kernel; None if the
    shapes don't fit its contract."""
    from apex_tpu.ops.pair_bias_attention import pair_bias_flash_attention

    if q.ndim != 5 or bias is None or bias.ndim != 5:
        return None
    b, r, h, s, d = q.shape
    # measured on v5e (PERF_NOTES.md, r2): at Evoformer scale
    # (s=256, d=32) the materialized XLA path runs at its bandwidth
    # roofline (4.5 ms) while the kernel's per-tile overhead dominates
    # (89 ms) — the kernel only wins once the s^2 scores are too big to
    # stream, so routing is gated on long sequences
    if s < 1024:
        return None
    if bias.shape != (b, 1, h, s, s) or s % 128 or d % 8:
        return None
    kv_mask = None
    if mask is not None:
        if mask.shape != (b, r, 1, 1, s):
            return None
        # [b, r, s] -> rows-major [r*b, s] (bias batch is the inner factor)
        kv_mask = (mask.astype(bool)[:, :, 0, 0, :]
                   .transpose(1, 0, 2).reshape(r * b, s))
    # [b, r, ...] -> [r, b, ...] -> [r*b, h, s, d]
    to_flat = lambda x: x.transpose(1, 0, 2, 3, 4).reshape(r * b, h, s, d)
    out = pair_bias_flash_attention(
        to_flat(q), to_flat(k), to_flat(v), bias[:, 0], kv_mask)
    return out.reshape(r, b, h, s, d).transpose(1, 0, 2, 3, 4)


# reference export names for the two jitted variants (mha.py:400-460)
AttnBiasJIT = attention_core
AttnNoBiasJIT = attention_core


class LayerNormSmallShapeOptImpl:
    """layer_norm.py:26-140 — function-object form over the Pallas LN."""

    @staticmethod
    def apply(inputs, normalized_shape, weight, bias, eps=1e-5):
        """Affine LayerNorm over ``normalized_shape`` via the Pallas
        fused kernel (drop-in for the Triton small-shape impl)."""
        return fused_layer_norm_affine(inputs, weight, bias,
                                       normalized_shape, eps=eps)


def sync_triton_auto_tune_cache_across_gpus(*args, **kwargs):
    """No-op: XLA's compile cache replaces Triton autotune sync."""
    return None
