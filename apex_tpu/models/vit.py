"""Vision Transformer, TPU-first.

Parity target: the BASELINE.md target row "ViT-L/16 (SyncBatchNorm +
FusedAdam, DP)" — the vision-family flagship the reference's toolbox
trains.  Composition over apex_tpu's kernels and tp layers:

- patch embedding as one dense on unfolded patches (XLA lowers the
  equivalent conv to the same MXU matmul; the unfold keeps it explicitly
  batched and shard-friendly)
- pre-LN encoder blocks from Column/RowParallelLinear (tp-shardable
  heads/MLP), :class:`~apex_tpu.normalization.FusedLayerNorm` (Pallas),
  exact gelu (HF ViT convention), XLA-fused materialized attention (the
  n^2+1 token count is never lane-aligned, and sub-1024 sequences are
  where the materialized path measures faster anyway — PERF_NOTES.md)
- [CLS]-token classification head

Numerics are pinned against ``transformers.ViTForImageClassification``
(torch CPU oracle) in ``tests/test_vit.py`` — same weights, same logits.

Layout: tokens are [s, b, h] (Megatron layout) inside the encoder;
inputs are NHWC images [b, H, W, C].
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import flax.linen as nn

from apex_tpu.normalization import FusedLayerNorm
from apex_tpu.obs.scopes import ATTN_PROJ, component
from apex_tpu.transformer.parallel_state import TENSOR_PARALLEL_AXIS
from apex_tpu.transformer.tensor_parallel import (
    ColumnParallelLinear,
    RowParallelLinear,
    tp_world_size,
)

__all__ = ["ViTConfig", "ViTForImageClassification"]


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    """ViT architecture knobs (HF ViTConfig field names)."""

    image_size: int = 224
    patch_size: int = 16
    num_channels: int = 3
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    layer_norm_eps: float = 1e-12
    num_labels: int = 1000

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @classmethod
    def vit_l16(cls) -> "ViTConfig":
        """ViT-Large/16: 24 x 1024, 16 heads, 4096 MLP, 16px patches."""
        return cls(hidden_size=1024, num_hidden_layers=24,
                   num_attention_heads=16, intermediate_size=4096)


class ViTSelfAttention(nn.Module):
    config: ViTConfig
    params_dtype: Any = jnp.float32
    axis_name: str = TENSOR_PARALLEL_AXIS

    @nn.compact
    @component(ATTN_PROJ)
    def __call__(self, x):
        cfg = self.config
        world = tp_world_size(self.axis_name)
        nh = cfg.num_attention_heads // world
        hd = cfg.hidden_size // cfg.num_attention_heads
        common = dict(params_dtype=self.params_dtype,
                      axis_name=self.axis_name, gather_output=False)
        q = ColumnParallelLinear(cfg.hidden_size, cfg.hidden_size,
                                 name="query", **common)(x)
        k = ColumnParallelLinear(cfg.hidden_size, cfg.hidden_size,
                                 name="key", **common)(x)
        v = ColumnParallelLinear(cfg.hidden_size, cfg.hidden_size,
                                 name="value", **common)(x)
        s, b = x.shape[0], x.shape[1]
        to_bhsd = lambda t: t.reshape(s, b, nh, hd).transpose(1, 2, 0, 3)
        scale = 1.0 / float(hd) ** 0.5
        # ViT token counts (n^2 patches + [CLS]) are never lane-aligned
        # (n^2 + 1 % 128 == 0 has no integer solution), so the flash
        # kernel cannot apply; the materialized softmax is XLA-fused and,
        # per the openfold measurement (PERF_NOTES.md), FASTER than a
        # flash kernel at these sub-1024 sequence lengths anyway
        qt, kt, vt = to_bhsd(q), to_bhsd(k), to_bhsd(v)
        sc = jax.lax.dot_general(
            qt.astype(jnp.float32) * scale, kt.astype(jnp.float32),
            (((3,), (3,)), ((0, 1), (0, 1))))
        p = jax.nn.softmax(sc, axis=-1)
        ctx = jax.lax.dot_general(
            p, vt.astype(jnp.float32),
            (((3,), (2,)), ((0, 1), (0, 1)))).astype(x.dtype)
        ctx = ctx.transpose(2, 0, 1, 3).reshape(s, b, nh * hd)
        return RowParallelLinear(cfg.hidden_size, cfg.hidden_size,
                                 input_is_parallel=True,
                                 params_dtype=self.params_dtype,
                                 axis_name=self.axis_name,
                                 name="output")(ctx)


class ViTLayer(nn.Module):
    """Pre-LN block: LN → attn → +res → LN → MLP(exact gelu) → +res."""

    config: ViTConfig
    params_dtype: Any = jnp.float32
    axis_name: str = TENSOR_PARALLEL_AXIS

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        h = FusedLayerNorm(cfg.hidden_size, eps=cfg.layer_norm_eps,
                           param_dtype=self.params_dtype,
                           name="layernorm_before")(x)
        x = x + ViTSelfAttention(cfg, params_dtype=self.params_dtype,
                                 axis_name=self.axis_name,
                                 name="attention")(h)
        h = FusedLayerNorm(cfg.hidden_size, eps=cfg.layer_norm_eps,
                           param_dtype=self.params_dtype,
                           name="layernorm_after")(x)
        h = ColumnParallelLinear(cfg.hidden_size, cfg.intermediate_size,
                                 gather_output=False,
                                 params_dtype=self.params_dtype,
                                 axis_name=self.axis_name,
                                 name="intermediate")(h)
        h = nn.gelu(h, approximate=False)  # HF ViT uses exact gelu
        h = RowParallelLinear(cfg.intermediate_size, cfg.hidden_size,
                              input_is_parallel=True,
                              params_dtype=self.params_dtype,
                              axis_name=self.axis_name, name="output")(h)
        return x + h


class ViTForImageClassification(nn.Module):
    """Patch embed + [CLS] + encoder + LN + linear head → logits [b, L]."""

    config: ViTConfig
    params_dtype: Any = jnp.float32
    axis_name: str = TENSOR_PARALLEL_AXIS

    @nn.compact
    def __call__(self, pixels):
        cfg = self.config
        b = pixels.shape[0]
        p = cfg.patch_size
        n = cfg.image_size // p
        # NHWC -> [b, n*n, p*p*C] patches (channel-fastest to match the
        # torch conv weight layout after transpose)
        x = pixels.reshape(b, n, p, n, p, cfg.num_channels)
        x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, n * n, p * p
                                                  * cfg.num_channels)
        proj_w = self.param("patch_kernel", nn.initializers.lecun_normal(),
                            (p * p * cfg.num_channels, cfg.hidden_size),
                            self.params_dtype)
        proj_b = self.param("patch_bias", nn.initializers.zeros,
                            (cfg.hidden_size,), self.params_dtype)
        x = x @ proj_w.astype(x.dtype) + proj_b.astype(x.dtype)

        cls = self.param("cls_token", nn.initializers.zeros,
                         (1, 1, cfg.hidden_size), self.params_dtype)
        pos = self.param("position_embeddings", nn.initializers.normal(0.02),
                         (1, cfg.num_patches + 1, cfg.hidden_size),
                         self.params_dtype)
        x = jnp.concatenate(
            [jnp.broadcast_to(cls.astype(x.dtype),
                              (b, 1, cfg.hidden_size)), x], axis=1)
        x = x + pos.astype(x.dtype)

        x = x.transpose(1, 0, 2)  # [s, b, h]
        for i in range(cfg.num_hidden_layers):
            x = ViTLayer(cfg, params_dtype=self.params_dtype,
                         axis_name=self.axis_name, name=f"layer_{i}")(x)
        x = FusedLayerNorm(cfg.hidden_size, eps=cfg.layer_norm_eps,
                           param_dtype=self.params_dtype, name="layernorm")(x)
        cls_out = x[0]            # [b, h]
        head_w = self.param("classifier_kernel",
                            nn.initializers.lecun_normal(),
                            (cfg.hidden_size, cfg.num_labels),
                            self.params_dtype)
        head_b = self.param("classifier_bias", nn.initializers.zeros,
                            (cfg.num_labels,), self.params_dtype)
        return cls_out @ head_w.astype(cls_out.dtype) \
            + head_b.astype(cls_out.dtype)
