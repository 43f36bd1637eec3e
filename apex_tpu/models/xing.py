"""Xing4.0 causal LM (``model_type`` ``xing4_0``): DeepSeek-V3's multi-head
latent attention in every layer - K and V of all heads expanded from one
compressed row a token beside one rope key all heads share, a query LoRA,
YaRN, every earlier row attended (no selector, no window, no head gate:
dots3's :class:`~apex_tpu.models.dots3.LatentAttention` of kind
``every_row``, sized by :meth:`XingConfig.attention`) - and a residual
stream ``hc_mult`` streams wide, mixed around each sublayer by
manifold-constrained hyper-connections (mHC, arXiv:2512.24880).  The first
``first_k_dense_replace`` layers have a SwiGLU MLP, the others sigmoid-routed
gated experts (top-``num_experts_per_tok`` of ``score + bias``, the chosen
scores renormalised and times ``routed_scaling_factor``) beside a shared
expert (:class:`apex_tpu.transformer.moe.GatedMoE`).  The equations are
written out in ``benchmark/reference/xing.py``, the plain float32 forward
this module is tested against.

The residual stream of a token is ``x [hc_mult, hidden]`` in float32.  Each
sublayer ``F`` (the attention, then the MLP or the experts, each with its
own pre-norm inside) is wrapped as::

    u = vec(x) / rms(vec(x))                            hc_mult x hidden values
    [p, q, r] = alpha (.) (u phi) + b                   phi: [n hidden, 2n + n^2]
    H_pre = sigmoid(p) + hc_eps;  H_post = 2 sigmoid(q)
    H_res = Sinkhorn(exp(clamp(mat(r))))                hc_sinkhorn_iters
    y = F(sum_i H_pre,i x_i);  x'_j = sum_i H_res,ji x_i + H_post,j y

after the embedding ``x_0 = [e, ..., e]`` and before the final norm the
read-out ``h = sum_i (sigmoid(alpha_h (u phi_h) + b_h)_i + hc_eps) x_i``.
All of it is the ``apex.residual`` scope.

Serving contract: :class:`Dots3NoteForCausalLM`'s (``input_ids``,
``kv_cache=``, ``position=``, ``slot=``, ``length=``, ``active=``, returning
``(logits, cache)``).  :meth:`XingForCausalLM.cache_layers` declares
:class:`~apex_tpu.serving.kv_cache.FullLatentRows` for every layer and call
counters for the expert layers.  A decode step reads every live latent row
in the absorbed form, a prompt chunk every visible row through each head's
K and V (``latent_decode_attend`` / ``latent_prefill_attend`` with no
selector).  Not built: the multi-token-prediction module, tensor
parallelism, a backward pass anybody has checked.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.models.dots3 import EVERY_ROW, GatedMLP, LatentAttention
from apex_tpu.models.mellum import RopeParameters
from apex_tpu.normalization import FusedRMSNorm
from apex_tpu.obs.scopes import (
    ATTN_PROJ,
    EMBED,
    HEAD,
    MLP,
    NORM,
    RESIDUAL,
    component,
)
from apex_tpu.transformer.moe import MOE_COUNTERS, GatedMoE
from apex_tpu.transformer.parallel_state import TENSOR_PARALLEL_AXIS
from apex_tpu.transformer.tensor_parallel import (
    VocabParallelEmbedding,
    parallel_lm_logits,
)

__all__ = ["XingConfig", "XingForCausalLM"]

# H_post = POST_GAIN sigmoid(q): a sublayer writes back at up to twice its
# output into each stream
POST_GAIN = 2.0


@dataclasses.dataclass(frozen=True)
class XingConfig:
    """The published keys the forward reads, under their own names
    (``rope_scaling``'s as ``rope_*`` / ``beta_*`` / ``mscale*``).
    ``n_routed_experts`` is the router's width; ``experts_held`` is the
    ``(start, count)`` of them this chip holds."""

    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216
    num_hidden_layers: int = 6
    first_k_dense_replace: int = 2
    num_attention_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e4
    rope_factor: float = 64.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0
    n_routed_experts: int = 64
    experts_held: Tuple[int, int] = (0, 64)
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1024
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.0
    rms_norm_eps: float = 1e-6
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0

    def rope(self) -> RopeParameters:
        """YaRN's frequencies; cos and sin times ``mscale(mscale) /
        mscale(mscale_all_dim)`` (1 as published)."""
        return RopeParameters(
            "yarn", self.rope_theta, self.rope_factor,
            self.original_max_position_embeddings, self.beta_fast,
            self.beta_slow,
            yarn_mscale(self.rope_factor, self.mscale)
            / yarn_mscale(self.rope_factor, self.mscale_all_dim))

    def attention(self, kind: str) -> dict:
        """What :class:`~apex_tpu.models.dots3.LatentAttention` reads of
        every layer (kind ``every_row``): DeepSeek-V3's latent attention
        with YaRN's rope and softmax scale, no head gate, no LoRA
        rescale."""
        return dict(
            heads=self.num_attention_heads, q_rank=self.q_lora_rank,
            rank=self.kv_lora_rank, nope=self.qk_nope_head_dim,
            rope=self.qk_rope_head_dim, v=self.v_head_dim, theta=self.rope(),
            scale=softmax_scale(self), gate=False, lora_rescale=False)


def yarn_mscale(factor: float, mscale: float) -> float:
    """DeepSeek-V3's YaRN magnitude: ``0.1 mscale ln(factor) + 1``, 1 where
    nothing is interpolated."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(cfg: XingConfig) -> float:
    """``(nope + rope)^-1/2 mscale(mscale_all_dim)^2``: 192^-1/2 x 1.4159^2
    as published."""
    return ((cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
            * yarn_mscale(cfg.rope_factor, cfg.mscale_all_dim) ** 2)


def sinkhorn(m, iters: int, eps: float):
    """``iters`` times: each row of ``m [..., n, n]`` over its sum +
    ``eps``, then each column over its sum + ``eps``."""
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


def _unit_rms(x, eps: float):
    """``vec(x) / rms(vec(x))`` of each token's streams ``x [t, n, C]``:
    ``[t, n C]`` float32."""
    flat = x.reshape(x.shape[0], -1)
    return flat * jax.lax.rsqrt((flat * flat).mean(-1, keepdims=True) + eps)


def _project(u, phi):
    """``u phi`` in float32: a few dozen columns, so every bit is kept."""
    return jnp.dot(u, phi, precision=jax.lax.Precision.HIGHEST)


def mappings(x, phi, alpha, bias, cfg: XingConfig):
    """One sublayer's ``(H_pre [t, n], H_post [t, n], H_res [t, n, n])``
    from its tokens' streams ``x [t, n, C]`` float32 and its parameters
    ``phi [n C, 2n + n^2]``, ``alpha [3]``, ``bias [2n + n^2]``."""
    n = x.shape[1]
    gain = jnp.repeat(alpha, jnp.array([n, n, n * n]),
                      total_repeat_length=2 * n + n * n)
    z = gain * _project(_unit_rms(x, cfg.rms_norm_eps), phi) + bias
    pre = jax.nn.sigmoid(z[:, :n]) + cfg.hc_eps
    post = POST_GAIN * jax.nn.sigmoid(z[:, n:2 * n])
    res = jnp.exp(jnp.clip(z[:, 2 * n:].reshape(-1, n, n),
                           cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max))
    return pre, post, sinkhorn(res, cfg.hc_sinkhorn_iters, cfg.hc_eps)


def read_out(x, phi, alpha, bias, cfg: XingConfig):
    """``sum_i (sigmoid(alpha (u phi) + b)_i + hc_eps) x_i``: the one row
    ``[t, C]`` float32 the final norm reads."""
    w = jax.nn.sigmoid(
        alpha[0] * _project(_unit_rms(x, cfg.rms_norm_eps), phi) + bias)
    return _weighted(w + cfg.hc_eps, x)


def _weighted(w, x):
    """``sum_i w[:, i] x[:, i]``: elementwise in float32 (a product of four
    terms is no matrix for the MXU, which would round its operands)."""
    return sum(w[:, i, None] * x[:, i] for i in range(x.shape[1]))


def _combine(res, post, x, y):
    """``x'_j = sum_i H_res,ji x_i + H_post,j y`` of each token, float32."""
    mixed = sum(res[:, :, i, None] * x[:, None, i] for i in range(x.shape[1]))
    return mixed + post[..., None] * y.astype(jnp.float32)[:, None]


class HyperConnection(nn.Module):
    """The parameters of one sublayer's mappings, or of the read-out
    (``read_out``): ``phi`` normal x ``init_std``, ``alpha`` ones, ``bias``
    zeros (what the benchmark draws instead: its configuration's
    ``assumed.weights``)."""

    config: XingConfig
    read_out: bool = False

    @nn.compact
    def __call__(self, x):
        n = self.config.hc_mult
        width = n if self.read_out else 2 * n + n * n
        phi = self.param("phi", nn.initializers.normal(0.02),
                         (n * self.config.hidden_size, width), jnp.float32)
        alpha = self.param("alpha", nn.initializers.ones,
                           (1 if self.read_out else 3,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (width,),
                          jnp.float32)
        fn = read_out if self.read_out else mappings
        return fn(x, phi, alpha, bias, self.config)


class XingLayer(nn.Module):
    """The attention, then the MLP or the experts, each wrapped by its own
    hyper-connection: ``x [s, b, hc_mult, hidden]`` float32 in and out."""

    config: XingConfig
    layer: int
    params_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, *, kv_cache=None, position=None, slot=None,
                 length=None, active=None):
        cfg, i = self.config, self.layer
        s, lanes, n, hidden = x.shape
        dt = self.params_dtype

        def norm(t, name):
            with component(NORM):
                return FusedRMSNorm((hidden,), eps=cfg.rms_norm_eps,
                                    param_dtype=jnp.float32, name=name)(t)

        def enter(name):
            """The sublayer's mappings and the row it reads."""
            with component(RESIDUAL):
                pre, post, res = HyperConnection(cfg, name=name)(
                    x.reshape(s * lanes, n, hidden))
                streams = x.reshape(s * lanes, n, hidden)
                return (post, res, _weighted(pre, streams).astype(dt)
                        .reshape(s, lanes, hidden))

        def leave(post, res, y):
            with component(RESIDUAL):
                return _combine(res, post, x.reshape(s * lanes, n, hidden),
                                y.reshape(s * lanes, hidden)).reshape(
                    s, lanes, n, hidden)

        post, res, h = enter("attn_hc")
        out, kv_cache = LatentAttention(cfg, EVERY_ROW, params_dtype=dt,
                                        name="self_attn")(
            norm(h, "input_layernorm"), kv_cache=kv_cache, layer_idx=i,
            position=position, slot=slot, length=length)
        x = leave(post, res, out)

        post, res, h = enter("mlp_hc")
        h = norm(h, "post_attention_layernorm")
        if i < cfg.first_k_dense_replace:
            with component(MLP):
                out = GatedMLP(cfg.intermediate_size, dt, name="mlp")(h)
            return leave(post, res, out), kv_cache
        decode = kv_cache is not None and s == 1
        # rows are s-major: a decode step's are its lanes, a chunk's (one
        # lane) its positions
        if decode:
            valid = active
        elif kv_cache is not None:
            valid = jnp.arange(s) < length
        else:
            valid = None
        out, counts = GatedMoE(
            num_experts=cfg.n_routed_experts, experts_held=cfg.experts_held,
            top_k=cfg.num_experts_per_tok, hidden_size=hidden,
            expert_width=cfg.moe_intermediate_size,
            shared_width=cfg.moe_intermediate_size * cfg.n_shared_experts,
            routed_scaling_factor=cfg.routed_scaling_factor,
            param_dtype=dt, name="mlp")(h.reshape(s * lanes, -1), valid)
        if decode:
            from apex_tpu.serving.kv_cache import add_counts

            kv_cache = add_counts(kv_cache, i - cfg.first_k_dense_replace,
                                  counts)
        return leave(post, res, out), kv_cache


class XingForCausalLM(nn.Module):
    """Embedding, expanded to ``hc_mult`` streams -> the layers -> the
    read-out -> final RMSNorm -> untied head.

    ``__call__(input_ids [b, s])`` returns logits ``[s, b, vocab]``.  With
    ``kv_cache`` (built by ``DecodeEngine`` from :meth:`cache_layers`) it
    returns ``(logits, kv_cache)``: ``input_ids [1, s > 1]`` + ``slot`` +
    scalar ``position`` + ``length`` prefills one chunk of one slot, of
    which the first ``length`` rows are real; ``input_ids [slots, 1]`` +
    ``position [slots]`` + ``active [slots]`` runs one decode step.
    ``keep`` (a traced int32 row index) cuts the streams to that one row
    after the last layer, so that the read-out, the final norm and the head
    run on it alone and the logits are ``[1, b, vocab]``; ``None`` keeps
    every row."""

    config: XingConfig
    params_dtype: Any = jnp.float32
    axis_name: str = TENSOR_PARALLEL_AXIS

    def cache_layers(self) -> list:
        """What each sublayer keeps a slot between calls: every layer's
        attention its latent rows, read whole; an expert layer its
        counters."""
        from apex_tpu.serving.kv_cache import CallCounters, FullLatentRows

        cfg = self.config
        out = []
        for i in range(cfg.num_hidden_layers):
            out.append(FullLatentRows(cfg.kv_lora_rank
                                      + cfg.qk_rope_head_dim))
            out.append(None if i < cfg.first_k_dense_replace
                       else CallCounters(MOE_COUNTERS))
        return out

    @nn.compact
    def __call__(self, input_ids, *, kv_cache=None, position=None, slot=None,
                 length=None, active=None, keep=None):
        cfg = self.config
        if kv_cache is not None:
            s = input_ids.shape[1]
            if s == 1 and active is None:
                raise ValueError("a decode step needs active= (the lanes "
                                 "whose tokens the experts count)")
            if s > 1 and length is None:
                raise ValueError("a prefill chunk needs length= (its real "
                                 "rows: the experts count no padding)")
        with component(EMBED):
            e = VocabParallelEmbedding(
                cfg.vocab_size, cfg.hidden_size,
                params_dtype=self.params_dtype, axis_name=self.axis_name,
                name="embed_tokens")(input_ids)
            e = e.transpose(1, 0, 2)                       # [s, b, h]
        with component(RESIDUAL):
            x = jnp.broadcast_to(e.astype(jnp.float32)[:, :, None],
                                 e.shape[:2] + (cfg.hc_mult, cfg.hidden_size))
        for i in range(cfg.num_hidden_layers):
            x, kv_cache = XingLayer(
                cfg, i, params_dtype=self.params_dtype, name=f"layers_{i}")(
                x, kv_cache=kv_cache, position=position, slot=slot,
                length=length, active=active)
        with component(RESIDUAL):
            if keep is not None:
                x = lax.dynamic_slice_in_dim(x, keep, 1, axis=0)
            s, b = x.shape[:2]
            h = HyperConnection(cfg, read_out=True, name="head_hc")(
                x.reshape(s * b, cfg.hc_mult, cfg.hidden_size))
            h = h.reshape(s, b, cfg.hidden_size).astype(e.dtype)
        with component(HEAD):
            h = FusedRMSNorm((cfg.hidden_size,), eps=cfg.rms_norm_eps,
                             param_dtype=jnp.float32, name="norm")(h)
            head = self.param("lm_head", nn.initializers.normal(0.02),
                              (cfg.vocab_size, cfg.hidden_size),
                              self.params_dtype)
            logits = parallel_lm_logits(h, head.astype(h.dtype),
                                        self.axis_name)
        return logits if kv_cache is None else (logits, kv_cache)
