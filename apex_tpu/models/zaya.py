"""ZAYA1 causal LM (``model_type`` ``zaya``): every layer is one compressed
convolutional attention (CCA) sublayer and one routed-expert sublayer.  The
equations are written out in ``benchmark/reference/zaya.py``, the plain
float32 forward this module is tested against.

CCA over ``H`` query heads and ``G`` K/V heads of ``d``, at position ``t``
(``a_t`` the normed input)::

    q~_t = a_t W_q  [H x d];   k~_t = a_t W_k  [G x d]
    v_t = [a_t W_v0 ; a_{t-1} W_v1]        the first G/2 heads of this token,
                                           the rest of the token before
    u_t = [q~_t ; k~_t]                    (H + G) d channels
    c_t = sum_j w0_j * u_{t-T0+1+j} + b0   depthwise along the sequence
    d_t^h = sum_j W1_j^h c_{t-T1+1+j}^h + b1^h     within each head
    m_t^g = (mean_{i in g} q~_t^i + k~_t^g) / 2
    q_t^i = d_t^i + m_t^{g(i)};   k_t^g = d_t^{H+g} + m_t^g
    q^ = q / |q|,  k^ = k / |k|;  rope on the first partial_rotary_factor x d
    p = softmax_tau(s_i q^_t^i . k^_tau^g);   o^i = sum_tau p v_tau^g

(``T0 = cca_time0``, ``T1 = cca_time1``; zeros before a prompt's first
position), then ``x + [o^0 .. o^{H-1}] W_o``.  The temperature ``s_i`` is
folded into the query as ``s_i sqrt(d) q^``, so that the K/V seam's reads,
which scale by ``d^-1/2``, take the call unchanged.

The router reads ``b = RMSNorm(x)`` and carries its down-projection across
the layers::

    r^l = b W_d^l;   rbar^l = r^l + gamma^l rbar^{l-1}    (rbar = r at layer 0)
    p = softmax(gelu(gelu(rbar W_1) W_2) W_3)              float32
    e* = argmax(p + beta);   y = p_e* (silu(b W_g^e*) * b W_u^e*) W_dn^e*

through :class:`~apex_tpu.transformer.moe.GatedMoE` with ``scoring="given"``.
Final RMSNorm; the head is the embedding.

Serving contract: :class:`~apex_tpu.models.mellum.MellumForCausalLM`'s
(``input_ids``, ``kv_cache=``, ``position=``, ``slot=``, ``length=``,
``active=``, ``keep=``, returning ``(logits, cache)``).
:meth:`ZayaForCausalLM.cache_layers` declares three entries a layer: K/V rows
(read through ``decode_attend`` / ``prefill_attend``, as every K/V model's),
the convolution tail a slot carries to its next token (the last ``T0 - 1``
inputs of the first convolution, the last ``T1 - 1`` of the second and the
shifted value heads' row, 2,688 values at the published widths) as the
``conv`` of a :class:`~apex_tpu.serving.kv_cache.RecurrentRows` with an empty
``ssm``, and the experts' counters; layer ``i`` is index ``i`` among each
kind.  A chunk that starts a prompt reads a zero tail (``slot_state``); a
decode step writes the active lanes' tails only (``write_lane_state``).

Not built: "residual-scaled MoD" (``described_as``; no key of the config
sizes it), tensor parallelism, a backward pass anybody has checked.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.normalization import FusedRMSNorm
from apex_tpu.obs.scopes import (
    ATTN_PROJ,
    EMBED,
    EXPERTS,
    HEAD,
    NORM,
    QK_MIX,
    ROUTER,
    STATE,
    component,
)
from apex_tpu.ops.rope import fused_apply_rotary_pos_emb_cached
from apex_tpu.transformer.moe import MOE_COUNTERS, GatedMoE
from apex_tpu.transformer.parallel_state import TENSOR_PARALLEL_AXIS
from apex_tpu.transformer.tensor_parallel import (
    VocabParallelEmbedding,
    parallel_lm_logits,
)

__all__ = ["ZayaConfig", "ZayaForCausalLM"]

_HIGHEST = lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class ZayaConfig:
    """The published keys the forward reads, under their own names
    (``rope_parameters.hybrid``'s ``rope_theta`` as ``rope_theta``).
    ``experts_held`` is the ``(start, count)`` of the ``num_experts`` this
    chip holds."""

    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5e6
    num_experts: int = 16
    experts_held: Tuple[int, int] = (0, 16)
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048
    router_hidden_size: int = 256
    rms_norm_eps: float = 1e-5

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not group over "
                f"{self.num_key_value_heads} K/V heads")
        if self.num_key_value_heads % 2:
            raise ValueError(
                f"{self.num_key_value_heads} K/V heads: half of them take "
                f"this token's values and half the token before's")
        if self.num_experts_per_tok != 1:
            raise ValueError(f"num_experts_per_tok "
                             f"{self.num_experts_per_tok}: the router "
                             f"chooses one expert")
        if min(self.cca_time0, self.cca_time1) < 1:
            raise ValueError("cca_time0 and cca_time1 count taps: >= 1")

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def mixed(self) -> int:
        """Channels of ``u = [q~ ; k~]``: what the convolutions mix."""
        return (self.num_attention_heads + self.num_key_value_heads) \
            * self.head_dim

    @property
    def shifted(self) -> int:
        """Channels of the value heads read from the token before."""
        return self.num_key_value_heads // 2 * self.head_dim

    @property
    def tail_width(self) -> int:
        """What a slot carries to its next token: the last ``T0 - 1`` ``u``,
        the last ``T1 - 1`` ``c`` and the shifted heads' row."""
        return ((self.cca_time0 - 1) + (self.cca_time1 - 1)) * self.mixed \
            + self.shifted


# -- the mechanisms, one function each (the tests leave each out in turn) ----


def sequence_conv(u_ext, kernel, bias, rows: int):
    """``c [rows, b, C]``: the depthwise causal convolution along the
    sequence of ``u_ext [T0 - 1 + rows, b, C]`` (the carried inputs, then
    the rows), ``kernel [T0, C]``, float32."""
    taps = kernel.shape[0]
    u = u_ext.astype(jnp.float32)
    return sum(u[j:j + rows] * kernel[j].astype(jnp.float32)
               for j in range(taps)) + bias.astype(jnp.float32)


def channel_conv(c_ext, kernel, bias, rows: int):
    """``d [rows, b, heads, d]``: the causal convolution within each head
    of ``c_ext [T1 - 1 + rows, b, heads, d]``, ``kernel [T1, heads, d,
    d]`` (``d`` in, ``d`` out), float32 sums."""
    taps = kernel.shape[0]
    out = sum(jnp.einsum("sbhd,hde->sbhe", c_ext[j:j + rows],
                         kernel[j].astype(c_ext.dtype),
                         preferred_element_type=jnp.float32)
              for j in range(taps))
    return out + bias.astype(jnp.float32).reshape(kernel.shape[1], -1)


def qk_mean(q, k):
    """``m [s, b, G, d]``: half the sum of the mean of a group's query heads
    ``q [s, b, H, d]`` and its key head ``k [s, b, G, d]``, float32."""
    s, b, heads, d = q.shape
    groups = k.shape[2]
    mean = q.astype(jnp.float32).reshape(s, b, groups, heads // groups,
                                         d).mean(3)
    return (mean + k.astype(jnp.float32)) / 2


def unit(t):
    """``t / |t|`` over the last axis, float32."""
    t = t.astype(jnp.float32)
    return t * lax.rsqrt((t * t).sum(-1, keepdims=True))


def shifted_values(prev_row, rows):
    """The shifted value heads: row ``t`` takes ``rows[t - 1]`` and row 0
    ``prev_row``, the carried row of the token before (``rows [s, b, n]``,
    ``prev_row [b, n]``)."""
    return jnp.concatenate([prev_row[None], rows[:-1]], axis=0)


def depth_carry(r, gamma, carry):
    """``rbar = r + gamma carry``; ``r`` where no layer came before."""
    return r if carry is None else r + gamma * carry


def router_logits(rbar, mats):
    """``gelu(... gelu(rbar W_1) ...) W_n`` over ``mats``, float32."""
    z = rbar
    for w in mats[:-1]:
        z = jax.nn.gelu(jnp.dot(z, w, precision=_HIGHEST), approximate=False)
    return jnp.dot(z, mats[-1], precision=_HIGHEST)


def _rope(t, cfg: ZayaConfig, position):
    """Rotate-half over the first ``rotary_dim`` channels of ``t [s, b,
    heads, d]`` at positions ``position .. position + s`` (a scalar, or one
    a lane); the others pass through."""
    dim = cfg.rotary_dim
    inv = 1.0 / cfg.rope_theta ** (
        jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    offset = jnp.asarray(0 if position is None else position, jnp.float32)
    at = jnp.arange(t.shape[0], dtype=jnp.float32)[:, None] + offset.reshape(
        1, -1)                                             # [s, 1 | b]
    angles = at[..., None] * inv
    angles = jnp.concatenate([angles, angles], axis=-1)[:, :, None]
    return fused_apply_rotary_pos_emb_cached(t, jnp.cos(angles),
                                             jnp.sin(angles))


def _dense(features, x, params_dtype, name):
    return nn.Dense(features, use_bias=False, dtype=x.dtype,
                    param_dtype=params_dtype,
                    kernel_init=nn.initializers.normal(0.02), name=name)(x)


class CCAttention(nn.Module):
    """Compressed convolutional attention (the module docstring's
    equations).  Without a cache the whole sequence from zeros; with one, a
    chunk of one slot (``position`` scalar, ``length`` real rows) or a
    decode step (``position [lanes]``, ``active [lanes]``)."""

    config: ZayaConfig
    params_dtype: Any = jnp.float32

    @nn.compact
    @component(ATTN_PROJ)
    def __call__(self, x, *, kv_cache=None, layer_idx=None, position=None,
                 slot=None, length=None, active=None):
        cfg = self.config
        heads, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                         cfg.head_dim)
        t0, t1, mixed, shifted = (cfg.cca_time0, cfg.cca_time1, cfg.mixed,
                                  cfg.shifted)
        s, b, _ = x.shape
        dt = self.params_dtype
        q = _dense(heads * d, x, dt, "q_proj")
        k = _dense(nkv * d, x, dt, "k_proj")
        v = _dense(nkv * d, x, dt, "v_proj")
        u = jnp.concatenate([q, k], axis=-1)               # [s, b, mixed]
        normal = nn.initializers.normal(0.02)
        conv0 = self.param("conv_time0", lambda key, sh, dtype: {
            "kernel": normal(key, sh, dtype),
            "bias": jnp.zeros(sh[1:], dtype)}, (t0, mixed), dt)
        conv1 = self.param("conv_time1", lambda key, sh, dtype: {
            "kernel": normal(key, sh, dtype),
            "bias": jnp.zeros((sh[1] * sh[2],), dtype)},
            (t1, heads + nkv, d, d), dt)
        temperature = self.param(
            "temperature", nn.initializers.constant(d ** 0.5), (heads,),
            jnp.float32)

        decode = kv_cache is not None and s == 1
        if kv_cache is None:
            prev = jnp.zeros((b, cfg.tail_width), x.dtype)
        else:
            from apex_tpu.serving.kv_cache import slot_state

            if decode:
                with component(STATE):
                    prev = kv_cache.state.conv[layer_idx]
            else:
                prev = slot_state(kv_cache, layer_idx, slot, position)[1][None]
            prev = prev.astype(x.dtype)
        cut = [(t0 - 1) * mixed, (t0 - 1 + t1 - 1) * mixed]
        prev_u, prev_c, prev_v = jnp.split(prev, cut, axis=-1)
        prev_u = prev_u.reshape(b, t0 - 1, mixed).transpose(1, 0, 2)
        prev_c = prev_c.reshape(b, t1 - 1, mixed).transpose(1, 0, 2)

        with component(QK_MIX):
            u_ext = jnp.concatenate([prev_u, u], axis=0)
            c = sequence_conv(u_ext, conv0["kernel"], conv0["bias"],
                              s).astype(x.dtype)
            c_ext = jnp.concatenate([prev_c, c], axis=0)
            dd = channel_conv(
                c_ext.reshape(t1 - 1 + s, b, heads + nkv, d),
                conv1["kernel"], conv1["bias"], s)
            m = qk_mean(q.reshape(s, b, heads, d), k.reshape(s, b, nkv, d))
            rep = heads // nkv
            qn = unit(dd[:, :, :heads] + jnp.repeat(m, rep, axis=2))
            kn = unit(dd[:, :, heads:] + m)
            # the temperature folded into the query: the reads scale by
            # d^-1/2 and then score s_i (q^ . k^)
            qn = qn * (temperature * d ** 0.5)[:, None]
        qn = _rope(qn.astype(x.dtype), cfg, position)
        kn = _rope(kn.astype(x.dtype), cfg, position)
        v_now, v_next = v[..., :nkv * d - shifted], v[..., nkv * d - shifted:]
        v = jnp.concatenate([v_now, shifted_values(prev_v, v_next)],
                            axis=-1).reshape(s, b, nkv, d)

        if kv_cache is None:
            ctx = self._uncached(qn, kn, v)
        else:
            from apex_tpu.serving.kv_cache import (
                decode_attend,
                prefill_attend,
                write_lane_state,
                write_slot_state,
            )

            # the state is the tail alone: its ssm part has no values
            empty = kv_cache.state.ssm[layer_idx]

            if decode:
                tail = jnp.concatenate(
                    [u_ext[1:].transpose(1, 0, 2).reshape(b, -1),
                     c_ext[1:].transpose(1, 0, 2).reshape(b, -1),
                     v_next[0]], axis=-1)
                kv_cache = write_lane_state(kv_cache, layer_idx, empty, tail,
                                            active)
                ctx, kv_cache = decode_attend(kv_cache, layer_idx, qn, kn, v,
                                              position)
            else:
                # the tail after the last REAL row: padding leaves no trace
                tail = jnp.concatenate(
                    [lax.dynamic_slice_in_dim(u_ext[:, 0], length, t0 - 1,
                                              0).reshape(-1),
                     lax.dynamic_slice_in_dim(c_ext[:, 0], length, t1 - 1,
                                              0).reshape(-1),
                     lax.dynamic_index_in_dim(v_next[:, 0], length - 1, 0,
                                              keepdims=False)])
                kv_cache = write_slot_state(kv_cache, layer_idx, slot,
                                            empty[0], tail)
                offset = jnp.asarray(0 if position is None else position,
                                     jnp.int32)
                ctx, kv_cache = prefill_attend(kv_cache, layer_idx, slot, qn,
                                               kn, v, offset)
        ctx = ctx.transpose(2, 0, 1, 3).reshape(s, b, heads * d)
        return _dense(cfg.hidden_size, ctx, dt, "o_proj"), kv_cache

    def _uncached(self, q, k, v):
        """The whole sequence at once in plain ``jax.numpy``, K and V
        repeated to the query heads, scaled as the cached reads scale.  The
        tests' path.  Returns ``[b, heads, s, d]``."""
        s, rep = q.shape[0], q.shape[2] // k.shape[2]
        at = jnp.arange(s)
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        sc = jnp.einsum("tbhd,sbhd->bhts", q, k,
                        preferred_element_type=jnp.float32
                        ) * q.shape[-1] ** -0.5
        probs = jax.nn.softmax(jnp.where(at[None] <= at[:, None], sc,
                                         -jnp.inf), -1)
        return jnp.einsum("bhts,sbhd->bhtd", probs.astype(v.dtype), v,
                          preferred_element_type=jnp.float32).astype(q.dtype)


class ZayaRouter(nn.Module):
    """The router MLP with its depth carry: ``rows [t, hidden]`` and the
    previous layer's ``carry [t, router_hidden_size]`` (None at the stage's
    first layer) to ``((chosen [t, 1] int32, weights [t, 1] float32),
    rbar)``, all float32."""

    config: ZayaConfig

    @nn.compact
    @component(ROUTER)
    def __call__(self, rows, carry):
        cfg = self.config
        width, experts = cfg.router_hidden_size, cfg.num_experts
        normal = nn.initializers.normal(0.02)
        down = self.param("down", normal, (cfg.hidden_size, width),
                          jnp.float32)
        gamma = self.param("gamma", nn.initializers.constant(0.5), (),
                           jnp.float32)
        mats = [self.param(f"mlp_{i}", normal, shape, jnp.float32)
                for i, shape in enumerate(((width, width), (width, width),
                                           (width, experts)))]
        beta = self.param("balance_bias", nn.initializers.zeros, (experts,),
                          jnp.float32)
        rbar = depth_carry(jnp.dot(rows.astype(jnp.float32), down,
                                   precision=_HIGHEST), gamma, carry)
        probs = jax.nn.softmax(router_logits(rbar, mats), axis=-1)
        chosen = jnp.argmax(probs + beta, axis=-1)[:, None]
        weights = jnp.take_along_axis(probs, chosen, axis=-1)
        return (chosen.astype(jnp.int32), weights), rbar


class ZayaLayer(nn.Module):
    """``x + cca(norm(x))`` then ``x + experts(norm(x))``, the router's
    depth carry handed on."""

    config: ZayaConfig
    params_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, carry, *, kv_cache=None, layer_idx=None,
                 position=None, slot=None, length=None, active=None):
        cfg = self.config

        def norm(t, name):
            with component(NORM):
                return FusedRMSNorm((cfg.hidden_size,), eps=cfg.rms_norm_eps,
                                    param_dtype=jnp.float32, name=name)(t)

        out, kv_cache = CCAttention(
            cfg, params_dtype=self.params_dtype, name="self_attn")(
            norm(x, "input_layernorm"), kv_cache=kv_cache,
            layer_idx=layer_idx, position=position, slot=slot, length=length,
            active=active)
        # a residual add is the root of the fusion XLA makes of it and the
        # product before it: it counts with the branch it closes
        with component(ATTN_PROJ):
            x = x + out.astype(x.dtype)
        h = norm(x, "post_attention_layernorm")
        s, lanes, _ = x.shape
        decode = kv_cache is not None and s == 1
        # rows are s-major: a decode step's are its lanes, a chunk's (one
        # lane) its positions
        if decode:
            valid = active
        elif kv_cache is not None:
            valid = jnp.arange(s) < length
        else:
            valid = None
        rows = h.reshape(s * lanes, -1)
        route, carry = ZayaRouter(cfg, name="router")(rows, carry)
        out, counts = GatedMoE(
            num_experts=cfg.num_experts, experts_held=cfg.experts_held,
            top_k=1, hidden_size=cfg.hidden_size,
            expert_width=cfg.moe_intermediate_size, shared_width=0,
            scoring="given", param_dtype=self.params_dtype, name="mlp")(
            rows, valid, route=route)
        if decode:
            from apex_tpu.serving.kv_cache import add_counts

            kv_cache = add_counts(kv_cache, layer_idx, counts)
        with component(EXPERTS):
            return (x + out.reshape(s, lanes, -1).astype(x.dtype), carry,
                    kv_cache)


class ZayaForCausalLM(nn.Module):
    """Embedding -> the layers -> final RMSNorm -> the embedding as head.

    ``__call__(input_ids [b, s])`` returns logits ``[s, b, vocab]`` from
    zero tails.  With ``kv_cache`` (built by ``DecodeEngine`` from
    :meth:`cache_layers`) it returns ``(logits, kv_cache)``: ``input_ids
    [1, s > 1]`` + ``slot`` + scalar ``position`` + ``length`` prefills one
    chunk of one slot, of which the first ``length`` rows are real;
    ``input_ids [slots, 1]`` + ``position [slots]`` + ``active [slots]``
    runs one decode step.  ``keep`` (a traced int32 row index) cuts the
    hidden state to that one row before the final norm and the head."""

    config: ZayaConfig
    params_dtype: Any = jnp.float32
    axis_name: str = TENSOR_PARALLEL_AXIS

    def cache_layers(self) -> list:
        """What each layer keeps a slot between calls: its K/V rows, its
        convolution tail (a recurrent state with no ssm values), its
        experts' counters."""
        from apex_tpu.serving.kv_cache import (
            CallCounters,
            KVRows,
            RecurrentRows,
        )

        cfg = self.config
        return [entry for _ in range(cfg.num_hidden_layers)
                for entry in (KVRows(cfg.num_key_value_heads, cfg.head_dim),
                              RecurrentRows(ssm=(0,), conv=(cfg.tail_width,)),
                              CallCounters(MOE_COUNTERS))]

    @nn.compact
    def __call__(self, input_ids, *, kv_cache=None, position=None, slot=None,
                 length=None, active=None, keep=None):
        cfg = self.config
        if kv_cache is not None:
            s = input_ids.shape[1]
            if s == 1 and active is None:
                raise ValueError("a decode step needs active= (the lanes "
                                 "whose convolution tails may advance)")
            if s > 1 and length is None:
                raise ValueError("a prefill chunk needs length= (its real "
                                 "rows: padding must not enter a tail)")
        with component(EMBED):
            x = VocabParallelEmbedding(
                cfg.vocab_size, cfg.hidden_size,
                params_dtype=self.params_dtype, axis_name=self.axis_name,
                name="embed_tokens")(input_ids)
            x = x.transpose(1, 0, 2)                       # [s, b, h]
        carry = None
        for i in range(cfg.num_hidden_layers):
            x, carry, kv_cache = ZayaLayer(
                cfg, params_dtype=self.params_dtype, name=f"layers_{i}")(
                x, carry, kv_cache=kv_cache, layer_idx=i, position=position,
                slot=slot, length=length, active=active)
        with component(HEAD):
            if keep is not None:
                x = lax.dynamic_slice_in_dim(x, keep, 1, axis=0)
            x = FusedRMSNorm((cfg.hidden_size,), eps=cfg.rms_norm_eps,
                             param_dtype=jnp.float32, name="norm")(x)
            head = self.variables["params"]["embed_tokens"]["embedding"]
            logits = parallel_lm_logits(x, head.astype(x.dtype),
                                        self.axis_name)
        return logits if kv_cache is None else (logits, kv_cache)
