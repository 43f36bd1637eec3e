"""Mellum causal LM (``model_type`` ``mellum``): a pre-norm decoder whose
attention is grouped-query in every layer and of two kinds chosen by
``layer_types`` - a ``sliding_attention`` layer attends the last
``sliding_window`` positions under plain rope, a ``full_attention`` layer
every earlier position under YaRN-scaled rope - and whose MLP is, in every
layer, softmax-routed gated experts without a shared expert
(:class:`apex_tpu.transformer.moe.GatedMoE`).  The equations are written out
in ``benchmark/reference/mellum.py``, the plain float32 forward this module
is tested against.

Serving contract: :class:`MellumForCausalLM` takes
:class:`~apex_tpu.models.dots3.Dots3NoteForCausalLM`'s cached call
(``input_ids``, ``kv_cache=``, ``position=``, ``slot=``, ``length=``,
``active=``, returning ``(logits, cache)``).  :meth:`cache_layers` declares
what each sublayer keeps a slot - K/V rows for a full layer, a ring of the
window's K/V rows for a sliding one, call counters for the experts - and
``DecodeEngine`` builds one cache from that: a sliding layer keeps and reads
at most its window, whatever ``max_len`` is.  A full layer calls the seam
every K/V model calls (``serving.kv_cache.decode_attend`` /
``prefill_attend``), a sliding layer its window pair
(``window_decode_attend`` / ``window_prefill_attend``).

Not built: the multi-token-prediction head the model card describes (the
config has no key for it and it adds nothing to these logits), dense MLP
layers (``mlp_layer_types`` lists none), tensor parallelism, a window in the
uncached path's kernel (it masks ``[s, s]`` scores: the tests' path), a
backward pass anybody has checked.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.normalization import FusedRMSNorm
from apex_tpu.obs.scopes import (
    ATTN_PROJ,
    EMBED,
    EXPERTS,
    HEAD,
    NORM,
    component,
)
from apex_tpu.ops.rope import (
    fused_apply_rotary_pos_emb_cached,
    yarn_inv_freq,
)
from apex_tpu.transformer.moe import MOE_COUNTERS, GatedMoE
from apex_tpu.transformer.parallel_state import TENSOR_PARALLEL_AXIS
from apex_tpu.transformer.tensor_parallel import (
    VocabParallelEmbedding,
    parallel_lm_logits,
)

__all__ = ["MellumConfig", "MellumForCausalLM", "RopeParameters"]

FULL, WINDOW = "full_attention", "sliding_attention"


@dataclasses.dataclass(frozen=True)
class RopeParameters:
    """One block of the published ``rope_parameters``, its keys under their
    own names: ``rope_type`` ``default`` reads ``rope_theta`` alone,
    ``yarn`` all of them."""

    rope_type: str = "default"
    rope_theta: float = 5e5
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def __post_init__(self):
        if self.rope_type not in ("default", "yarn"):
            raise ValueError(f"rope_type {self.rope_type!r}: 'default' or "
                             f"'yarn'")

    def table(self, dim: int):
        """``(inv_freq [dim // 2] float32, factor)``: a position's angles
        are ``position x inv_freq``, and cos and sin are multiplied by
        ``factor`` (so a layer's scores carry its square)."""
        if self.rope_type == "default":
            pair = jnp.arange(0, dim, 2, dtype=jnp.float32)
            return 1.0 / self.rope_theta ** (pair / dim), 1.0
        return yarn_inv_freq(
            dim, self.rope_theta, factor=self.factor,
            original_max_position_embeddings=(
                self.original_max_position_embeddings),
            beta_fast=self.beta_fast,
            beta_slow=self.beta_slow), self.attention_factor


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    """The published keys the forward reads, under their own names
    (``rope_parameters``' two blocks as :class:`RopeParameters`).
    ``num_experts`` is the router's width; ``experts_held`` is the ``(start,
    count)`` of them this chip holds."""

    vocab_size: int = 98304
    hidden_size: int = 2304
    layer_types: Tuple[str, ...] = (WINDOW, WINDOW, WINDOW, FULL)
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    full_attention_rope: RopeParameters = RopeParameters(
        "yarn", 5e5, 16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    sliding_attention_rope: RopeParameters = RopeParameters()
    num_experts: int = 64
    experts_held: Tuple[int, int] = (0, 64)
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    rms_norm_eps: float = 1e-6

    def __post_init__(self):
        bad = set(self.layer_types) - {FULL, WINDOW}
        if bad or not self.layer_types:
            raise ValueError(
                f"layer_types {self.layer_types!r}: a non-empty sequence of "
                f"{FULL!r} and {WINDOW!r}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not group over "
                f"{self.num_key_value_heads} KV heads")

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)

    def index_among(self, layer: int) -> int:
        """Layer ``layer``'s index among the layers whose attention is of
        its kind: its row on the leading axis of that kind's rows."""
        return self.layer_types[:layer].count(self.layer_types[layer])

    def rope(self, kind: str) -> RopeParameters:
        return (self.full_attention_rope if kind == FULL
                else self.sliding_attention_rope)


def _dense(features, x, params_dtype, name):
    return nn.Dense(features, use_bias=False, dtype=x.dtype,
                    param_dtype=params_dtype,
                    kernel_init=nn.initializers.normal(0.02), name=name)(x)


def _rope(t, rope: RopeParameters, position):
    """Rotate all channels of ``t [s, b, h, d]`` (rotate-half) at positions
    ``position .. position + s`` (a scalar, or one a lane)."""
    inv, factor = rope.table(t.shape[-1])
    offset = jnp.asarray(0 if position is None else position, jnp.float32)
    at = jnp.arange(t.shape[0], dtype=jnp.float32)[:, None] + offset.reshape(
        1, -1)                                             # [s, 1 | b]
    angles = at[..., None] * inv
    angles = jnp.concatenate([angles, angles], axis=-1)[:, :, None]
    return fused_apply_rotary_pos_emb_cached(
        t, factor * jnp.cos(angles), factor * jnp.sin(angles))


class MellumAttention(nn.Module):
    """Grouped-query attention of one ``kind``::

        q = x W_q -> heads x head_dim;  k, v = x W_k, x W_v -> kv_heads x head_dim
        rope on q and k, all channels, by the kind's rope_parameters
        o_j = softmax_s(q_j . k_{j // rep, s} / sqrt(head_dim)) v_{j // rep, s}
        y = concat_j(o_j) W_o

    over the keys ``s <= t`` in a ``full_attention`` layer and ``t -
    sliding_window < s <= t`` in a ``sliding_attention`` layer.  A slot keeps
    rotated K and V a token: every row in a full layer, a ring of the
    window's in a sliding one."""

    config: MellumConfig
    kind: str
    params_dtype: Any = jnp.float32

    @nn.compact
    @component(ATTN_PROJ)
    def __call__(self, x, *, kv_cache=None, layer_idx=None, position=None,
                 slot=None, length=None):
        cfg = self.config
        heads, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                          cfg.head_dim)
        s, b, hidden = x.shape
        dt = self.params_dtype
        rope = cfg.rope(self.kind)
        q = _rope(_dense(heads * hd, x, dt, "q_proj").reshape(
            s, b, heads, hd), rope, position)
        k = _rope(_dense(nkv * hd, x, dt, "k_proj").reshape(s, b, nkv, hd),
                  rope, position)
        v = _dense(nkv * hd, x, dt, "v_proj").reshape(s, b, nkv, hd)

        if kv_cache is None:
            ctx = self._uncached(q, k, v)
        else:
            # imported here: the uncached forward needs no serving
            from apex_tpu.serving.kv_cache import (
                decode_attend,
                prefill_attend,
                window_decode_attend,
                window_prefill_attend,
            )

            window = cfg.sliding_window
            if s == 1 and self.kind == FULL:
                ctx, kv_cache = decode_attend(kv_cache, layer_idx, q, k, v,
                                              position)
            elif s == 1:
                ctx, kv_cache = window_decode_attend(
                    kv_cache, layer_idx, q, k, v, position, window=window)
            else:
                offset = jnp.asarray(0 if position is None else position,
                                     jnp.int32)
                if self.kind == FULL:
                    ctx, kv_cache = prefill_attend(
                        kv_cache, layer_idx, slot, q, k, v, offset)
                else:
                    ctx, kv_cache = window_prefill_attend(
                        kv_cache, layer_idx, slot, q, k, v, offset, length,
                        window=window)
        ctx = ctx.transpose(2, 0, 1, 3).reshape(s, b, heads * hd)
        return _dense(hidden, ctx, dt, "o_proj"), kv_cache

    def _uncached(self, q, k, v):
        """The whole sequence at once in plain ``jax.numpy``: K and V
        repeated to the query heads, the window a mask on ``[s, s]``
        scores.  The tests' path.  Returns ``[b, heads, s, hd]``."""
        s, rep = q.shape[0], q.shape[2] // k.shape[2]
        at = jnp.arange(s)
        mask = at[None] <= at[:, None]
        if self.kind == WINDOW:
            mask &= at[None] > at[:, None] - self.config.sliding_window
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        sc = jnp.einsum("tbhd,sbhd->bhts", q, k,
                        preferred_element_type=jnp.float32
                        ) * q.shape[-1] ** -0.5
        probs = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), -1)
        return jnp.einsum("bhts,sbhd->bhtd", probs.astype(v.dtype), v,
                          preferred_element_type=jnp.float32).astype(q.dtype)


class MellumLayer(nn.Module):
    """``x + attn(norm(x))`` then ``x + experts(norm(x))``."""

    config: MellumConfig
    layer: int
    params_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, *, kv_cache=None, position=None, slot=None,
                 length=None, active=None):
        cfg, i = self.config, self.layer

        def norm(t, name):
            with component(NORM):
                return FusedRMSNorm((cfg.hidden_size,), eps=cfg.rms_norm_eps,
                                    param_dtype=jnp.float32, name=name)(t)

        out, kv_cache = MellumAttention(
            cfg, cfg.layer_types[i], params_dtype=self.params_dtype,
            name="self_attn")(
            norm(x, "input_layernorm"), kv_cache=kv_cache,
            layer_idx=cfg.index_among(i), position=position, slot=slot,
            length=length)
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
        out, counts = GatedMoE(
            num_experts=cfg.num_experts, experts_held=cfg.experts_held,
            top_k=cfg.num_experts_per_tok, hidden_size=cfg.hidden_size,
            expert_width=cfg.moe_intermediate_size, shared_width=0,
            scoring="softmax", param_dtype=self.params_dtype, name="mlp")(
            h.reshape(s * lanes, -1), valid)
        if decode:
            from apex_tpu.serving.kv_cache import add_counts

            kv_cache = add_counts(kv_cache, i, counts)
        with component(EXPERTS):
            return x + out.reshape(s, lanes, -1).astype(x.dtype), kv_cache


class MellumForCausalLM(nn.Module):
    """Embedding -> the layers -> final RMSNorm -> untied head.

    ``__call__(input_ids [b, s])`` returns logits ``[s, b, vocab]``.  With
    ``kv_cache`` (built by ``DecodeEngine`` from :meth:`cache_layers`) it
    returns ``(logits, kv_cache)``: ``input_ids [1, s > 1]`` + ``slot`` +
    scalar ``position`` + ``length`` prefills one chunk of one slot, of
    which the first ``length`` rows are real; ``input_ids [slots, 1]`` +
    ``position [slots]`` + ``active [slots]`` runs one decode step."""

    config: MellumConfig
    params_dtype: Any = jnp.float32
    axis_name: str = TENSOR_PARALLEL_AXIS

    def cache_layers(self) -> list:
        """What each sublayer keeps a slot between calls: a layer's
        attention, then its experts."""
        from apex_tpu.serving.kv_cache import (
            CallCounters,
            KVRows,
            KVWindowRows,
        )

        cfg = self.config
        shape = (cfg.num_key_value_heads, cfg.head_dim)
        out = []
        for kind in cfg.layer_types:
            out.append(KVRows(*shape) if kind == FULL
                       else KVWindowRows(*shape, cfg.sliding_window))
            out.append(CallCounters(MOE_COUNTERS))
        return out

    @nn.compact
    def __call__(self, input_ids, *, kv_cache=None, position=None, slot=None,
                 length=None, active=None):
        cfg = self.config
        if kv_cache is not None:
            s = input_ids.shape[1]
            if s == 1 and active is None:
                raise ValueError("a decode step needs active= (the lanes "
                                 "whose tokens the experts count)")
            if s > 1 and length is None:
                raise ValueError("a prefill chunk needs length= (its real "
                                 "rows: a window ring keeps no padding)")
        with component(EMBED):
            x = VocabParallelEmbedding(
                cfg.vocab_size, cfg.hidden_size,
                params_dtype=self.params_dtype, axis_name=self.axis_name,
                name="embed_tokens")(input_ids)
            x = x.transpose(1, 0, 2)                       # [s, b, h]
        for i in range(cfg.num_hidden_layers):
            x, kv_cache = MellumLayer(
                cfg, i, params_dtype=self.params_dtype, name=f"layers_{i}")(
                x, kv_cache=kv_cache, position=position, slot=slot,
                length=length, active=active)
        with component(HEAD):
            x = FusedRMSNorm((cfg.hidden_size,), eps=cfg.rms_norm_eps,
                             param_dtype=jnp.float32, name="norm")(x)
            head = self.param("lm_head", nn.initializers.normal(0.02),
                              (cfg.vocab_size, cfg.hidden_size),
                              self.params_dtype)
            logits = parallel_lm_logits(x, head.astype(x.dtype),
                                        self.axis_name)
        return logits if kv_cache is None else (logits, kv_cache)
