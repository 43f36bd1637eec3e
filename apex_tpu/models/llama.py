"""Llama-family causal LM, TPU-first.

Parity target: the BASELINE.md flagship row "Llama-2 7B (TP x PP, RMSNorm +
multi-tensor Adam)" — the reference trains Llama-class models through its
kernel toolbox (fused RMSNorm, fused rope, flash attention); this module is
the same composition over apex_tpu's kernels:

- :class:`~apex_tpu.normalization.FusedRMSNorm` (Pallas RMS kernels)
- :func:`~apex_tpu.ops.rope.fused_apply_rotary_pos_emb` (HF/GPT-NeoX
  rotate-half convention, configurable theta)
- :func:`~apex_tpu.ops.flash_attention.flash_attention` with grouped-query
  attention (kv heads broadcast to query heads)
- SwiGLU MLP over Column/RowParallelLinear (tp-shardable, SP-aware)
- :func:`~apex_tpu.ops.fused_lm_head.fused_lm_head_loss` for the
  single-shard training loss; tp keeps vocab-parallel CE.

Numerics are pinned against ``transformers.LlamaForCausalLM`` (torch CPU
oracle) in ``tests/test_llama.py`` — same weights, same logits.

Layout: activations are [s, b, h] (Megatron layout, SP shards dim 0);
inputs are [b, s] token ids.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn

from apex_tpu.normalization import FusedRMSNorm
from apex_tpu.obs.scopes import ATTN_PROJ, EMBED, HEAD, MLP, NORM, component
from apex_tpu.ops.flash_attention import flash_attention
from apex_tpu.ops.rope import fused_apply_rotary_pos_emb
from apex_tpu.transformer.parallel_state import TENSOR_PARALLEL_AXIS
from apex_tpu.transformer.tensor_parallel import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
    parallel_lm_logits,
    shard_init,
    tp_world_size,
    vocab_parallel_cross_entropy,
)
from apex_tpu.transformer.tensor_parallel.utils import divide

__all__ = ["LlamaConfig", "LlamaForCausalLM", "tp_param_spec",
           "validate_tp_divisibility"]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    """Llama-2/3 architecture knobs (HF LlamaConfig field names)."""

    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: Optional[int] = None   # None = MHA; < heads = GQA
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False

    @property
    def kv_heads(self) -> int:
        return self.num_key_value_heads or self.num_attention_heads

    @classmethod
    def llama2_7b(cls) -> "LlamaConfig":
        """Llama-2 7B: 32 x 4096, MHA, 32k vocab (the dataclass defaults)."""
        return cls()

    @classmethod
    def llama3_8b(cls) -> "LlamaConfig":
        """Llama-3 8B: GQA 8 kv heads, 128k vocab, theta 5e5, 8k context."""
        return cls(vocab_size=128256, intermediate_size=14336,
                   num_key_value_heads=8, rope_theta=500000.0,
                   max_position_embeddings=8192)


# which flax param leaves the tensor_parallel layers shard, by module
# name — the model owns this layout knowledge (engine/weights derive
# their NamedShardings from it instead of re-guessing the Megatron
# column/row split from shapes)
_TP_COLUMN_MODULES = ("q_proj", "k_proj", "v_proj", "gate_proj",
                      "up_proj")
_TP_ROW_MODULES = ("o_proj", "down_proj")


def tp_param_spec(path, axis_name: str = TENSOR_PARALLEL_AXIS):
    """``PartitionSpec`` for one Llama param leaf under a 1-D tp mesh.

    ``path`` is a ``jax.tree_util`` key path (or its ``keystr`` string)
    of a leaf of the flax params tree.  The mapping mirrors what the
    tensor_parallel layers build per rank:

    - ``embed_tokens.embedding`` and ``lm_head``: ``[vocab/tp, h]``
      (vocab-parallel) -> ``P(axis, None)``;
    - Column-parallel kernels (q/k/v/gate/up): ``[in, out/tp]`` ->
      ``P(None, axis)``; their biases ``[out/tp]`` -> ``P(axis)``;
    - Row-parallel kernels (o_proj/down_proj): ``[in/tp, out]`` ->
      ``P(axis, None)``; their biases are added after the psum,
      replicated -> ``P()``;
    - everything else (norm scales): replicated -> ``P()``.

    Serving uses this to lay params out on the decode engine's mesh
    (:class:`apex_tpu.serving.engine.DecodeEngine` with ``tp=``) and to
    restore checkpoints directly onto it
    (:func:`apex_tpu.serving.weights.load_serving_params`).
    """
    from jax.sharding import PartitionSpec as P

    ks = path if isinstance(path, str) else jax.tree_util.keystr(path)
    if "embedding" in ks or "lm_head" in ks:
        return P(axis_name, None)
    column = any(m in ks for m in _TP_COLUMN_MODULES)
    row = any(m in ks for m in _TP_ROW_MODULES)
    if "kernel" in ks:
        if column:
            return P(None, axis_name)
        if row:
            return P(axis_name, None)
    if "bias" in ks and column:
        return P(axis_name)
    return P()


def validate_tp_divisibility(config: "LlamaConfig", tp: int) -> None:
    """Raise ``ValueError`` unless every tp-sharded dimension divides by
    ``tp`` — attention heads and kv heads (head-wise KV-cache shard),
    vocab (embedding + lm_head), and the MLP intermediate width."""
    tp = int(tp)
    for what, dim in (("num_attention_heads", config.num_attention_heads),
                      ("kv_heads", config.kv_heads),
                      ("vocab_size", config.vocab_size),
                      ("intermediate_size", config.intermediate_size)):
        if dim % tp:
            raise ValueError(
                f"{what}={dim} is not divisible by tp={tp} — every "
                f"tensor-parallel shard must be equal-sized (heads, kv "
                f"heads, vocab rows, and MLP intermediate columns are "
                f"the sharded dimensions)")


def _rope_freqs(s: int, dim: int, theta: float, offset=0) -> jax.Array:
    """Rotary frequencies for ``s`` positions starting at ``offset``.

    A scalar ``offset`` (the training path, and single-stream decode)
    yields ``[s, 1, 1, d]``.  A vector ``offset`` of shape ``[b]`` — one
    start position per batch element, the batched-decode case where
    every KV-cache slot sits at its own depth — yields ``[s, b, 1, d]``,
    which broadcasts against ``[s, b, h, d]`` activations identically.
    Position ``p``'s row is ``p * inv`` in both forms, so decoding token
    ``p`` through the vector path is bit-identical to the full-sequence
    training freqs at row ``p``.
    """
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    if isinstance(offset, jax.Array) and offset.ndim:
        t = (jnp.arange(s, dtype=jnp.float32)[:, None]
             + offset.astype(jnp.float32)[None, :])        # [s, b]
        f = t[..., None] * inv
        return jnp.concatenate([f, f], axis=-1)[:, :, None, :]  # [s,b,1,d]
    t = jnp.arange(s, dtype=jnp.float32) + offset
    f = jnp.outer(t, inv)
    return jnp.concatenate([f, f], axis=-1)[:, None, None, :]  # [s,1,1,d]


class LlamaMLP(nn.Module):
    """SwiGLU: down( silu(gate(x)) * up(x) )."""

    config: LlamaConfig
    sequence_parallel_enabled: bool = False
    params_dtype: Any = jnp.float32
    axis_name: str = TENSOR_PARALLEL_AXIS

    @nn.compact
    @component(MLP)
    def __call__(self, x):
        cfg = self.config
        common = dict(sequence_parallel_enabled=self.sequence_parallel_enabled,
                      params_dtype=self.params_dtype,
                      axis_name=self.axis_name, use_bias=False)
        gate = ColumnParallelLinear(cfg.hidden_size, cfg.intermediate_size,
                                    gather_output=False, name="gate_proj",
                                    **common)(x)
        up = ColumnParallelLinear(cfg.hidden_size, cfg.intermediate_size,
                                  gather_output=False, name="up_proj",
                                  **common)(x)
        h = jax.nn.silu(gate) * up
        return RowParallelLinear(cfg.intermediate_size, cfg.hidden_size,
                                 input_is_parallel=True, name="down_proj",
                                 **common)(h)


class LlamaAttention(nn.Module):
    """Grouped-query attention with rotary embeddings.

    Query head ``j`` reads KV head ``j // (heads // kv_heads)`` (the GQA
    share pattern).  The uncached (training) branch repeats the KV heads
    to the query-head count before the flash kernel; the two cached
    (serving) branches do not — the cache's read
    (:func:`apex_tpu.serving.kv_cache.cached_attention`) groups the query
    heads over their KV head and reads the cache as it is stored.
    With tp, both q heads and kv heads shard over the axis, so
    ``kv_heads % tp == 0`` is required (the group size is unchanged)."""

    config: LlamaConfig
    sequence_parallel_enabled: bool = False
    params_dtype: Any = jnp.float32
    axis_name: str = TENSOR_PARALLEL_AXIS

    @nn.compact
    @component(ATTN_PROJ)
    def __call__(self, x, deterministic: bool = True, *, kv_cache=None,
                 layer_idx: Optional[int] = None, position=None, slot=None):
        """Causal self-attention; optionally reading/writing a KV cache.

        Without ``kv_cache`` this is the training path, unchanged.  With
        one, two serving modes, each one call into
        :mod:`apex_tpu.serving.kv_cache` (``prefill_attend`` /
        ``decode_attend``: write, view, cast, masked grouped read, for
        whatever layout and storage format the cache has):

        - **chunked prefill** (``s > 1``): ``position`` is a scalar
          offset — the number of tokens already cached in ``slot``
          (``None`` means 0, a fresh prompt).  Rope is applied at the
          true positions ``offset..offset+s``, the chunk's K/V are
          written into ``kv_cache`` at ``(layer_idx, slot, offset..)``,
          and the chunk's causal block attends the full ``max_len``
          cache under per-row bounds (``idx <= offset + row``) — so a
          chunk reads every previously cached token through the same
          masked, fixed-extent, grouped read decode uses
          (``cached_attention``: the slot's ``[max_len, kv_heads,
          hd]`` rows as stored, operands in the cache's dtype, float32
          accumulation and softmax), and chunk logits are the same bits
          no matter how the prompt is split — for a float32 cache the
          bits of the shape-stable uncached forward (context padded to
          ``max_len``), for a bf16 cache the flash kernel's arithmetic.
          This mode also carries **speculative verification**
          (``DecodeEngine.verify_draft``): the per-ROW logits it
          returns are each bit-identical to the single-token decode
          logits at that depth (same reduction extents), so comparing
          row ``i``'s argmax against a drafted token ``i+1`` is an
          *exact* accept/reject test — speculation changes scheduling,
          never a bit of the emitted stream.
        - **decode** (``s == 1``): ``position`` is a ``[b]`` vector of
          per-slot depths; rope is applied at the true position, the new
          K/V are appended at ``position``, and attention reads the full
          ``max_len`` cache under a length mask — one static shape for
          every decode step (no recompiles after warmup).  The read is
          the layer's ``[slots, max_len, kv_heads, hd]`` buffer itself:
          no head-repeated, transposed or float32 copy of it is built.

        Returns ``out`` (training) or ``(out, kv_cache)`` (serving).
        """
        cfg = self.config
        world = tp_world_size(self.axis_name)
        hd = cfg.hidden_size // cfg.num_attention_heads
        nq = cfg.num_attention_heads // world
        nkv = cfg.kv_heads // world
        common = dict(sequence_parallel_enabled=self.sequence_parallel_enabled,
                      params_dtype=self.params_dtype,
                      axis_name=self.axis_name, use_bias=False,
                      gather_output=False)
        q = ColumnParallelLinear(cfg.hidden_size, cfg.hidden_size,
                                 name="q_proj", **common)(x)
        k = ColumnParallelLinear(cfg.hidden_size, cfg.kv_heads * hd,
                                 name="k_proj", **common)(x)
        v = ColumnParallelLinear(cfg.hidden_size, cfg.kv_heads * hd,
                                 name="v_proj", **common)(x)
        s, b = q.shape[0], q.shape[1]
        q = q.reshape(s, b, nq, hd)
        k = k.reshape(s, b, nkv, hd)
        v = v.reshape(s, b, nkv, hd)

        decode = kv_cache is not None and s == 1
        if decode:
            # rope at each slot's true depth ([b]-vector offset)
            freqs = _rope_freqs(s, hd, cfg.rope_theta,
                                offset=jnp.asarray(position))
        elif kv_cache is not None:
            # chunked prefill: rope at offset..offset+s (scalar offset;
            # 0 == a fresh prompt's first chunk)
            offset = jnp.asarray(0 if position is None else position,
                                 jnp.int32)
            freqs = _rope_freqs(s, hd, cfg.rope_theta, offset=offset)
        else:
            freqs = _rope_freqs(s, hd, cfg.rope_theta)
        q = fused_apply_rotary_pos_emb(q, freqs)
        k = fused_apply_rotary_pos_emb(k, freqs)

        if kv_cache is None:
            # GQA: each kv head serves nq/nkv query heads
            if nkv != nq:
                rep = nq // nkv
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)

            qt = q.transpose(1, 2, 0, 3)     # [b, nq, s, hd]
            kt = k.transpose(1, 2, 0, 3)
            vt = v.transpose(1, 2, 0, 3)
            ctx = flash_attention(qt, kt, vt, causal=True)
        else:
            # imported here: training imports this module without serving
            from apex_tpu.serving.kv_cache import decode_attend, prefill_attend

            if decode:
                ctx, kv_cache = decode_attend(kv_cache, layer_idx, q, k, v,
                                              position)
            else:
                ctx, kv_cache = prefill_attend(kv_cache, layer_idx, slot, q,
                                               k, v, offset)
        ctx = ctx.transpose(2, 0, 1, 3).reshape(s, b, nq * hd)
        out = RowParallelLinear(cfg.hidden_size, cfg.hidden_size,
                                input_is_parallel=True,
                                sequence_parallel_enabled=self.sequence_parallel_enabled,
                                params_dtype=self.params_dtype,
                                axis_name=self.axis_name, use_bias=False,
                                name="o_proj")(ctx)
        if kv_cache is not None:
            return out, kv_cache
        return out


class LlamaDecoderLayer(nn.Module):
    config: LlamaConfig
    sequence_parallel_enabled: bool = False
    params_dtype: Any = jnp.float32
    axis_name: str = TENSOR_PARALLEL_AXIS

    @nn.compact
    def __call__(self, x, deterministic: bool = True, *, kv_cache=None,
                 layer_idx: Optional[int] = None, position=None, slot=None):
        cfg = self.config
        with component(NORM):
            h = FusedRMSNorm((cfg.hidden_size,), eps=cfg.rms_norm_eps,
                             param_dtype=self.params_dtype,
                             name="input_layernorm")(x)
        attn = LlamaAttention(
            cfg, sequence_parallel_enabled=self.sequence_parallel_enabled,
            params_dtype=self.params_dtype, axis_name=self.axis_name,
            name="self_attn")
        if kv_cache is not None:
            a, kv_cache = attn(h, deterministic, kv_cache=kv_cache,
                               layer_idx=layer_idx, position=position,
                               slot=slot)
        else:
            a = attn(h, deterministic)
        # a residual add is the root of the fusion XLA makes of it and the
        # product before it: it counts with the branch it closes
        with component(ATTN_PROJ):
            x = x + a
        with component(NORM):
            h = FusedRMSNorm((cfg.hidden_size,), eps=cfg.rms_norm_eps,
                             param_dtype=self.params_dtype,
                             name="post_attention_layernorm")(x)
        with component(MLP):
            out = x + LlamaMLP(
                cfg,
                sequence_parallel_enabled=self.sequence_parallel_enabled,
                params_dtype=self.params_dtype, axis_name=self.axis_name,
                name="mlp")(h)
        if kv_cache is not None:
            return out, kv_cache
        return out


class LlamaForCausalLM(nn.Module):
    """Embedding -> decoder stack -> final RMSNorm -> LM head.

    ``__call__(input_ids)`` returns logits [s, b, vocab/tp];
    ``__call__(input_ids, labels=...)`` returns per-token loss [b, s]
    (fused head kernel on a single shard, vocab-parallel CE under tp)."""

    config: LlamaConfig
    activations_checkpoint: bool = False
    sequence_parallel_enabled: bool = False
    params_dtype: Any = jnp.float32
    axis_name: str = TENSOR_PARALLEL_AXIS

    def cache_layers(self) -> list:
        """What each layer keeps a slot between calls, in layer order:
        K/V rows, every layer alike."""
        from apex_tpu.serving.kv_cache import KVRows

        cfg = self.config
        return [KVRows(cfg.kv_heads,
                       cfg.hidden_size // cfg.num_attention_heads)
                ] * cfg.num_hidden_layers

    @nn.compact
    def __call__(self, input_ids, labels=None, deterministic: bool = True,
                 *, kv_cache=None, position=None, slot=None, length=None,
                 active=None):
        """Forward pass; optionally in KV-cached serving mode.

        With ``kv_cache`` (what ``DecodeEngine`` builds from
        :meth:`cache_layers`) the call returns ``(logits, kv_cache)`` instead of logits/loss:
        ``input_ids [1, s>1]`` + ``slot`` (+ scalar ``position`` = the
        chunk's start offset, 0/None for a fresh prompt) prefills one
        chunk of one slot — the serving engine slices the last real
        row's logits for prefill and keeps EVERY row for speculative
        verification — and ``input_ids [slots, 1]`` + ``position
        [slots]`` runs one batched decode step (see
        :class:`apex_tpu.serving.engine.DecodeEngine`).  ``labels``
        is a training-only argument and rejected in serving mode.  The
        default (``kv_cache=None``) path is unchanged.  ``length`` (a
        chunk's real rows) and ``active`` (a decode step's live lanes)
        are what ``DecodeEngine`` tells every model; K/V rows are hidden
        after the fact by the slot lengths, so this one ignores both.
        """
        del length, active
        cfg = self.config
        if kv_cache is not None and labels is not None:
            raise ValueError("kv_cache is a serving-mode argument; "
                             "labels is training-only")
        with component(EMBED):
            x = VocabParallelEmbedding(
                cfg.vocab_size, cfg.hidden_size,
                params_dtype=self.params_dtype, axis_name=self.axis_name,
                name="embed_tokens")(input_ids)
            x = x.transpose(1, 0, 2)  # [s, b, h]
        if self.sequence_parallel_enabled:
            from apex_tpu.transformer.tensor_parallel import (
                scatter_to_sequence_parallel_region,
            )

            x = scatter_to_sequence_parallel_region(x, self.axis_name)

        # serving always uses the plain layer: activation recompute is a
        # training-memory lever (nothing to recompute at inference), and
        # remat's static_argnums contract doesn't cover the cache kwargs
        layer_cls = (nn.remat(LlamaDecoderLayer, static_argnums=(2,))
                     if self.activations_checkpoint and kv_cache is None
                     else LlamaDecoderLayer)
        for i in range(cfg.num_hidden_layers):
            layer = layer_cls(
                cfg, sequence_parallel_enabled=self.sequence_parallel_enabled,
                params_dtype=self.params_dtype, axis_name=self.axis_name,
                name=f"layers_{i}")
            if kv_cache is not None:
                x, kv_cache = layer(x, deterministic, kv_cache=kv_cache,
                                    layer_idx=i, position=position,
                                    slot=slot)
            else:
                x = layer(x, deterministic)
        with component(HEAD):
            x = FusedRMSNorm((cfg.hidden_size,), eps=cfg.rms_norm_eps,
                             param_dtype=self.params_dtype, name="norm")(x)

            if cfg.tie_word_embeddings:
                head = self.variables["params"]["embed_tokens"]["embedding"]
            else:
                # vocab-sharded like the embedding table ([vocab/tp, h]
                # per rank)
                head = self.param(
                    "lm_head",
                    shard_init(nn.initializers.normal(0.02), self.axis_name),
                    (divide(cfg.vocab_size, tp_world_size(self.axis_name)),
                     cfg.hidden_size), self.params_dtype)

            if (labels is not None and tp_world_size(self.axis_name) == 1
                    and not self.sequence_parallel_enabled):
                from apex_tpu.ops.fused_lm_head import fused_lm_head_loss

                loss = fused_lm_head_loss(x, head.astype(x.dtype), labels.T)
                return loss.T
            logits = parallel_lm_logits(
                x, head.astype(x.dtype), self.axis_name,
                sequence_parallel_enabled=self.sequence_parallel_enabled)
        if kv_cache is not None:
            return logits, kv_cache
        if labels is None:
            return logits
        return vocab_parallel_cross_entropy(
            logits.transpose(1, 0, 2), labels, axis_name=self.axis_name)
