"""dots3-note causal LM (``model_type`` ``dots3_note``): a pre-norm decoder
whose attention is *latent* in every layer - K and V of all heads are
expanded from one compressed row a token, beside one rope key all heads
share - and of two kinds chosen by ``layer_types``: a ``full_attention``
layer scores every earlier token with a small learned selector and attends
the ``index_topk`` best, a ``sliding_attention`` layer (its own ranks and
head widths, the ``swa_`` keys) attends the last ``sliding_window_size``
positions.  Both gate each head's output by a sigmoid of the layer's input.
The first ``first_k_dense_replace`` layers have a SwiGLU MLP, the others
sigmoid-routed gated experts beside a shared expert
(:class:`apex_tpu.transformer.moe.GatedMoE`).  The equations are written
out in ``benchmark/reference/dots3.py``, the plain float32 forward this
module is tested against.

Serving contract: :class:`Dots3NoteForCausalLM` takes
:class:`~apex_tpu.models.nemotron_h.NemotronHForCausalLM`'s cached call
(``input_ids``, ``kv_cache=``, ``position=``, ``slot=``, ``length=``,
``active=``, returning ``(logits, cache)``).  :meth:`cache_layers` declares
what each sublayer keeps a slot - latent rows with selector keys, a ring of
window rows, call counters - and ``DecodeEngine`` builds the cache from
that.

- A decode step reads in the *absorbed* form: the query is taken through
  ``W_kvb``'s key half once (``q' = q_nope W_kvb[k]^T``) and scored against
  the stored rows themselves, and ``W_kvb``'s value half is applied to the
  ``p . c_kv`` sum - a head's K and V are never expanded for a cached row.
  A full layer gathers the rows its selector chose, a window layer the
  window's (``serving.kv_cache.latent_decode_attend`` /
  ``ring_decode_attend``).
- A prompt chunk reads in the explicit form, a block of rows expanded at a
  time (``latent_prefill_attend`` / ``ring_prefill_attend``): per-head K and
  V are 320 wide where a stored row is 576, so many queries against one
  block cost less that way.
- Not built: the vision and audio towers and the multi-token-prediction
  module of the published model, tensor parallelism, a backward pass
  anybody has checked.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.normalization import FusedRMSNorm
from apex_tpu.obs.scopes import ATTN_PROJ, EMBED, HEAD, MLP, NORM, component
from apex_tpu.ops.rope import fused_apply_rotary_pos_emb
from apex_tpu.transformer.moe import MOE_COUNTERS, GatedMoE
from apex_tpu.transformer.parallel_state import TENSOR_PARALLEL_AXIS
from apex_tpu.transformer.tensor_parallel import (
    VocabParallelEmbedding,
    parallel_lm_logits,
)

__all__ = ["Dots3NoteConfig", "Dots3NoteForCausalLM"]

FULL, WINDOW = "full_attention", "sliding_attention"


@dataclasses.dataclass(frozen=True)
class Dots3NoteConfig:
    """The published keys the forward reads, under their own names.
    ``n_routed_experts`` is the router's width (the model's experts);
    ``experts_held`` is the ``(start, count)`` of them this chip holds."""

    vocab_size: int = 152064
    hidden_size: int = 5120
    intermediate_size: int = 13824
    layer_types: Tuple[str, ...] = (FULL, WINDOW, WINDOW, WINDOW, FULL)
    first_k_dense_replace: int = 1
    num_attention_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    swa_num_attention_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 5e4
    sliding_window_size: int = 513
    n_routed_experts: int = 256
    experts_held: Tuple[int, int] = (0, 256)
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 1536
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    apply_mla_qkv_lora_rescale: bool = True

    def __post_init__(self):
        bad = set(self.layer_types) - {FULL, WINDOW}
        if bad or not self.layer_types:
            raise ValueError(
                f"layer_types {self.layer_types!r}: a non-empty sequence of "
                f"{FULL!r} and {WINDOW!r}")

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)

    def index_among(self, layer: int) -> int:
        """Layer ``layer``'s index among the layers whose attention is of
        its kind: its row on the leading axis of that kind's rows."""
        return self.layer_types[:layer].count(self.layer_types[layer])

    def expert_index(self, layer: int) -> int:
        """Its index among the layers with routed experts."""
        return layer - self.first_k_dense_replace

    def attention(self, kind: str) -> dict:
        """The sizes of one kind of attention layer, under one set of
        names: the ``swa_`` keys for a window layer."""
        pre = "swa_" if kind == WINDOW else ""
        get = lambda key: getattr(self, pre + key)   # noqa: E731
        return dict(
            heads=get("num_attention_heads"), q_rank=get("q_lora_rank"),
            rank=get("kv_lora_rank"), nope=get("qk_nope_head_dim"),
            rope=get("qk_rope_head_dim"), v=get("v_head_dim"),
            theta=get("rope_theta"))


def _dense(features, x, params_dtype, name):
    return nn.Dense(features, use_bias=False, dtype=x.dtype,
                    param_dtype=params_dtype,
                    kernel_init=nn.initializers.normal(0.02), name=name)(x)


def _rope(t, theta: float, position, width: int):
    """Rotate the first ``width`` channels of ``t [s, b, h, d]`` at
    positions ``position .. position + s`` (a scalar, or one a lane)."""
    offset = jnp.asarray(0 if position is None else position, jnp.float32)
    at = jnp.arange(t.shape[0], dtype=jnp.float32)[:, None] + offset.reshape(
        1, -1)                                             # [s, 1 | b]
    inv = 1.0 / theta ** (jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    angles = at[..., None] * inv
    return fused_apply_rotary_pos_emb(
        t, jnp.concatenate([angles, angles], axis=-1)[:, :, None])


class LatentAttention(nn.Module):
    """Multi-head latent attention of one ``kind``, gated by head::

        c_q = r_q RMSNorm(x W_qa);  q_h = c_q W_qb = [nope | rope], rope on it
        [c_kv | k_r] = x W_kva;  c_kv <- r_kv RMSNorm(c_kv);  rope on k_r
        [k_h^nope | v_h] = c_kv W_kvb;  k_h = [k_h^nope | k_r]
        o_h = sigmoid(x W_g)_h softmax_s(q_h . k_{h,s} / sqrt(nope + rope)) v_s
        y = concat_h(o_h) W_o

    over the keys ``s`` the kind allows: a ``full_attention`` layer the
    ``index_topk`` largest ``I(t, s) = sum_j w_{t,j} relu(q^I_{t,j} .
    k^I_s)`` of ``s <= t`` (``q^I = c_q W^I_q`` by head, ``k^I =
    LayerNorm(x W^I_k)``, both with rope on their first ``qk_rope_head_dim``
    channels, ``w = x W^I_w``), a ``sliding_attention`` layer ``t -
    sliding_window_size < s <= t``.  A slot keeps ``[c_kv | k_r]`` a token
    (and ``k^I`` where keys are selected)."""

    config: Dots3NoteConfig
    kind: str
    params_dtype: Any = jnp.float32

    @nn.compact
    @component(ATTN_PROJ)
    def __call__(self, x, *, kv_cache=None, layer_idx=None, position=None,
                 slot=None, length=None):
        cfg = self.config
        a = cfg.attention(self.kind)
        heads, rank, nope, dr, dv = (a["heads"], a["rank"], a["nope"],
                                     a["rope"], a["v"])
        s, b, hidden = x.shape
        dt = self.params_dtype
        scale = (nope + dr) ** -0.5
        r_q = r_kv = 1.0
        if cfg.apply_mla_qkv_lora_rescale:
            r_q, r_kv = ((hidden / a["q_rank"]) ** 0.5,
                         (hidden / rank) ** 0.5)

        def norm(t, name):
            return FusedRMSNorm((t.shape[-1],), eps=cfg.rms_norm_eps,
                                param_dtype=jnp.float32, name=name)(t)

        c_q = (r_q * norm(_dense(a["q_rank"], x, dt, "q_a_proj"),
                          "q_a_norm")).astype(x.dtype)
        q = _dense(heads * (nope + dr), c_q, dt, "q_b_proj").reshape(
            s, b, heads, nope + dr)
        q_rope = _rope(q[..., nope:], a["theta"], position, dr)
        kv_a = _dense(rank + dr, x, dt, "kv_a_proj")
        c_kv = (r_kv * norm(kv_a[..., :rank], "kv_a_norm")).astype(x.dtype)
        k_r = _rope(kv_a[..., None, rank:], a["theta"], position, dr)[:, :, 0]
        rows = jnp.concatenate([c_kv, k_r], axis=-1)       # [s, b, rank + dr]
        w_kvb = self.param("kv_b_proj", nn.initializers.normal(0.02),
                           (rank, heads, nope + dv), dt).astype(x.dtype)

        def expand(stored):
            """Stored rows ``[n, rank + dr]`` to each head's K and V (the
            uncached path's)."""
            kv = jnp.einsum("nr,rhd->nhd", stored[:, :rank], w_kvb,
                            preferred_element_type=jnp.float32
                            ).astype(stored.dtype)
            k_rope = jnp.broadcast_to(stored[:, None, rank:],
                                      (stored.shape[0], heads, dr))
            return (jnp.concatenate([kv[..., :nope], k_rope], axis=-1),
                    kv[..., nope:])

        select = None
        if self.kind == FULL:
            j, d = cfg.index_n_heads, cfg.index_head_dim
            q_i = _rope(_dense(j * d, c_q, dt, "index_q_proj").reshape(
                s, b, j, d), a["theta"], position, dr)
            k_i = nn.LayerNorm(epsilon=cfg.rms_norm_eps, dtype=x.dtype,
                               param_dtype=jnp.float32, name="index_k_norm")(
                _dense(d, x, dt, "index_k_proj"))
            k_i = _rope(k_i[:, :, None], a["theta"], position, dr)[:, :, 0]
            w_i = _dense(j, x, dt, "index_w_proj").astype(jnp.float32)
            select = {"top_k": cfg.index_topk, "scale": j ** -0.5 * d ** -0.5}

        if kv_cache is None:
            ctx = self._uncached(q[..., :nope], q_rope, rows, expand, scale,
                                 None if select is None else
                                 dict(select, q=q_i, w=w_i, key=k_i))
        elif s == 1:
            from apex_tpu.serving.kv_cache import (
                latent_decode_attend,
                ring_decode_attend,
            )

            # absorbed: the query through W_kvb's key half, then against
            # the rows as stored; W_kvb's value half after the sum
            q_abs = jnp.einsum("bhd,rhd->bhr", q[0, ..., :nope],
                               w_kvb[..., :nope],
                               preferred_element_type=jnp.float32)
            q_full = jnp.concatenate([q_abs.astype(x.dtype), q_rope[0]], -1)
            if select is None:
                ctx, kv_cache = ring_decode_attend(
                    kv_cache, layer_idx, q_full, rows[0], position,
                    scale=scale, rank=rank, window=cfg.sliding_window_size)
            else:
                ctx, kv_cache = latent_decode_attend(
                    kv_cache, layer_idx, q_full, rows[0], position,
                    scale=scale, rank=rank,
                    select=dict(select, q=q_i[0], w=w_i[0], key=k_i[0]))
            ctx = jnp.einsum("bhr,rhd->bhd", ctx.astype(x.dtype),
                             w_kvb[..., nope:],
                             preferred_element_type=jnp.float32)[None]
        else:
            from apex_tpu.serving.kv_cache import (
                latent_prefill_attend,
                ring_prefill_attend,
            )

            if b != 1:
                raise ValueError(f"prefill expects one slot per call "
                                 f"(b=1), got b={b}")
            offset = jnp.asarray(0 if position is None else position,
                                 jnp.int32)
            q_full = jnp.concatenate([q[..., :nope], q_rope], -1)[:, 0]
            # what ``expand`` is made of: the seam reads through it itself
            expansion = {"w": w_kvb, "nope": nope}
            if select is None:
                ctx, kv_cache = ring_prefill_attend(
                    kv_cache, layer_idx, slot, q_full, rows[:, 0], offset,
                    length, scale=scale, expand=expansion,
                    window=cfg.sliding_window_size)
            else:
                ctx, kv_cache = latent_prefill_attend(
                    kv_cache, layer_idx, slot, q_full, rows[:, 0], offset,
                    scale=scale, expand=expansion,
                    select=dict(select, q=q_i[:, 0], w=w_i[:, 0],
                                key=k_i[:, 0]))
            ctx = ctx[:, None]                             # [s, 1, H, dv]
        gate = jax.nn.sigmoid(
            _dense(heads, x, dt, "gate_proj").astype(jnp.float32))
        ctx = (ctx * gate[..., None]).astype(x.dtype).reshape(s, b, heads * dv)
        return _dense(hidden, ctx, dt, "o_proj"), kv_cache

    def _uncached(self, q_nope, q_rope, rows, expand, scale, select):
        """The whole sequence at once in plain ``jax.numpy``: K and V
        explicit, the selection a ``top_k`` over the masked ``[s, s]``
        selector scores scattered into a mask.  The tests' path."""
        s, b = rows.shape[:2]
        at = jnp.arange(s)
        seen = at[None] <= at[:, None]
        q = jnp.concatenate([q_nope, q_rope], -1)          # [s, b, H, dk]
        out = []
        for lane in range(b):
            k, v = expand(rows[:, lane])
            if select is None:
                mask = seen & (at[None] > at[:, None]
                               - self.config.sliding_window_size)
            else:
                dots = jnp.einsum("tjd,sd->tjs", select["q"][:, lane],
                                  select["key"][:, lane],
                                  preferred_element_type=jnp.float32)
                scores = jnp.einsum("tjs,tj->ts", jax.nn.relu(dots),
                                    select["w"][:, lane]) * select["scale"]
                values, chosen = jax.lax.top_k(
                    jnp.where(seen, scores, -jnp.inf),
                    min(select["top_k"], s))
                mask = jnp.zeros((s, s), bool).at[at[:, None], chosen].set(
                    values > -jnp.inf)
            sc = jnp.einsum("thd,shd->hts", q[:, lane], k,
                            preferred_element_type=jnp.float32) * scale
            probs = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), -1)
            out.append(jnp.einsum("hts,shd->thd", probs.astype(v.dtype), v,
                                  preferred_element_type=jnp.float32))
        return jnp.stack(out, axis=1)                      # [s, b, H, dv]


class GatedMLP(nn.Module):
    """SwiGLU: ``down(silu(gate(x)) * up(x))``."""

    width: int
    params_dtype: Any = jnp.float32

    @nn.compact
    @component(MLP)
    def __call__(self, x):
        hid = (jax.nn.silu(_dense(self.width, x, self.params_dtype,
                                  "gate_proj"))
               * _dense(self.width, x, self.params_dtype, "up_proj"))
        return _dense(x.shape[-1], hid, self.params_dtype, "down_proj")


class Dots3NoteLayer(nn.Module):
    """``x + attn(norm(x))`` then ``x + mlp(norm(x))``."""

    config: Dots3NoteConfig
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

        out, kv_cache = LatentAttention(
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
        if i < cfg.first_k_dense_replace:
            with component(MLP):
                return x + GatedMLP(
                    cfg.intermediate_size, self.params_dtype,
                    name="mlp")(h).astype(x.dtype), kv_cache
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
            num_experts=cfg.n_routed_experts, experts_held=cfg.experts_held,
            top_k=cfg.num_experts_per_tok, hidden_size=cfg.hidden_size,
            expert_width=cfg.moe_intermediate_size,
            shared_width=cfg.moe_intermediate_size * cfg.n_shared_experts,
            routed_scaling_factor=cfg.routed_scaling_factor,
            param_dtype=self.params_dtype, name="mlp")(
            h.reshape(s * lanes, -1), valid)
        if decode:
            from apex_tpu.serving.kv_cache import add_counts

            kv_cache = add_counts(kv_cache, cfg.expert_index(i), counts)
        with component(MLP):
            return x + out.reshape(s, lanes, -1).astype(x.dtype), kv_cache


class Dots3NoteForCausalLM(nn.Module):
    """Embedding -> the layers -> final RMSNorm -> untied head.

    ``__call__(input_ids [b, s])`` returns logits ``[s, b, vocab]``.  With
    ``kv_cache`` (built by ``DecodeEngine`` from :meth:`cache_layers`) it
    returns ``(logits, kv_cache)``: ``input_ids [1, s > 1]`` + ``slot`` +
    scalar ``position`` + ``length`` prefills one chunk of one slot, of
    which the first ``length`` rows are real; ``input_ids [slots, 1]`` +
    ``position [slots]`` + ``active [slots]`` runs one decode step."""

    config: Dots3NoteConfig
    params_dtype: Any = jnp.float32
    axis_name: str = TENSOR_PARALLEL_AXIS

    def cache_layers(self) -> list:
        """What each sublayer keeps a slot between calls: a layer's
        attention, then its MLP (None where it keeps nothing)."""
        from apex_tpu.serving.kv_cache import (
            CallCounters,
            LatentRows,
            RingRows,
        )

        cfg = self.config
        out = []
        for i, kind in enumerate(cfg.layer_types):
            a = cfg.attention(kind)
            out.append(LatentRows(a["rank"] + a["rope"], cfg.index_head_dim,
                                  cfg.index_topk)
                       if kind == FULL else
                       RingRows(a["rank"] + a["rope"],
                                cfg.sliding_window_size))
            out.append(None if i < cfg.first_k_dense_replace
                       else CallCounters(MOE_COUNTERS))
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
            x, kv_cache = Dots3NoteLayer(
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
