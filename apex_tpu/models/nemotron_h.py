"""Nemotron-H causal LM (``model_type`` ``nemotron_h``): a decoder whose
layers are *a mixer or a feed-forward part alone* - one RMSNorm and one
residual a layer, ``x <- x + f_i(norm_i(x))`` - chosen by a pattern string:
``M`` a Mamba-2 mixer, ``*`` grouped-query attention without positional
embedding, ``E`` routed experts in a latent space beside a shared expert
(:class:`apex_tpu.transformer.moe.LatentMoE`).  The equations are written out
in ``benchmark/reference/nemotron_h.py``, the plain float32 forward this
module is tested against.

Serving contract: :class:`NemotronHForCausalLM` takes
:class:`~apex_tpu.models.llama.LlamaForCausalLM`'s cached call
(``input_ids``, ``kv_cache=``, ``position=``, ``slot=``, returning
``(logits, cache)``) and **needs** the two things a recurrent state cannot
do without: the chunk's real ``length`` (rows at or beyond it advance no
state) and the decode step's ``active`` lanes (an idle lane keeps its state
bit for bit).  :meth:`NemotronHForCausalLM.cache_layers` declares what each
layer keeps a slot; ``DecodeEngine`` builds the cache from that.

- Attention is the cache's own step (``serving.kv_cache.decode_attend`` /
  ``prefill_attend``, here 16 query heads a KV head), as in
  ``models.llama``.
- Mamba-2 prefill is the chunked scan (:func:`ssd_chunked`: inside a chunk
  masked matrix products, between chunks a short scan over chunk states)
  started from the slot's carried state; decode is the one-token update
  (:func:`ssd_step`) over all slots.  ``jax.numpy`` / ``lax`` only.
- Not built: the multi-token-prediction module, tensor parallelism, a
  backward pass anybody has checked.
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
    HEAD,
    MLP,
    NORM,
    STATE,
    component,
)
from apex_tpu.transformer.moe import MOE_COUNTERS, LatentMoE
from apex_tpu.transformer.parallel_state import TENSOR_PARALLEL_AXIS
from apex_tpu.transformer.tensor_parallel import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
    parallel_lm_logits,
)

__all__ = ["NemotronHConfig", "NemotronHForCausalLM", "ssd_chunked",
           "ssd_step", "mamba2_init"]


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """The published keys the forward reads, under their own names.
    ``n_routed_experts`` is the router's width (the model's experts);
    ``experts_held`` is the ``(start, count)`` of them this chip holds."""

    vocab_size: int = 131072
    hidden_size: int = 4096
    hybrid_override_pattern: str = "MEMEMEM*EME"
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    n_routed_experts: int = 512
    experts_held: Tuple[int, int] = (0, 512)
    num_experts_per_tok: int = 22
    moe_latent_size: int = 1024
    moe_intermediate_size: int = 2688
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    layer_norm_epsilon: float = 1e-5

    def __post_init__(self):
        bad = set(self.hybrid_override_pattern) - set("M*E")
        if bad or not self.hybrid_override_pattern:
            raise ValueError(
                f"hybrid_override_pattern {self.hybrid_override_pattern!r}: "
                f"a non-empty string of M (Mamba-2), * (attention), E "
                f"(routed experts)")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError(f"mamba_num_heads {self.mamba_num_heads} is not "
                             f"a multiple of n_groups {self.n_groups}")

    @property
    def num_hidden_layers(self) -> int:
        return len(self.hybrid_override_pattern)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    def index_among(self, layer: int) -> int:
        """Layer ``layer``'s index among the layers of its own kind: its row
        on the leading axis of that kind's part of the cache."""
        pattern = self.hybrid_override_pattern
        return pattern[:layer].count(pattern[layer])


# ---- Mamba-2: the state-space duality, chunked, and one token -------------

def ssd_chunked(x, dt, a, b, c, s0, chunk: int):
    """``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t`` over
    ``s`` steps from ``s0``, in chunks: inside a chunk the steps' effect on
    each other is one masked ``[chunk, chunk]`` matrix a head (``C B^T``
    times the decay between the two steps), between chunks a scan over the
    chunk states.

    ``x [s, H, P]``, ``dt [s, H]`` float32 (after softplus; 0 on a row that
    must change nothing), ``a [H]`` float32 (negative), ``b`` / ``c``
    ``[s, G, N]`` (head ``h`` reads group ``h // (H / G)``), ``s0
    [H, P, N]`` float32.  Returns ``(y [s, H, P] float32, s1 [H, P, N])``.
    Matrix operands are in ``x``'s type with float32 sums; decays, ``dt``
    and the state are float32."""
    s, n_head, hd = x.shape
    groups, n = b.shape[1], b.shape[2]
    rep = n_head // groups
    size = min(chunk, s)
    pad = -s % size
    if pad:
        # rows with dt = 0 change neither the state nor any real row's y
        x, b, c = (jnp.pad(t, ((0, pad), (0, 0), (0, 0))) for t in (x, b, c))
        dt = jnp.pad(dt, ((0, pad), (0, 0)))
    nc = (s + pad) // size
    xg = x.reshape(nc, size, groups, rep, hd)
    b = b.reshape(nc, size, groups, n)
    c = c.reshape(nc, size, groups, n)
    dt = dt.reshape(nc, size, groups, rep)
    cs = jnp.cumsum(dt * a.reshape(groups, rep), axis=1)     # [c, l, g, r]
    f32 = jnp.float32

    # inside a chunk: y_l += sum_{m <= l} exp(cs_l - cs_m) (C_l . B_m) dt_m x_m
    cb = jnp.einsum("clgn,cmgn->cglm", c, b, preferred_element_type=f32)
    diff = cs[:, :, None] - cs[:, None, :]                   # [c, l, m, g, r]
    causal = jnp.tril(jnp.ones((size, size), bool))[None, :, :, None, None]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    mix = (cb[:, :, None] * decay.transpose(0, 3, 4, 1, 2)
           * dt.transpose(0, 2, 3, 1)[:, :, :, None, :])     # [c, g, r, l, m]
    y = jnp.einsum("cgrlm,cmgrp->clgrp", mix.astype(x.dtype), xg,
                   preferred_element_type=f32)

    # what a chunk adds to the state by its end, and how much of the state
    # it was handed survives it
    to_end = jnp.exp(cs[:, -1:] - cs) * dt                   # [c, l, g, r]
    local = jnp.einsum("clgrp,clgn->cgrpn",
                       (xg * to_end[..., None]).astype(x.dtype), b,
                       preferred_element_type=f32)
    keep = jnp.exp(cs[:, -1])                                # [c, g, r]

    def carry(state, chunk_):
        keep_c, local_c = chunk_
        return keep_c[..., None, None] * state + local_c, state

    s1, entering = lax.scan(carry, s0.reshape(groups, rep, hd, n),
                            (keep, local))
    y = y + jnp.exp(cs)[..., None] * jnp.einsum(
        "clgn,cgrpn->clgrp", c.astype(f32), entering,
        preferred_element_type=f32)
    return (y.reshape(nc * size, n_head, hd)[:s],
            s1.reshape(n_head, hd, n))


def ssd_step(x, dt, a, b, c, s0):
    """One token a lane: ``x [lanes, H, P]``, ``dt [lanes, H]`` float32,
    ``b`` / ``c`` ``[lanes, G, N]``, ``s0 [lanes, H, P, N]`` float32.
    Returns ``(y [lanes, H, P] float32, s1)``; all float32 elementwise."""
    rep = x.shape[1] // b.shape[1]
    f32 = jnp.float32
    b = jnp.repeat(b.astype(f32), rep, axis=1)               # [lanes, H, N]
    c = jnp.repeat(c.astype(f32), rep, axis=1)
    s1 = (jnp.exp(dt * a)[..., None, None] * s0
          + (dt[..., None] * x.astype(f32))[..., None] * b[:, :, None, :])
    return (s1 * c[:, :, None, :]).sum(-1), s1


def mamba2_init(key, shape, dtype, *, what: str, dt_min: float = 1e-3,
                dt_max: float = 0.1, dt_floor: float = 1e-4):
    """Mamba-2's published initialisation of its three per-head vectors, so
    that states decay as they do in the model: ``A_log = log(u)``, ``u ~
    U[1, 16]``; ``dt_bias = softplus^-1(t)``, ``t`` log-uniform on
    ``[dt_min, dt_max]`` floored at ``dt_floor``; ``D = 1``."""
    if what == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                          16.0)).astype(dtype)
    if what == "dt_bias":
        t = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                       jnp.log(dt_min), jnp.log(dt_max)))
        t = jnp.maximum(t, dt_floor)
        return (t + jnp.log(-jnp.expm1(-t))).astype(dtype)
    if what == "D":
        return jnp.ones(shape, dtype)
    raise ValueError(what)


class Mamba2Mixer(nn.Module):
    """``[z | xBC | dt] = W_in u``; causal depthwise convolution and silu on
    ``xBC``; the state-space recurrence on ``x, B, C``; gated grouped
    RMSNorm; ``W_out``.  Carried a slot: the state ``S [H, P, N]`` and the
    last ``conv_kernel - 1`` rows of the pre-convolution ``xBC``."""

    config: NemotronHConfig
    params_dtype: Any = jnp.float32

    @nn.compact
    @component(STATE)
    def __call__(self, u, *, kv_cache=None, layer_idx=None, position=None,
                 slot=None, length=None, active=None):
        cfg = self.config
        s, lanes, _ = u.shape
        n_head, hd = cfg.mamba_num_heads, cfg.mamba_head_dim
        groups, n, k = cfg.n_groups, cfg.ssm_state_size, cfg.conv_kernel
        d_inner, conv_dim = cfg.d_inner, cfg.conv_dim
        normal = nn.initializers.normal(0.02)

        def vector(name):
            return self.param(
                name, lambda key, sh, d: mamba2_init(key, sh, d, what=name),
                (n_head,), jnp.float32)

        # the mixer's two products count with the other projections; what
        # is left under ``apex.state`` is the recurrence and its state
        with component(ATTN_PROJ):
            proj = nn.Dense(d_inner + conv_dim + n_head, use_bias=False,
                            dtype=u.dtype, param_dtype=self.params_dtype,
                            kernel_init=normal, name="in_proj")(u)
        z, xbc, dt = jnp.split(proj, [d_inner, d_inner + conv_dim], axis=-1)
        conv = self.param("conv1d", lambda key, sh, d: {
            "kernel": normal(key, sh, d), "bias": jnp.zeros(sh[1:], d)},
            (k, conv_dim), self.params_dtype)
        w, bias = conv["kernel"].astype(u.dtype), conv["bias"].astype(u.dtype)
        a = -jnp.exp(vector("A_log"))
        dt = jax.nn.softplus(dt.astype(jnp.float32) + vector("dt_bias"))
        d_skip = vector("D")

        def activate(padded, rows):
            # padded [.., rows + k - 1, C]: the carried tail, then the rows
            out = sum(padded[..., j:j + rows, :] * w[j] for j in range(k))
            out = jax.nn.silu(out + bias)
            return jnp.split(out, [d_inner, d_inner + groups * n], axis=-1)

        if kv_cache is not None:
            from apex_tpu.serving.kv_cache import (
                slot_state,
                write_lane_state,
                write_slot_state,
            )
        if kv_cache is not None and s == 1:
            st = kv_cache.state
            padded = jnp.concatenate(
                [st.conv[layer_idx].astype(u.dtype), xbc[0][:, None]], axis=1)
            x, b, c = activate(padded, 1)                  # [lanes, 1, ..]
            x = x.reshape(lanes, n_head, hd)
            y, ssm = ssd_step(x, dt[0], a, b.reshape(lanes, groups, n),
                              c.reshape(lanes, groups, n),
                              st.ssm[layer_idx])
            kv_cache = write_lane_state(kv_cache, layer_idx, ssm,
                                        padded[:, 1:], active)
            y = (y + d_skip[:, None] * x)[None]            # [1, lanes, H, P]
        else:
            if kv_cache is not None:
                if lanes != 1:
                    raise ValueError(f"prefill expects one slot per call "
                                     f"(b=1), got b={lanes}")
                offset = 0 if position is None else position
                ssm0, tail = slot_state(kv_cache, layer_idx, slot, offset)
                ssm0, tail = ssm0[None], tail[None].astype(u.dtype)
                real = (jnp.arange(s) < length)[:, None, None]
                # a padded row is no step: dt = 0 keeps the state as it is
                dt = jnp.where(real, dt, 0.0)
            else:
                ssm0 = jnp.zeros((lanes, n_head, hd, n), jnp.float32)
                tail = jnp.zeros((lanes, k - 1, conv_dim), u.dtype)
            padded = jnp.concatenate([tail, xbc.transpose(1, 0, 2)], axis=1)
            x, b, c = activate(padded, s)                  # [lanes, s, ..]
            x = x.reshape(lanes, s, n_head, hd)
            y, ssm = jax.vmap(
                lambda x_, dt_, b_, c_, s_: ssd_chunked(
                    x_, dt_, a, b_, c_, s_, cfg.chunk_size))(
                x, dt.transpose(1, 0, 2), b.reshape(lanes, s, groups, n),
                c.reshape(lanes, s, groups, n), ssm0)
            if kv_cache is not None:
                # the tail after the last REAL row: padding leaves no trace
                tail = lax.dynamic_slice_in_dim(padded[0], length, k - 1, 0)
                kv_cache = write_slot_state(kv_cache, layer_idx, slot, ssm[0],
                                            tail)
            y = (y + d_skip[:, None] * x).transpose(1, 0, 2, 3)

        # gated RMSNorm over groups of d_inner / n_groups, one weight vector
        y = y.reshape(s, lanes, d_inner) * jax.nn.silu(z.astype(jnp.float32))
        y = y.reshape(s, lanes, groups, d_inner // groups)
        y = y * lax.rsqrt((y * y).mean(-1, keepdims=True)
                          + cfg.layer_norm_epsilon)
        scale = self.param("norm", lambda key, sh, d: {
            "scale": jnp.ones(sh, d)}, (d_inner,), jnp.float32)["scale"]
        y = (y.reshape(s, lanes, d_inner) * scale).astype(u.dtype)
        with component(ATTN_PROJ):
            out = nn.Dense(cfg.hidden_size, use_bias=False, dtype=u.dtype,
                           param_dtype=self.params_dtype, kernel_init=normal,
                           name="out_proj")(y)
        return out, kv_cache


class NemotronHAttention(nn.Module):
    """Causal grouped-query attention with no positional embedding (the
    Mamba layers carry order).  The cached branches are
    ``models.llama.LlamaAttention``'s without the rope: the same two calls
    into the cache."""

    config: NemotronHConfig
    params_dtype: Any = jnp.float32
    axis_name: str = TENSOR_PARALLEL_AXIS

    @nn.compact
    @component(ATTN_PROJ)
    def __call__(self, x, *, kv_cache=None, layer_idx=None, position=None,
                 slot=None):
        cfg = self.config
        hd, nq, nkv = cfg.head_dim, cfg.num_attention_heads, \
            cfg.num_key_value_heads
        common = dict(params_dtype=self.params_dtype,
                      axis_name=self.axis_name, use_bias=False)
        q = ColumnParallelLinear(cfg.hidden_size, nq * hd, name="q_proj",
                                 gather_output=False, **common)(x)
        k = ColumnParallelLinear(cfg.hidden_size, nkv * hd, name="k_proj",
                                 gather_output=False, **common)(x)
        v = ColumnParallelLinear(cfg.hidden_size, nkv * hd, name="v_proj",
                                 gather_output=False, **common)(x)
        s, b = q.shape[0], q.shape[1]
        q = q.reshape(s, b, nq, hd)
        k = k.reshape(s, b, nkv, hd)
        v = v.reshape(s, b, nkv, hd)
        if kv_cache is None:
            rep = nq // nkv
            qt = q.transpose(1, 2, 0, 3)                   # [b, nq, s, hd]
            kt = jnp.repeat(k, rep, axis=2).transpose(1, 2, 0, 3)
            vt = jnp.repeat(v, rep, axis=2).transpose(1, 2, 0, 3)
            scores = jnp.einsum("bhqd,bhkd->bhqk", qt, kt,
                                preferred_element_type=jnp.float32)
            scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)),
                               scores / hd ** 0.5, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1).astype(vt.dtype)
            ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
        else:
            from apex_tpu.serving.kv_cache import decode_attend, prefill_attend

            if s == 1:
                ctx, kv_cache = decode_attend(kv_cache, layer_idx, q, k, v,
                                              position)
            else:
                offset = jnp.asarray(0 if position is None else position,
                                     jnp.int32)
                ctx, kv_cache = prefill_attend(kv_cache, layer_idx, slot, q,
                                               k, v, offset)
        ctx = ctx.transpose(2, 0, 1, 3).reshape(s, b, nq * hd)
        out = RowParallelLinear(nq * hd, cfg.hidden_size,
                                input_is_parallel=True, name="o_proj",
                                **common)(ctx)
        return out, kv_cache


class NemotronHLayer(nn.Module):
    """``x + mixer(norm(x))``: one norm, one residual, one mixer."""

    config: NemotronHConfig
    kind: str
    params_dtype: Any = jnp.float32
    axis_name: str = TENSOR_PARALLEL_AXIS

    @nn.compact
    def __call__(self, x, *, kv_cache=None, layer_idx=None, position=None,
                 slot=None, length=None, active=None):
        cfg = self.config
        with component(NORM):
            h = FusedRMSNorm((cfg.hidden_size,), eps=cfg.layer_norm_epsilon,
                             param_dtype=jnp.float32, name="norm")(x)
        s, lanes, _ = x.shape
        decode = kv_cache is not None and s == 1
        if self.kind == "M":
            out, kv_cache = Mamba2Mixer(
                cfg, params_dtype=self.params_dtype, name="mixer")(
                h, kv_cache=kv_cache, layer_idx=layer_idx, position=position,
                slot=slot, length=length, active=active)
        elif self.kind == "*":
            out, kv_cache = NemotronHAttention(
                cfg, params_dtype=self.params_dtype,
                axis_name=self.axis_name, name="mixer")(
                h, kv_cache=kv_cache, layer_idx=layer_idx, position=position,
                slot=slot)
        else:
            # rows are s-major: a decode step's are its lanes, a chunk's
            # (one lane) its positions
            if decode:
                valid = active
            elif kv_cache is not None:
                valid = jnp.arange(s) < length
            else:
                valid = None
            out, counts = LatentMoE(
                num_experts=cfg.n_routed_experts,
                experts_held=cfg.experts_held,
                top_k=cfg.num_experts_per_tok, hidden_size=cfg.hidden_size,
                latent_size=cfg.moe_latent_size,
                expert_width=cfg.moe_intermediate_size,
                shared_width=cfg.moe_shared_expert_intermediate_size,
                routed_scaling_factor=cfg.routed_scaling_factor,
                param_dtype=self.params_dtype, name="mixer")(
                h.reshape(s * lanes, -1), valid)
            out = out.reshape(s, lanes, -1)
            if decode:
                from apex_tpu.serving.kv_cache import add_counts

                kv_cache = add_counts(kv_cache, layer_idx, counts)
        # a residual add is the root of the fusion XLA makes of it and the
        # product before it: it counts with the branch it closes
        with component(MLP if self.kind == "E" else ATTN_PROJ):
            return x + out.astype(x.dtype), kv_cache


class NemotronHForCausalLM(nn.Module):
    """Embedding -> the pattern's layers -> final RMSNorm -> untied head.

    ``__call__(input_ids [b, s])`` returns logits ``[s, b, vocab]`` from
    zero states.  With ``kv_cache`` (built by ``DecodeEngine`` from
    :meth:`cache_layers`) it returns ``(logits, kv_cache)``: ``input_ids
    [1, s > 1]`` + ``slot`` + scalar ``position`` + ``length`` prefills one
    chunk of one slot, of which the first ``length`` rows are real;
    ``input_ids [slots, 1]`` + ``position [slots]`` + ``active [slots]``
    runs one decode step."""

    config: NemotronHConfig
    params_dtype: Any = jnp.float32
    axis_name: str = TENSOR_PARALLEL_AXIS

    def cache_layers(self) -> list:
        """What each layer keeps a slot between calls, in layer order."""
        from apex_tpu.serving.kv_cache import (
            CallCounters,
            KVRows,
            RecurrentRows,
        )

        cfg = self.config
        kinds = {
            "M": RecurrentRows(
                ssm=(cfg.mamba_num_heads, cfg.mamba_head_dim,
                     cfg.ssm_state_size),
                conv=(cfg.conv_kernel - 1, cfg.conv_dim)),
            "*": KVRows(cfg.num_key_value_heads, cfg.head_dim),
            "E": CallCounters(MOE_COUNTERS)}
        return [kinds[kind] for kind in cfg.hybrid_override_pattern]

    @nn.compact
    def __call__(self, input_ids, *, kv_cache=None, position=None, slot=None,
                 length=None, active=None):
        cfg = self.config
        if kv_cache is not None:
            s = input_ids.shape[1]
            if s == 1 and active is None:
                raise ValueError("a decode step needs active= (the lanes "
                                 "whose recurrent state may advance)")
            if s > 1 and length is None:
                raise ValueError("a prefill chunk needs length= (its real "
                                 "rows: padding must not advance a "
                                 "recurrent state)")
        with component(EMBED):
            x = VocabParallelEmbedding(
                cfg.vocab_size, cfg.hidden_size,
                params_dtype=self.params_dtype, axis_name=self.axis_name,
                name="embed_tokens")(input_ids)
            x = x.transpose(1, 0, 2)                       # [s, b, h]
        for i, kind in enumerate(cfg.hybrid_override_pattern):
            x, kv_cache = NemotronHLayer(
                cfg, kind, params_dtype=self.params_dtype,
                axis_name=self.axis_name, name=f"layers_{i}")(
                x, kv_cache=kv_cache, layer_idx=cfg.index_among(i),
                position=position, slot=slot, length=length, active=active)
        with component(HEAD):
            x = FusedRMSNorm((cfg.hidden_size,), eps=cfg.layer_norm_epsilon,
                             param_dtype=jnp.float32, name="norm_f")(x)
            head = self.param("lm_head", nn.initializers.normal(0.02),
                              (cfg.vocab_size, cfg.hidden_size),
                              self.params_dtype)
            logits = parallel_lm_logits(x, head.astype(x.dtype),
                                        self.axis_name)
        return logits if kv_cache is None else (logits, kv_cache)
