"""Plain float32 forward of the Mellum architecture (``model_type``
``mellum``: JetBrains/Mellum2-12B-A2.5B-Instruct) in ``jax.numpy``: no
kernels, no cache, no ring - the window is a mask on the ``[t, t]`` scores -,
no batching, no bf16, K and V repeated to the query heads, every expert
computed densely and weighted (zero for the unchosen), ``lax.top_k`` on the
softmax.  It reads the system's parameter tree and upcasts one layer - in
its experts one block of them - at a time, and walks heads in blocks, so that
neither a float32 copy of the model nor a ``[heads, t, t]`` score tensor ever
exists.

The equations (keys are the published config's; ``x`` in R^hidden_size):

Pre-norm, RMSNorm (``rms_norm_eps``) before the attention and before the
experts, a residual after each; final RMSNorm; untied head; no bias
anywhere.

Attention, every layer (``H = num_attention_heads``, ``G =
num_key_value_heads``, ``d = head_dim``)::

    q = x W_q -> H x d;   k = x W_k -> G x d;   v = x W_v -> G x d
    rope (rotate-half) over all d channels of q and k:
        angle_{t,i} = t inv_freq_i;  cos and sin times attention_factor
    query head j reads KV head j // (H / G)
    p = softmax over the visible s of q_j . k_s d^-1/2       float32
    y = concat_j(sum_s p_s v_s) W_o

``layer_types[i] == "sliding_attention"``: ``inv_freq_i = theta^(-2i/d)``
(``rope_parameters.sliding_attention``), keys ``t - sliding_window < s <=
t``.  ``"full_attention"``: keys ``s <= t``, rope under YaRN
(``rope_parameters.full_attention``: ``factor``,
``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``)::

    extra_i = theta^(-2i/d);   inter_i = extra_i / factor
    dim(r) = d ln(original / (2 pi r)) / (2 ln theta)
    low = floor(dim(beta_fast));   high = ceil(dim(beta_slow))
    ramp_i = clip((i - low) / (high - low), 0, 1)
    inv_freq_i = inter_i ramp_i + extra_i (1 - ramp_i)

and ``attention_factor`` multiplies cos and sin, so a full layer's scores
carry its square.

Experts, every layer (``mlp_layer_types`` all ``sparse``)::

    p = softmax(x W_r)                      float32, all num_experts
    chosen = the num_experts_per_tok largest p
    w_e = p_e / sum over chosen p           (norm_topk_prob)
    y = sum over chosen e of w_e W^down_e (silu(W^gate_e x) * W^up_e x)

Conventions the published config leaves to the families that share its keys,
each listed under ``assumed`` in ``configs/mellum2-12b-l8.json``: softmax
before the top-k and the renormalisation over the chosen; the window counts
the query's own position; YaRN's ramp and where ``attention_factor`` enters
as above; no per-head norm on q or k, no attention sink, no gate, no shared
expert, no selection bias, no scaling factor (the config declares none).

Departures from the published model, each the configuration's:

- **held experts**: the tree holds the experts ``[lo, lo + n)`` of a layer
  (``experts_gate [n, hidden, width]``); the router stays as wide as
  published and keeps its top-k and its weights, and what chosen experts
  held elsewhere would add is left out, here as in the system.  ``held``
  gives ``lo``.  (The benchmark's cell holds all 64: nothing is left out.)
- **no multi-token-prediction head**: the model card describes one, the
  config has no key for it, and it adds nothing to these logits.
- ``intermediate_size`` is the width of a ``dense`` entry of
  ``mlp_layer_types``, of which the published list has none: unused.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST
EXPERT_BLOCK = 8           # experts upcast to float32 at a time
HEAD_BLOCK = 8             # heads whose [t, t] scores exist at a time
FULL, WINDOW = "full_attention", "sliding_attention"


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _mm(x, w):
    return jnp.dot(x, w, precision=_HIGHEST)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def inv_freq(rope: dict, dim: int):
    """``(inv_freq [dim // 2], attention_factor)`` of one block of
    ``rope_parameters``."""
    theta = rope["rope_theta"]
    pair = jnp.arange(dim // 2, dtype=jnp.float32)
    extra = theta ** (-2.0 * pair / dim)
    if rope["rope_type"] == "default":
        return extra, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")

    def pair_of(turns):
        return (dim * math.log(rope["original_max_position_embeddings"]
                               / (2 * math.pi * turns))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_of(rope["beta_fast"])), 0)
    high = min(math.ceil(pair_of(rope["beta_slow"])), dim - 1)
    ramp = jnp.clip((pair - low) / (high - low), 0.0, 1.0)
    return (extra / rope["factor"] * ramp + extra * (1.0 - ramp),
            rope["attention_factor"])


def _rope(x, rope: dict):
    """Every channel of ``x [s, heads, d]`` rotated at positions ``0 .. s -
    1``, rotate-half."""
    s, _, d = x.shape
    inv, factor = inv_freq(rope, d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return (x * (factor * jnp.cos(ang))
            + jnp.concatenate([-x2, x1], axis=-1) * (factor * jnp.sin(ang)))


def _head_block(heads: int) -> int:
    """The largest divisor of ``heads`` up to ``HEAD_BLOCK``."""
    return max(b for b in range(1, HEAD_BLOCK + 1) if heads % b == 0)


def attention(u, p, config: dict, kind: str):
    """``u [s, hidden]`` (normed) through one attention layer."""
    p = _f32(p)
    heads, nkv, d = (config["num_attention_heads"],
                     config["num_key_value_heads"], config["head_dim"])
    s = u.shape[0]
    rope = config["rope_parameters"][kind]
    q = _rope(_mm(u, p["q_proj"]["kernel"]).reshape(s, heads, d), rope)
    k = _rope(_mm(u, p["k_proj"]["kernel"]).reshape(s, nkv, d), rope)
    v = _mm(u, p["v_proj"]["kernel"]).reshape(s, nkv, d)
    k, v = (jnp.repeat(t, heads // nkv, axis=1) for t in (k, v))
    at = jnp.arange(s)
    mask = at[None] <= at[:, None]
    if kind == WINDOW:
        mask &= at[None] > at[:, None] - config["sliding_window"]
    block = _head_block(heads)

    def read(args):
        q_blk, k_blk, v_blk = args                # [block, s, d] each
        scores = jnp.einsum("htd,hsd->hts", q_blk, k_blk,
                            precision=_HIGHEST) * d ** -0.5
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), -1)
        return jnp.einsum("hts,hsd->htd", probs, v_blk, precision=_HIGHEST)

    ctx = jax.lax.map(read, tuple(
        t.transpose(1, 0, 2).reshape(heads // block, block, s, d)
        for t in (q, k, v)))
    ctx = ctx.reshape(heads, s, d).transpose(1, 0, 2).reshape(s, heads * d)
    return _mm(ctx, p["o_proj"]["kernel"])


def router_probs(u, kernel):
    """``softmax(u W_r)`` in float32 over every published expert."""
    return jax.nn.softmax(_mm(u, kernel.astype(jnp.float32)), axis=-1)


def route(u, kernel, config: dict):
    """``[s, num_experts]`` float32: each token's weight on each expert,
    zero for the unchosen."""
    probs = router_probs(u, kernel)
    picked, chosen = jax.lax.top_k(probs, config["num_experts_per_tok"])
    if config.get("norm_topk_prob", True):
        picked = picked / picked.sum(-1, keepdims=True)
    return jnp.zeros_like(probs).at[
        jnp.arange(u.shape[0])[:, None], chosen].set(picked)


@jax.jit
def _expert_block(u, w_gate, w_up, w_down, weights):
    """``sum_e weights[:, e] W_down_e (silu(W_gate_e u) * W_up_e u)`` over
    one block of experts."""
    w_gate, w_up, w_down = _f32((w_gate, w_up, w_down))
    hid = (jax.nn.silu(jnp.einsum("sh,ehw->esw", u, w_gate,
                                  precision=_HIGHEST))
           * jnp.einsum("sh,ehw->esw", u, w_up, precision=_HIGHEST))
    out = jnp.einsum("esw,ewh->esh", hid, w_down, precision=_HIGHEST)
    return jnp.einsum("esh,se->sh", out, weights, precision=_HIGHEST)


def experts(u, p, config: dict, *, held: int = 0):
    """``u [s, hidden]`` through the expert layer's share of the experts
    ``[held, held + n)``, ``n`` read from the tree."""
    weights = route(u, p["router_kernel"], config)
    n = p["experts_gate"].shape[0]
    out = jnp.zeros_like(u)
    for lo in range(0, n, EXPERT_BLOCK):
        hi = min(lo + EXPERT_BLOCK, n)
        out = out + _expert_block(
            u, p["experts_gate"][lo:hi], p["experts_up"][lo:hi],
            p["experts_down"][lo:hi], weights[:, held + lo:held + hi])
    return out


class _Frozen(dict):
    """The config's numbers, strings and nested groups of them, hashable,
    so that one jitted layer function serves every layer of a kind."""

    def __init__(self, config):
        super().__init__({
            k: _Frozen(v) if isinstance(v, dict) else v
            for k, v in config.items()
            if isinstance(v, (int, float, str, type(None), dict))})

    def __hash__(self):
        return hash(tuple(sorted(self.items(), key=lambda kv: kv[0])))


_attention = jax.jit(attention, static_argnames=("config", "kind"))


@functools.partial(jax.jit, static_argnames="eps")
def _head(x, scale, table, *, eps):
    x = _rms_norm(x, scale.astype(jnp.float32), eps)
    return jnp.dot(x, table.astype(jnp.float32).T, precision=_HIGHEST)


def embed(params, ids):
    return params["params"]["embed_tokens"]["embedding"][
        jnp.asarray(ids)].astype(jnp.float32)


def normed(x, scale, config: dict):
    """The rows a sublayer reads: its RMSNorm of the residual."""
    return _rms_norm(x, scale["scale"].astype(jnp.float32),
                     config["rms_norm_eps"])


def attention_out(x, layer, config: dict, kind: str):
    """One layer's attention on the residual ``x [s, hidden]``."""
    return _attention(normed(x, layer["input_layernorm"], config),
                      layer["self_attn"], config=_Frozen(config), kind=kind)


def mlp_out(h, layer, config: dict, *, held: int = 0):
    """One layer's experts on its normed rows ``h``."""
    return experts(h, layer["mlp"], config, held=held)


def logits_at(params, ids, positions, config: dict, *, held: int = 0):
    """Next-token logits ``[len(positions), vocab]`` of the causal forward
    over one sequence ``ids [s]``, at the given positions."""
    with jax.default_matmul_precision("highest"):
        p = params["params"]
        x = embed(params, ids)
        for i, kind in enumerate(config["layer_types"]):
            layer = p[f"layers_{i}"]
            x = x + attention_out(x, layer, config, kind)
            x = x + mlp_out(
                normed(x, layer["post_attention_layernorm"], config), layer,
                config, held=held)
        return _head(x[jnp.asarray(positions)], p["norm"]["scale"],
                     p["lm_head"], eps=config["rms_norm_eps"])
