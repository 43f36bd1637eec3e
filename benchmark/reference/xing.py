"""Plain float32 forward of the Xing4.0 architecture (``model_type``
``xing4_0``: XingChen-AGI/Xing4.0-29B-A4B) in ``jax.numpy``: no kernels, no
cache, no batching, no bf16, no absorption - every head's K and V are
expanded from the latent row -, the causal mask on a ``[t, t]`` score
matrix, a dense loop over the experts, the Sinkhorn iterations written out.
It reads the system's parameter tree and upcasts one layer - in an expert
layer one block of experts, in the head one block of the vocabulary - at a
time, and walks heads in blocks, so that neither a float32 copy of the model
nor a ``[heads, t, t]`` score tensor ever exists.

The equations (keys are the published config's; ``n = hc_mult``, ``C =
hidden_size``, a token's residual streams ``x in R^{n x C}``):

Embedding ``e``; ``x_0 = [e, ..., e]`` (``n`` copies).  Each layer is two
sublayers ``F`` - the attention, then the MLP - each with its own pre-RMSNorm
(``rms_norm_eps``) inside ``F`` and its own ``phi [n C, 2n + n^2]``,
``alpha [3]``, ``b [2n + n^2]``::

    u = vec(x) / sqrt(mean(vec(x)^2) + rms_norm_eps)
    [p, q, r] = [alpha_0 (u phi)_{:n}, alpha_1 (u phi)_{n:2n}, alpha_2 (u phi)_{2n:}] + b
    H_pre = sigmoid(p) + hc_eps;   H_post = 2 sigmoid(q)
    M = exp(clamp(mat(r), mhc_h_res_clamp_min, mhc_h_res_clamp_max))     mat: row-major n x n
    hc_sinkhorn_iters times: M_ji <- M_ji / (sum_i M_ji + hc_eps); M_ji <- M_ji / (sum_j M_ji + hc_eps)
    y = F(sum_i H_pre,i x_i)
    x'_j = sum_i M_ji x_i + H_post,j y

Read-out ``h = sum_i (sigmoid(alpha_h (u phi_h) + b_h)_i + hc_eps) x_i``
(``phi_h [n C, n]``, one ``alpha_h``); final RMSNorm; untied head.

Attention, every layer (``H = num_attention_heads``, ``nope =
qk_nope_head_dim``, ``rope = qk_rope_head_dim``)::

    c_q = RMSNorm(x W_qa)                              q_lora_rank
    q_h = c_q W_qb = [q_h^nope | q_h^rope]             rope on q_h^rope
    [c_kv | k_r] = x W_kva;  c_kv <- RMSNorm(c_kv);  rope on k_r
    [k_h^nope | v_h] = c_kv W_kvb                      kv_lora_rank -> H x (nope + v)
    p = softmax over s <= t of (q_h^nope . k_{h,s}^nope + q_h^rope . k_{r,s}) scale
    scale = (nope + rope)^-1/2 m^2,   m = 0.1 mscale_all_dim ln(factor) + 1
    y = concat_h(sum_s p_s v_{h,s}) W_o

rope: rotate-half over the ``rope`` channels, YaRN frequencies
(``rope_scaling``: ``factor``, ``original_max_position_embeddings``,
``beta_fast``, ``beta_slow``; the ramp ``reference/mellum.py::inv_freq``
writes out), cos and sin times ``m(mscale) / m(mscale_all_dim)`` (1 as
published).

MLP: the first ``first_k_dense_replace`` layers SwiGLU ``C ->
intermediate_size -> C``; every other layer::

    s = sigmoid(x W_r)                      float32, all n_routed_experts
    chosen = the num_experts_per_tok largest of s + b   (noaux_tc, n_group 1)
    w_e = routed_scaling_factor s_e / (sum of the chosen s + 1e-20)    norm_topk_prob
    y = sum over chosen e of w_e W^down_e (silu(W^gate_e x) * W^up_e x)
        + n_shared_experts shared experts of the same shape, unweighted

Conventions the published config leaves open, each listed under ``assumed``
in ``configs/xing4-29b-l6.json``: (a) the read-out's form and ``hc_eps``
added to ``H_pre`` and to the read-out's weights and to each Sinkhorn
normaliser, as DeepSeek's published mHC inference code places them
(arXiv:2512.24880; a plain sum of the streams is the Hyper-Connections
paper's read-out, arXiv:2409.19606); (b) rotate-half rope, a relabelling of
the published interleaved channels; (c) the residual streams in float32.

Departures from the published model, each the configuration's: 6 of 40
layers; no multi-token-prediction module (it adds nothing to these logits).
``held`` gives the first expert the tree holds, as in the other families.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# YaRN's ramp, written out in Mellum's reference
from benchmark.reference.mellum import inv_freq
# the sigmoid top-k routing with a selection bias is Nemotron-H's, key for key
from benchmark.reference.nemotron_h import route, router_scores  # noqa: F401

_HIGHEST = jax.lax.Precision.HIGHEST
EXPERT_BLOCK = 4           # experts upcast to float32 at a time
HEAD_BLOCK = 8             # heads whose [t, t] scores exist at a time
VOCAB_BLOCK = 16384        # rows of the head upcast to float32 at a time


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _mm(x, w):
    return jnp.dot(x, w, precision=_HIGHEST)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rope(x, config: dict):
    """``x [s, heads, rope]`` rotated at positions ``0 .. s - 1``,
    rotate-half, YaRN."""
    y = config["rope_scaling"]
    width = x.shape[-1]
    inv, _ = inv_freq({"rope_type": "yarn", "rope_theta": config["rope_theta"],
                       "attention_factor": 1.0,
                       **{k: y[k] for k in (
                           "factor", "original_max_position_embeddings",
                           "beta_fast", "beta_slow")}}, width)
    gain = (mscale(y["factor"], y["mscale"])
            / mscale(y["factor"], y["mscale_all_dim"]))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = x[..., :width // 2], x[..., width // 2:]
    return (x * (gain * jnp.cos(ang))
            + jnp.concatenate([-x2, x1], axis=-1) * (gain * jnp.sin(ang)))


def _head_block(heads: int) -> int:
    """The largest divisor of ``heads`` up to ``HEAD_BLOCK``."""
    return max(b for b in range(1, HEAD_BLOCK + 1) if heads % b == 0)


# ---- the hyper-connections --------------------------------------------------


def unit(x, config: dict):
    """``vec(x) / rms(vec(x))`` of each token's streams ``x [t, n, C]``."""
    flat = x.reshape(x.shape[0], -1)
    return flat / jnp.sqrt((flat * flat).mean(-1, keepdims=True)
                           + config["rms_norm_eps"])


def sinkhorn(m, config: dict):
    eps = config["hc_eps"]
    for _ in range(config["hc_sinkhorn_iters"]):
        m = m / (m.sum(-1, keepdims=True) + eps)      # rows
        m = m / (m.sum(-2, keepdims=True) + eps)      # columns
    return m


def maps(x, p, config: dict):
    """One sublayer's ``(H_pre [t, n], H_post [t, n], H_res [t, n, n])``."""
    n = config["hc_mult"]
    z = _mm(unit(x, config), p["phi"].astype(jnp.float32))
    a, b = p["alpha"].astype(jnp.float32), p["bias"].astype(jnp.float32)
    pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n]) + config["hc_eps"]
    post = 2.0 * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
    r = (a[2] * z[:, 2 * n:] + b[2 * n:]).reshape(-1, n, n)
    m = jnp.exp(jnp.clip(r, config["mhc_h_res_clamp_min"],
                         config["mhc_h_res_clamp_max"]))
    return pre, post, sinkhorn(m, config)


def sublayer_in(x, p, config: dict):
    """``(H_post, H_res, sum_i H_pre,i x_i)``: what a sublayer reads."""
    pre, post, res = maps(x, p, config)
    return post, res, jnp.einsum("tn,tnc->tc", pre, x, precision=_HIGHEST)


def sublayer_out(x, post, res, y):
    """``x'_j = sum_i H_res,ji x_i + H_post,j y``."""
    return (jnp.einsum("tji,tic->tjc", res, x, precision=_HIGHEST)
            + post[..., None] * y[:, None])


def read_out(x, p, config: dict):
    z = _mm(unit(x, config), p["phi"].astype(jnp.float32))
    w = (jax.nn.sigmoid(p["alpha"].astype(jnp.float32)[0] * z
                        + p["bias"].astype(jnp.float32)) + config["hc_eps"])
    return jnp.einsum("tn,tnc->tc", w, x, precision=_HIGHEST)


# ---- the sublayers ----------------------------------------------------------


def attention(u, p, config: dict):
    """``u [s, hidden]`` (normed) through one latent-attention layer."""
    p = _f32(p)
    heads, rank = config["num_attention_heads"], config["kv_lora_rank"]
    nope, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                    config["v_head_dim"])
    s = u.shape[0]
    eps = config["rms_norm_eps"]
    c_q = _rms_norm(_mm(u, p["q_a_proj"]["kernel"]), p["q_a_norm"]["scale"],
                    eps)
    q = _mm(c_q, p["q_b_proj"]["kernel"]).reshape(s, heads, nope + dr)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], config)], -1)
    kv_a = _mm(u, p["kv_a_proj"]["kernel"])
    c_kv = _rms_norm(kv_a[:, :rank], p["kv_a_norm"]["scale"], eps)
    k_r = _rope(kv_a[:, None, rank:], config)                  # [s, 1, rope]
    at = jnp.arange(s)
    mask = at[None] <= at[:, None]
    scale = ((nope + dr) ** -0.5
             * mscale(config["rope_scaling"]["factor"],
                      config["rope_scaling"]["mscale_all_dim"]) ** 2)
    block = _head_block(heads)

    def read(args):
        q_blk, w_blk = args           # [t, block, nope + dr], [rank, block, .]
        kv = jnp.einsum("sr,rhd->shd", c_kv, w_blk, precision=_HIGHEST)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_r, (s, block, dr))], -1)
        scores = jnp.einsum("thd,shd->hts", q_blk, k,
                            precision=_HIGHEST) * scale
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), -1)
        return jnp.einsum("hts,shd->thd", probs, kv[..., nope:],
                          precision=_HIGHEST)

    ctx = jax.lax.map(read, (
        q.reshape(s, heads // block, block, nope + dr).transpose(1, 0, 2, 3),
        p["kv_b_proj"].reshape(rank, heads // block, block,
                               nope + dv).transpose(1, 0, 2, 3)))
    ctx = ctx.transpose(1, 0, 2, 3).reshape(s, heads * dv)
    return _mm(ctx, p["o_proj"]["kernel"])


def gated_mlp(u, p):
    p = _f32(p)
    return _mm(jax.nn.silu(_mm(u, p["gate_proj"]["kernel"]))
               * _mm(u, p["up_proj"]["kernel"]), p["down_proj"]["kernel"])


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


@jax.jit
def _shared(u, p):
    return gated_mlp(u, {f"{k}_proj": v for k, v in p.items()})


def experts(u, p, config: dict, *, held: int = 0):
    """``u [s, hidden]`` through an expert layer: the experts ``[held, held
    + n)`` the tree holds (``n`` read from it) and the shared experts."""
    weights = route(u, p["router_kernel"], p["router_bias"], config)
    n = p["experts_gate"].shape[0]
    out = jnp.zeros_like(u)
    for lo in range(0, n, EXPERT_BLOCK):
        hi = min(lo + EXPERT_BLOCK, n)
        out = out + _expert_block(
            u, p["experts_gate"][lo:hi], p["experts_up"][lo:hi],
            p["experts_down"][lo:hi], weights[:, held + lo:held + hi])
    if config["n_shared_experts"]:
        out = out + _shared(u, {k: p[f"shared_{k}"]
                                for k in ("gate", "up", "down")})
    return out


class _Frozen(dict):
    """The config's numbers, strings and nested groups of them, hashable,
    so that one jitted layer function serves every layer."""

    def __init__(self, config):
        super().__init__({
            k: _Frozen(v) if isinstance(v, dict) else v
            for k, v in config.items()
            if isinstance(v, (int, float, str, type(None), dict))})

    def __hash__(self):
        return hash(tuple(sorted(self.items(), key=lambda kv: kv[0])))


_attention = jax.jit(attention, static_argnames="config")
_gated_mlp = jax.jit(gated_mlp)


def embed(params, ids):
    return params["params"]["embed_tokens"]["embedding"][
        jnp.asarray(ids)].astype(jnp.float32)


def streams(e, config: dict):
    """``x_0``: the embedding in each of the ``hc_mult`` streams."""
    return jnp.repeat(e[:, None], config["hc_mult"], axis=1)


def normed(x, scale, config: dict):
    """RMSNorm of ``x`` with a sublayer's scale."""
    return _rms_norm(x, scale["scale"].astype(jnp.float32),
                     config["rms_norm_eps"])


def attention_sublayer(x, layer, config: dict):
    """One layer's attention on the streams ``x [s, n, C]``."""
    post, res, h = sublayer_in(x, layer["attn_hc"], config)
    y = _attention(normed(h, layer["input_layernorm"], config),
                   layer["self_attn"], config=_Frozen(config))
    return sublayer_out(x, post, res, y)


def mlp_rows(x, layer, config: dict):
    """``(H_post, H_res, rows)``: what layer's MLP reads, normed."""
    post, res, h = sublayer_in(x, layer["mlp_hc"], config)
    return post, res, normed(h, layer["post_attention_layernorm"], config)


def mlp_out(h, layer, i: int, config: dict, *, held: int = 0):
    """Layer ``i``'s MLP on its normed rows ``h``."""
    if i < config["first_k_dense_replace"]:
        return _gated_mlp(h, layer["mlp"])
    return experts(h, layer["mlp"], config, held=held)


def final_rows(x, p, config: dict):
    """The rows the head reads: read-out, final RMSNorm."""
    return normed(read_out(x, p["head_hc"], config), p["norm"], config)


@jax.jit
def _head(h, table):
    """``h [t, C] table^T``, the table upcast a block of rows at a time."""
    vocab = table.shape[0]
    block = math.gcd(vocab, VOCAB_BLOCK)
    parts = jax.lax.map(
        lambda rows: jnp.dot(h, rows.astype(jnp.float32).T,
                             precision=_HIGHEST),
        table.reshape(vocab // block, block, -1))
    return parts.transpose(1, 0, 2).reshape(h.shape[0], vocab)


def margins(h, mlp, config: dict):
    """Each row's routing margin: its ``num_experts_per_tok``-th largest
    ``score + bias`` less the next one, float32 - how far the choice is from
    a tie."""
    k = config["num_experts_per_tok"]
    top, _ = jax.lax.top_k(router_scores(h, mlp["router_kernel"])
                           + mlp["router_bias"].astype(jnp.float32), k + 1)
    return top[:, k - 1] - top[:, k]


def logits_at(params, ids, positions, config: dict, *, held: int = 0,
              with_margins: bool = False):
    """Next-token logits ``[len(positions), vocab]`` of the causal forward
    over one sequence ``ids [s]``, at the given positions; with
    ``with_margins`` also each position's routing margin in each expert
    layer, ``[len(positions), expert layers]``."""
    at = jnp.asarray(positions)
    found = []
    with jax.default_matmul_precision("highest"):
        p = params["params"]
        x = streams(embed(params, ids), config)
        for i in range(config["num_hidden_layers"]):
            layer = p[f"layers_{i}"]
            x = attention_sublayer(x, layer, config)
            post, res, h = mlp_rows(x, layer, config)
            if with_margins and i >= config["first_k_dense_replace"]:
                found.append(margins(h[at], layer["mlp"], config))
            x = sublayer_out(x, post, res,
                             mlp_out(h, layer, i, config, held=held))
        logits = _head(final_rows(x[at], p, config), p["lm_head"])
    if not with_margins:
        return logits
    return logits, jnp.stack(found, axis=-1)
