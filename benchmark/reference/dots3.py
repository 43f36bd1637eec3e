"""Plain float32 forward of the dots3-note architecture (``model_type``
``dots3_note``: dots-studio/dots3-note-prev, 288B-A17B) in ``jax.numpy``:
no kernels, no cache, no batching, no bf16, no absorption - every head's K
and V are expanded from the latent row - the key selection a plain
``top_k`` over a masked ``[t, t]`` score matrix, the window a mask, a dense
loop over the experts.  It reads the system's parameter tree and upcasts
one layer - in an expert layer one block of experts - at a time, and walks
heads in blocks, so that neither a float32 copy of the model nor a ``[heads,
t, t]`` score tensor ever exists.

The equations (keys are the published config's; ``x`` in R^hidden_size):

Pre-norm, RMSNorm (``rms_norm_eps``) before each mixer and each MLP, a
residual after each; final RMSNorm; untied head.

``full_attention`` layer (``H = num_attention_heads``, ``nope =
qk_nope_head_dim``, ``rope = qk_rope_head_dim``)::

    c_q = r_q RMSNorm(x W_qa)                          q_lora_rank
    q_h = c_q W_qb = [q_h^nope | q_h^rope]             rope on q_h^rope
    [c_kv | k_r] = x W_kva;  c_kv <- r_kv RMSNorm(c_kv);  rope on k_r
    [k_h^nope | v_h] = c_kv W_kvb                      kv_lora_rank -> H x (nope + v)
    q^I_j = c_q W^I_q  (j < index_n_heads, index_head_dim wide)
    k^I = LayerNorm(x W^I_k);  rope on the first `rope` channels of both
    w = x W^I_w
    I(t, s) = sum_j w_{t,j} relu(q^I_{t,j} . k^I_s) index_n_heads^-1/2 index_head_dim^-1/2
    S_t = the index_topk largest I(t, s) over s <= t (all while t < index_topk)
    p = softmax over s in S_t of (q_h^nope . k_{h,s}^nope + q_h^rope . k_{r,s}) (nope + rope)^-1/2
    o_h = sigmoid(x W_g)_h sum_s p_s v_{h,s};  y = concat_h(o_h) W_o

``sliding_attention`` layer: the same with the ``swa_`` keys' ranks, head
count, head widths and theta, no selector, keys ``t - sliding_window_size
< s <= t``.

MLP: the first ``first_k_dense_replace`` layers SwiGLU ``hidden_size ->
intermediate_size -> hidden_size``; every other layer::

    s = sigmoid(x W_r)                      float32, all published experts
    chosen = the num_experts_per_tok largest of s + b   (noaux_tc, no groups)
    w_e = routed_scaling_factor s_e / (sum of the chosen s + 1e-20)
    y = sum over chosen e of w_e W^down_e (silu(W^gate_e x) * W^up_e x)
        + the shared expert, of the same shape, on every token

Conventions the published config leaves to its families, each listed under
``assumed`` in ``configs/dots3-note-ep8-l5.json``: (a)
``apply_mla_qkv_lora_rescale``: ``r_q = (hidden_size / q_lora_rank)^1/2``,
``r_kv = (hidden_size / kv_lora_rank)^1/2`` after the latent norms
(LongCat-Flash's form); (b) the selector's LayerNorm (with a bias, epsilon
``rms_norm_eps``), its rope on the first ``qk_rope_head_dim`` channels at the
layer's theta, relu and the two scale factors (DeepSeek-V3.2's indexer); (c)
the headwise gate a sigmoid of a linear map of the layer's normed input,
one scalar a head, before ``W_o``; (d) the window counts the query's own
position.  Rope is the rotate-half convention throughout.

Departures from the published model, each the configuration's:

- **held experts**: the tree holds the experts ``[lo, lo + n)`` of a layer
  (``experts_gate [n, hidden, width]``); the router stays as wide as
  published and keeps its top-k and its weights, and what the chosen experts
  held elsewhere would add is left out, here as in the system.  ``held``
  gives ``lo``.
- **sliced vocabulary**: embedding and head have the rows the tree holds.
- **no towers, no multi-token-prediction module**: they add nothing to
  these logits for text.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# the sigmoid top-k routing with a selection bias is Nemotron-H's, key for key
from benchmark.reference.nemotron_h import route, router_scores  # noqa: F401

_HIGHEST = jax.lax.Precision.HIGHEST
EXPERT_BLOCK = 4           # experts upcast to float32 at a time
HEAD_BLOCK = 8             # heads whose [t, t] scores exist at a time
FULL, WINDOW = "full_attention", "sliding_attention"


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _mm(x, w):
    return jnp.dot(x, w, precision=_HIGHEST)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta: float, width: int):
    """The first ``width`` channels of ``x [s, heads, d]`` rotated at
    positions ``0 .. s - 1``, rotate-half; the rest pass through."""
    s = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    part = x[..., :width]
    x1, x2 = part[..., :width // 2], part[..., width // 2:]
    rotated = part * jnp.cos(ang) + jnp.concatenate(
        [-x2, x1], axis=-1) * jnp.sin(ang)
    return jnp.concatenate([rotated, x[..., width:]], axis=-1)


def _head_block(heads: int) -> int:
    """The largest divisor of ``heads`` up to ``HEAD_BLOCK``."""
    return max(b for b in range(1, HEAD_BLOCK + 1) if heads % b == 0)


def sizes(config: dict, kind: str) -> dict:
    """One kind of attention layer's sizes: the ``swa_`` keys for a window
    layer."""
    pre = "swa_" if kind == WINDOW else ""
    return {name: config[pre + key] for name, key in (
        ("heads", "num_attention_heads"), ("q_rank", "q_lora_rank"),
        ("rank", "kv_lora_rank"), ("nope", "qk_nope_head_dim"),
        ("rope", "qk_rope_head_dim"), ("v", "v_head_dim"),
        ("theta", "rope_theta"), ("gate", "attention_gate_type"))}


def selection(u, c_q, p, config: dict, theta: float):
    """``[t, t]`` bool: the keys each query of a full layer attends."""
    s = u.shape[0]
    j, d = config["index_n_heads"], config["index_head_dim"]
    width = config["qk_rope_head_dim"]
    q_i = _rope(_mm(c_q, p["index_q_proj"]["kernel"]).reshape(s, j, d),
                theta, width)
    k_i = _mm(u, p["index_k_proj"]["kernel"])
    mean = k_i.mean(-1, keepdims=True)
    var = jnp.square(k_i - mean).mean(-1, keepdims=True)
    k_i = ((k_i - mean) / jnp.sqrt(var + config["rms_norm_eps"])
           * p["index_k_norm"]["scale"] + p["index_k_norm"]["bias"])
    k_i = _rope(k_i[:, None], theta, width)[:, 0]
    w = _mm(u, p["index_w_proj"]["kernel"])
    block = _head_block(j)

    def partial(args):
        q_blk, w_blk = args                   # [block, t, d], [block, t]
        dots = jnp.einsum("jtd,sd->jts", q_blk, k_i, precision=_HIGHEST)
        return jnp.einsum("jts,jt->ts", jax.nn.relu(dots), w_blk,
                          precision=_HIGHEST)

    scores = jax.lax.map(partial, (
        q_i.transpose(1, 0, 2).reshape(j // block, block, s, d),
        w.T.reshape(j // block, block, s))).sum(0)
    scores = scores * j ** -0.5 * d ** -0.5
    at = jnp.arange(s)
    causal = at[None] <= at[:, None]
    values, chosen = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                                   min(config["index_topk"], s))
    return jnp.zeros((s, s), bool).at[at[:, None], chosen].set(
        values > -jnp.inf)


def attention(u, p, config: dict, kind: str):
    """``u [s, hidden]`` (normed) through one latent-attention layer."""
    p = _f32(p)
    a = sizes(config, kind)
    heads, rank, nope, dr, dv = (a["heads"], a["rank"], a["nope"], a["rope"],
                                 a["v"])
    s, hidden = u.shape
    eps = config["rms_norm_eps"]
    r_q = r_kv = 1.0
    if config["apply_mla_qkv_lora_rescale"]:
        r_q, r_kv = (hidden / a["q_rank"]) ** 0.5, (hidden / rank) ** 0.5
    c_q = r_q * _rms_norm(_mm(u, p["q_a_proj"]["kernel"]),
                          p["q_a_norm"]["scale"], eps)
    q = _mm(c_q, p["q_b_proj"]["kernel"]).reshape(s, heads, nope + dr)
    q = jnp.concatenate(
        [q[..., :nope], _rope(q[..., nope:], a["theta"], dr)], axis=-1)
    kv_a = _mm(u, p["kv_a_proj"]["kernel"])
    c_kv = r_kv * _rms_norm(kv_a[:, :rank], p["kv_a_norm"]["scale"], eps)
    k_r = _rope(kv_a[:, None, rank:], a["theta"], dr)      # [s, 1, rope]
    at = jnp.arange(s)
    if kind == FULL:
        mask = selection(u, c_q, p, config, a["theta"])
    else:
        mask = ((at[None] <= at[:, None])
                & (at[None] > at[:, None] - config["sliding_window_size"]))
    block = _head_block(heads)
    scale = (nope + dr) ** -0.5

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
    ctx = ctx.transpose(1, 0, 2, 3).reshape(s, heads, dv)
    if a["gate"] == "headwise":
        ctx = ctx * jax.nn.sigmoid(_mm(u, p["gate_proj"]["kernel"]))[..., None]
    return _mm(ctx.reshape(s, heads * dv), p["o_proj"]["kernel"])


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


def gated_moe(u, p, config: dict, *, held: int = 0, shared: bool = True):
    """``u [s, hidden]`` through the expert layer's share of the experts
    ``[held, held + n)``, ``n`` read from the tree.  ``shared`` False leaves
    the shared expert out (the shares-add-up test counts it once)."""
    weights = route(u, p["router_kernel"], p["router_bias"], config)
    n = p["experts_gate"].shape[0]
    out = jnp.zeros_like(u)
    for lo in range(0, n, EXPERT_BLOCK):
        hi = min(lo + EXPERT_BLOCK, n)
        out = out + _expert_block(
            u, p["experts_gate"][lo:hi], p["experts_up"][lo:hi],
            p["experts_down"][lo:hi], weights[:, held + lo:held + hi])
    if shared:
        out = out + _shared(u, {k: p[f"shared_{k}"]
                                for k in ("gate", "up", "down")})
    return out


@jax.jit
def _shared(u, p):
    return gated_mlp(u, {f"{k}_proj": v for k, v in p.items()})


class _Frozen(dict):
    """The config's numbers and strings, hashable, so that one jitted layer
    function serves every layer of a kind."""

    def __init__(self, config):
        super().__init__({k: v for k, v in config.items()
                          if isinstance(v, (int, float, str, type(None)))})

    def __hash__(self):
        return hash(tuple(sorted(self.items(), key=lambda kv: kv[0])))


_attention = jax.jit(attention, static_argnames=("config", "kind"))
_gated_mlp = jax.jit(gated_mlp)


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


def mlp_out(h, layer, i: int, config: dict, *, held: int = 0):
    """Layer ``i``'s MLP on its normed rows ``h``."""
    if i < config["first_k_dense_replace"]:
        return _gated_mlp(h, layer["mlp"])
    return gated_moe(h, layer["mlp"], config, held=held)


def logits_at(params, ids, positions, config: dict, *, held: int = 0):
    """Next-token logits ``[len(positions), vocab held]`` of the causal
    forward over one sequence ``ids [s]``, at the given positions."""
    with jax.default_matmul_precision("highest"):
        p = params["params"]
        x = embed(params, ids)
        for i, kind in enumerate(config["layer_types"]):
            layer = p[f"layers_{i}"]
            x = x + attention_out(x, layer, config, kind)
            x = x + mlp_out(
                normed(x, layer["post_attention_layernorm"], config), layer,
                i, config, held=held)
        return _head(x[jnp.asarray(positions)], p["norm"]["scale"],
                     p["lm_head"], eps=config["rms_norm_eps"])
