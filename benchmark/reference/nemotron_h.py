"""Plain float32 forward of the Nemotron-H architecture (``model_type``
``nemotron_h``: NVIDIA-Nemotron-3-Super-120B-A12B) in ``jax.numpy``: no
kernels, no cache, no batching, no bf16, a sequential scan for Mamba-2 and a
dense loop over the experts.  It reads the system's parameter tree and
upcasts one layer - in an expert layer one block of experts - at a time, so
no float32 copy of the model ever exists.

The equations (keys are the published config's):

Pre-norm, one RMSNorm (``layer_norm_epsilon``) and one residual a layer:
``x <- x + f_i(norm_i(x))``; ``f_i`` is chosen by
``hybrid_override_pattern[i]``: ``M`` Mamba-2, ``*`` attention, ``E`` routed
experts.  Final RMSNorm, untied head.

``M``, Mamba-2 (``H = mamba_num_heads``, ``P = mamba_head_dim``, ``d_inner =
H P``, ``G = n_groups``, ``N = ssm_state_size``, ``K = conv_kernel``, no
projection bias, a convolution bias)::

    [z | xBC | dt] = u W_in             widths d_inner | d_inner + 2 G N | H
    xBC <- silu(causal depthwise conv_K(xBC) + b)
    xBC  = x [H, P] | B [G, N] | C [G, N]
    dt  <- softplus(dt + dt_bias),  A = -exp(A_log)          one a head
    S_t  = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T              S is [P, N]
    y_t  = S_t C_t + D x_t          head h reads group h // (H / G)
    y   <- RMSNorm_grouped(y * silu(z))   groups of d_inner / G, one weight
    out  = y W_out

``*``, attention: ``num_attention_heads`` query / ``num_key_value_heads`` KV
heads of ``head_dim``, no bias, causal, softmax scale ``1 / sqrt(head_dim)``,
no positional embedding.

``E``, latent routed experts (``n_group = topk_group = 1``: group limiting is
the identity)::

    s = sigmoid(x W_r)                      float32, all published experts
    chosen = the num_experts_per_tok largest of s + b_corr
    w_e = routed_scaling_factor * s_e / (sum of the chosen s + 1e-20)
    l = x W_down                            hidden -> moe_latent_size
    o_e = relu(l W1_e)^2 W2_e               in the latent space, no gate
    routed = (sum over chosen e of w_e o_e) W_up
    out = routed + relu(x W1_s)^2 W2_s      the shared expert, on x

Departures from the published model, each the configuration's
(``configs/nemotron3-super-ep4-l11.json``):

- **held experts**: the parameter tree holds the experts ``[lo, lo + n)`` of
  a layer (``experts_w1 [n, latent, width]``); the router stays as wide as
  published and keeps its top-k and its weights, and what the chosen experts
  held elsewhere would add is left out, here as in the system
  (``model-configs`` guide, section 4).  ``held`` gives ``lo``.
- **sliced vocabulary**: the embedding and the head have the rows the tree
  holds; ids and logits are over the slice.
- **no multi-token-prediction module**: it adds nothing to these logits.
- **no rope**: Nemotron-H attention applies no positional embedding (the
  Mamba layers carry order); ``rope_theta`` is unused.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST
EXPERT_BLOCK = 16          # experts upcast to float32 at a time


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _mm(x, w):
    return jnp.dot(x, w, precision=_HIGHEST)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def mamba2(u, p, config: dict, state=None):
    """``u [s, hidden]`` through one Mamba-2 mixer, one step at a time.
    ``state = (S [H, P, N], tail [K - 1, conv_dim])`` is what an earlier call
    left (zeros when None); returns ``(out [s, hidden], state)``."""
    p = _f32(p)
    n_head, hd = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, n = config["n_groups"], config["ssm_state_size"]
    k = config["conv_kernel"]
    d_inner = n_head * hd
    conv_dim = d_inner + 2 * groups * n
    s = u.shape[0]
    proj = _mm(u, p["in_proj"]["kernel"])
    z, xbc, dt = jnp.split(proj, [d_inner, d_inner + conv_dim], axis=-1)
    if state is None:
        state = (jnp.zeros((n_head, hd, n), jnp.float32),
                 jnp.zeros((k - 1, conv_dim), jnp.float32))
    s0, tail = state
    padded = jnp.concatenate([tail, xbc], axis=0)
    w = p["conv1d"]["kernel"]                       # [K, conv_dim]
    conv = sum(padded[j:j + s] * w[j] for j in range(k))
    xbc_a = jax.nn.silu(conv + p["conv1d"]["bias"])
    x, b, c = jnp.split(xbc_a, [d_inner, d_inner + groups * n], axis=-1)
    x = x.reshape(s, n_head, hd)
    rep = n_head // groups
    b = jnp.repeat(b.reshape(s, groups, n), rep, axis=1)    # [s, H, N]
    c = jnp.repeat(c.reshape(s, groups, n), rep, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])                 # [s, H]
    a = -jnp.exp(p["A_log"])                                # [H]

    def step(st, row):
        x_t, b_t, c_t, dt_t = row
        st = (jnp.exp(dt_t * a)[:, None, None] * st
              + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return st, (st * c_t[:, None, :]).sum(-1)

    s1, y = jax.lax.scan(step, s0, (x, b, c, dt))
    y = y + p["D"][None, :, None] * x
    y = (y.reshape(s, d_inner) * jax.nn.silu(z)).reshape(s, groups, -1)
    y = y / jnp.sqrt((y * y).mean(-1, keepdims=True)
                     + config["layer_norm_epsilon"])
    y = y.reshape(s, d_inner) * p["norm"]["scale"]
    return _mm(y, p["out_proj"]["kernel"]), (s1, padded[-(k - 1):])


def attention(u, p, config: dict):
    """``u [s, hidden]`` through causal grouped-query attention, no rope."""
    p = _f32(p)
    n_head, n_kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    s = u.shape[0]
    q = _mm(u, p["q_proj"]["kernel"]).reshape(s, n_head, hd)
    k = _mm(u, p["k_proj"]["kernel"]).reshape(s, n_kv, hd)
    v = _mm(u, p["v_proj"]["kernel"]).reshape(s, n_kv, hd)
    k = jnp.repeat(k, n_head // n_kv, axis=1)
    v = jnp.repeat(v, n_head // n_kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=_HIGHEST) / hd ** 0.5
    mask = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    ctx = jnp.einsum("hqk,khd->qhd", probs, v, precision=_HIGHEST)
    return _mm(ctx.reshape(s, n_head * hd), p["o_proj"]["kernel"])


def router_scores(u, kernel):
    """``sigmoid(u W_r)`` ``[s, published experts]``, float32."""
    return jax.nn.sigmoid(_mm(u, kernel.astype(jnp.float32)))


def route(u, kernel, bias, config: dict):
    """Dense ``[s, published experts]`` float32 combine weights: zero but for
    each row's ``num_experts_per_tok`` chosen experts."""
    scores = router_scores(u, kernel)
    _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32),
                              config["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = (config["routed_scaling_factor"] * picked
               / (picked.sum(-1, keepdims=True) + 1e-20))
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(weights)


@jax.jit
def _expert_block(latent, w1, w2, weights):
    """``sum_e weights[:, e] * relu(latent W1_e)^2 W2_e`` over one block."""
    w1, w2 = w1.astype(jnp.float32), w2.astype(jnp.float32)
    h = _relu2(jnp.einsum("sl,elw->esw", latent, w1, precision=_HIGHEST))
    o = jnp.einsum("esw,ewl->esl", h, w2, precision=_HIGHEST)
    return jnp.einsum("esl,se->sl", o, weights, precision=_HIGHEST)


def latent_moe(u, p, config: dict, *, held: int = 0, shared: bool = True):
    """``u [s, hidden]`` through the expert layer's share of the experts
    ``[held, held + n)``, ``n`` read from the tree.  ``shared`` False leaves
    the shared expert out (the shares-add-up test counts it once)."""
    weights = route(u, p["router_kernel"], p["router_bias"], config)
    latent = _mm(u, p["latent_down"]["kernel"].astype(jnp.float32))
    n = p["experts_w1"].shape[0]
    acc = jnp.zeros_like(latent)
    for lo in range(0, n, EXPERT_BLOCK):
        hi = min(lo + EXPERT_BLOCK, n)
        acc = acc + _expert_block(latent, p["experts_w1"][lo:hi],
                                  p["experts_w2"][lo:hi],
                                  weights[:, held + lo:held + hi])
    out = _mm(acc, p["latent_up"]["kernel"].astype(jnp.float32))
    if shared:
        h = _relu2(_mm(u, p["shared_up"]["kernel"].astype(jnp.float32)))
        out = out + _mm(h, p["shared_down"]["kernel"].astype(jnp.float32))
    return out


class _Frozen(dict):
    """The config's numbers, hashable, so that one jitted layer function
    serves every layer of a kind."""

    def __init__(self, config):
        super().__init__({k: v for k, v in config.items()
                          if isinstance(v, (int, float, str))})

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


@functools.partial(jax.jit, static_argnames="config")
def _mamba2(u, p, *, config):
    return mamba2(u, p, config)[0]


_attention = jax.jit(attention, static_argnames="config")


@functools.partial(jax.jit, static_argnames="eps")
def _head(x, scale, table, *, eps):
    x = _rms_norm(x, scale.astype(jnp.float32), eps)
    return jnp.dot(x, table.astype(jnp.float32).T, precision=_HIGHEST)


def layer_out(kind: str, h, mixer, config: dict, *, held: int = 0):
    """``f_i`` of one layer on its normed rows ``h [s, hidden]``."""
    if kind == "M":
        return _mamba2(h, mixer, config=_Frozen(config))
    if kind == "*":
        return _attention(h, mixer, config=_Frozen(config))
    if kind == "E":
        return latent_moe(h, mixer, config, held=held)
    raise ValueError(f"layer kind {kind!r}: M, * or E")


def embed(params, ids):
    return params["params"]["embed_tokens"]["embedding"][
        jnp.asarray(ids)].astype(jnp.float32)


def normed(x, layer, config: dict):
    """The rows a layer's mixer reads: its one RMSNorm of the residual."""
    return _rms_norm(x, layer["norm"]["scale"].astype(jnp.float32),
                     config["layer_norm_epsilon"])


def logits_at(params, ids, positions, config: dict, *, held: int = 0):
    """Next-token logits ``[len(positions), vocab held]`` of the causal
    forward over one sequence ``ids [s]``, at the given positions."""
    with jax.default_matmul_precision("highest"):
        p = params["params"]
        x = embed(params, ids)
        for i, kind in enumerate(config["hybrid_override_pattern"]):
            layer = p[f"layers_{i}"]
            x = x + layer_out(kind, normed(x, layer, config),
                              layer["mixer"], config, held=held)
        return _head(x[jnp.asarray(positions)], p["norm_f"]["scale"],
                     p["lm_head"], eps=config["layer_norm_epsilon"])
