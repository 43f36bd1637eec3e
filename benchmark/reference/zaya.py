"""Plain float32 forward of the ZAYA1 architecture (``model_type`` ``zaya``:
Zyphra/ZAYA1-8B) in ``jax.numpy``: no kernels, no cache, no tails, no
batching, no bf16, K and V repeated to the query heads and scored a block
of query rows at a time, every expert computed densely and weighted (zero
for the unchosen).  It reads the system's parameter tree and upcasts one
layer - in its experts one block of them, in the head one block of the
vocabulary - at a time, so that no float32 copy of the model exists.

The equations (keys are the published config's; ``x`` in R^hidden_size;
``H = num_attention_heads``, ``G = num_key_value_heads``, ``d = head_dim``,
``T0 = cca_time0``, ``T1 = cca_time1``):

Pre-norm, RMSNorm (``rms_norm_eps``) before the attention and before the
experts, a residual after each; final RMSNorm; the head is the embedding
(``tie_word_embeddings``); no bias but the convolutions'.

Compressed convolutional attention (CCA), every layer, position ``t``::

    a_t = RMSNorm(x_t)
    q~_t = a_t W_q [H x d];   k~_t = a_t W_k [G x d]
    v_t = [a_t W_v0 ; a_{t-1} W_v1]      heads 0 .. G/2 - 1 of this token,
                                         the others of the token before
    u_t = [q~_t ; k~_t]                                  (H + G) d channels
    c_t = sum_{j<T0} w0_j * u_{t-T0+1+j} + b0            depthwise
    d_t^h = sum_{j<T1} W1_j^h c_{t-T1+1+j}^h + b1^h      d -> d in head h
    m_t^g = (mean over the query heads i of group g of q~_t^i + k~_t^g) / 2
    q_t^i = d_t^i + m_t^{g(i)};   k_t^g = d_t^{H+g} + m_t^g
    q^ = q / |q|,  k^ = k / |k|                          over d
    rope (rotate-half, rope_theta) on the first partial_rotary_factor x d
    p = softmax_{tau <= t}(s_i q^_t^i . k^_tau^g)         float32
    o^i = sum_tau p v_tau^g;   x <- x + [o^0 .. o^{H-1}] W_o

with zeros for ``a``, ``u`` and ``c`` before position 0; query head ``i``
is in group ``i // (H / G)``.

Router and experts, every layer (``E = num_experts``)::

    b = RMSNorm(x)
    r^l = b W_d^l                                   hidden -> router_hidden_size
    rbar^l = r^l + gamma^l rbar^{l-1};   rbar = r at the first layer
    p = softmax(gelu(gelu(rbar W_1) W_2) W_3)        float32, exact gelu
    e* = argmax(p + beta)                            beta: balancing bias
    x <- x + p_e* W^down_e* (silu(W^gate_e* b) * W^up_e* b)

Choices the config does not fix, each under ``assumed`` in
``configs/zaya1-8b-l10.json``: which value heads are shifted; the causal
zero padding; the second convolution's taps and its head-wise form; the
q-k mean; L2 norms and a learned per-head temperature in place of ``d^-1/2``;
rope on the first channels after the norms; a scalar ``gamma`` a layer; the
router MLP without biases and with exact gelu; ``beta`` in the choice only.

Departures from the published model, each the configuration's:

- **held experts**: the tree holds the experts ``[lo, lo + n)`` of a layer;
  the router keeps all ``E`` and its choice, and what a chosen expert held
  elsewhere would add is left out, here as in the system.  ``held`` gives
  ``lo``.  (The benchmark's cell holds all 16.)
- **depth**: the tree's layers are a pipeline stage's; the first of them
  starts the router's carry.  The cell's stage holds the published layers
  0-9, so the carry loses nothing.
- **"residual-scaled MoD"** (``described_as``): no key sizes it; not built.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST
EXPERT_BLOCK = 4           # experts upcast to float32 at a time
VOCAB_BLOCK = 16384        # rows of the head upcast to float32 at a time
QUERY_BLOCK = 2048         # query rows scored at a time


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _mm(x, w):
    return jnp.dot(x, w, precision=_HIGHEST)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _causal_conv(x, kernel, rows_of):
    """``sum_j kernel[j] (x) x_{t - T + 1 + j}`` with zeros before position
    0: ``x [s, ...]``, ``kernel [T, ...]``; ``rows_of(x_shifted, k_j)``
    applies one tap."""
    taps, s = kernel.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1,) + x.shape[1:]), x])
    return sum(rows_of(padded[j:j + s], kernel[j]) for j in range(taps))


def _rope(x, config: dict):
    """The first ``partial_rotary_factor x d`` channels of ``x [s, heads,
    d]`` rotated at positions ``0 .. s - 1``, rotate-half."""
    s, _, d = x.shape
    dim = int(d * config["partial_rotary_factor"])
    theta = config["rope_parameters"]["hybrid"]["rope_theta"]
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    r, rest = x[..., :dim], x[..., dim:]
    r1, r2 = r[..., :dim // 2], r[..., dim // 2:]
    r = r * jnp.cos(ang) + jnp.concatenate([-r2, r1], axis=-1) * jnp.sin(ang)
    return jnp.concatenate([r, rest], axis=-1)


def _unit(x):
    return x / jnp.sqrt((x * x).sum(-1, keepdims=True))


def attention(a, p, config: dict):
    """``a [s, hidden]`` (normed) through one CCA layer."""
    p = _f32(p)
    heads, nkv, d = (config["num_attention_heads"],
                     config["num_key_value_heads"], config["head_dim"])
    s = a.shape[0]
    q = _mm(a, p["q_proj"]["kernel"])
    k = _mm(a, p["k_proj"]["kernel"])
    v = _mm(a, p["v_proj"]["kernel"]).reshape(s, nkv, d)
    half = nkv // 2
    v = jnp.concatenate([v[:, :half],
                         jnp.concatenate([jnp.zeros_like(v[:1, half:]),
                                          v[:-1, half:]])], axis=1)
    u = jnp.concatenate([q, k], axis=-1)
    c = _causal_conv(u, p["conv_time0"]["kernel"],
                     lambda x, w: x * w) + p["conv_time0"]["bias"]
    dd = _causal_conv(
        c.reshape(s, heads + nkv, d), p["conv_time1"]["kernel"],
        lambda x, w: jnp.einsum("shd,hde->she", x, w, precision=_HIGHEST)
    ) + p["conv_time1"]["bias"].reshape(heads + nkv, d)
    qt, kt = q.reshape(s, heads, d), k.reshape(s, nkv, d)
    m = (qt.reshape(s, nkv, heads // nkv, d).mean(2) + kt) / 2
    qn = _rope(_unit(dd[:, :heads] + jnp.repeat(m, heads // nkv, axis=1)),
               config)
    kn = _rope(_unit(dd[:, heads:] + m), config)
    kn, v = (jnp.repeat(t, heads // nkv, axis=1) for t in (kn, v))
    ctx = jnp.concatenate([
        _attend(qn[lo:lo + QUERY_BLOCK], kn, v, p["temperature"], lo)
        for lo in range(0, s, QUERY_BLOCK)])
    return _mm(ctx.reshape(s, heads * d), p["o_proj"]["kernel"])


def _attend(q, k, v, temperature, lo: int):
    """Rows ``lo .. lo + len(q) - 1`` of the causal attention over ``k``,
    ``v [s, heads, d]`` (the keys past the last query row are masked for
    every row, so they are left out)."""
    hi = lo + q.shape[0]
    scores = jnp.einsum("thd,shd->hts", q, k[:hi], precision=_HIGHEST) * \
        temperature[:, None, None]
    mask = jnp.arange(hi)[None] <= (lo + jnp.arange(q.shape[0]))[:, None]
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), -1)
    return jnp.einsum("hts,shd->thd", probs, v[:hi], precision=_HIGHEST)


def router(h, p, carry):
    """``(probs [s, E], rbar [s, router_hidden_size])`` of the router on the
    normed rows ``h``, its carry from the layer before (None at the
    first)."""
    p = _f32(p)
    rbar = _mm(h, p["down"])
    if carry is not None:
        rbar = rbar + p["gamma"] * carry
    z = rbar
    for name in ("mlp_0", "mlp_1"):
        z = jax.nn.gelu(_mm(z, p[name]), approximate=False)
    return jax.nn.softmax(_mm(z, p["mlp_2"]), axis=-1), rbar


def margins(probs, beta):
    """``[s]``: the top-1 less the top-2 ``p + beta``: how far the reference
    is from choosing another expert."""
    top, _ = jax.lax.top_k(probs + beta.astype(jnp.float32), 2)
    return top[:, 0] - top[:, 1]


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


def experts(h, p, weights, *, held: int = 0):
    """``h [s, hidden]`` through the layer's share of the experts ``[held,
    held + n)``, ``weights [s, E]`` zero for the unchosen."""
    n = p["experts_gate"].shape[0]
    out = jnp.zeros_like(h)
    for lo in range(0, n, EXPERT_BLOCK):
        hi = min(lo + EXPERT_BLOCK, n)
        out = out + _expert_block(
            h, p["experts_gate"][lo:hi], p["experts_up"][lo:hi],
            p["experts_down"][lo:hi], weights[:, held + lo:held + hi])
    return out


def route_weights(probs, beta):
    """``[s, E]``: ``p_e*`` at each row's ``e* = argmax(p + beta)``, zero
    elsewhere."""
    chosen = jnp.argmax(probs + beta.astype(jnp.float32), axis=-1)
    return jnp.where(jnp.arange(probs.shape[1])[None] == chosen[:, None],
                     probs, 0.0)


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


@functools.partial(jax.jit, static_argnames="eps")
def _final(x, scale, *, eps):
    return _rms_norm(x, scale.astype(jnp.float32), eps)


@jax.jit
def _head(x, table):
    """``x [n, hidden] @ table.T`` a block of the vocabulary at a time: a
    float32 copy of the whole table (2.1 GB at the published size) never
    stands beside the weights."""
    vocab = table.shape[0]
    block = max(b for b in range(1, min(VOCAB_BLOCK, vocab) + 1)
                if vocab % b == 0)
    out = jax.lax.map(lambda rows: _mm(x, rows.astype(jnp.float32).T),
                      table.reshape(vocab // block, block, -1))
    return out.transpose(1, 0, 2).reshape(x.shape[0], vocab)


def embed(params, ids):
    return params["params"]["embed_tokens"]["embedding"][
        jnp.asarray(ids)].astype(jnp.float32)


def normed(x, scale, config: dict):
    """The rows a sublayer reads: its RMSNorm of the residual."""
    return _rms_norm(x, scale["scale"].astype(jnp.float32),
                     config["rms_norm_eps"])


def attention_out(x, layer, config: dict):
    """One layer's CCA on the residual ``x [s, hidden]``."""
    return _attention(normed(x, layer["input_layernorm"], config),
                      layer["self_attn"], config=_Frozen(config))


def final_rows(x, params, config: dict):
    """The rows the head reads: the final RMSNorm of ``x``."""
    return _final(x, params["params"]["norm"]["scale"],
                  eps=config["rms_norm_eps"])


def walk(params, ids, config: dict, *, held: int = 0, visit=None):
    """The residual after every layer of the causal forward over one
    sequence ``ids [s]``; ``visit(i, h, probs, beta)`` sees each layer's
    router rows, its probabilities and its balancing bias."""
    p = params["params"]
    x = embed(params, ids)
    carry = None
    for i in range(config["num_hidden_layers"]):
        layer = p[f"layers_{i}"]
        x = x + attention_out(x, layer, config)
        h = normed(x, layer["post_attention_layernorm"], config)
        probs, carry = router(h, layer["router"], carry)
        beta = layer["router"]["balance_bias"]
        if visit is not None:
            visit(i, h, probs, beta)
        x = x + experts(h, layer["mlp"], route_weights(probs, beta),
                        held=held)
    return x


def logits_at(params, ids, positions, config: dict, *, held: int = 0,
              with_margins: bool = False):
    """Next-token logits ``[len(positions), vocab]`` of the causal forward
    over one sequence ``ids [s]``, at the given positions; with
    ``with_margins`` also each position's routing margin in each layer,
    ``[len(positions), layers]``."""
    at = jnp.asarray(positions)
    found = []
    with jax.default_matmul_precision("highest"):
        x = walk(params, ids, config, held=held,
                 visit=lambda i, h, probs, beta: found.append(
                     margins(probs[at], beta)))
        logits = _head(final_rows(x[at], params, config),
                       params["params"]["embed_tokens"]["embedding"])
    if not with_margins:
        return logits
    return logits, jnp.stack(found, axis=-1)
