"""Plain float32 GPT-2 forward and per-token loss in ``jax.numpy``: no
kernels, no fusion helpers, no bf16.  It reads the system's parameter tree
(so both sides hold the same weights) and upcasts one layer at a time, so
no float32 copy of the whole model ever exists.

Follows the GPT-2 description (pre-LN blocks, learned positions, tanh GELU,
tied head).  One departure, shared with the system: the fused
query-key-value projection is laid out per head (``[q_i k_i v_i]`` for
head ``i``, Megatron's layout), not as three contiguous blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_EPS = 1e-5
_HIGHEST = jax.lax.Precision.HIGHEST


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def _layer_norm(x, p):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + _EPS) * p["scale"] + p["bias"]


def _linear(x, p):
    return jnp.dot(x, p["kernel"], precision=_HIGHEST) + p["bias"]


@functools.partial(jax.jit, static_argnames="n_head")
def _layer(x, p, *, n_head):
    p = _f32(p)
    s, h = x.shape
    hd = h // n_head
    a = _layer_norm(x, p["input_layernorm"])
    qkv = _linear(a, p["self_attention"]["query_key_value"])
    q, k, v = jnp.split(qkv.reshape(s, n_head, 3 * hd), 3, axis=-1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=_HIGHEST) / hd ** 0.5
    mask = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    ctx = jnp.einsum("hqk,khd->qhd", probs, v, precision=_HIGHEST)
    x = x + _linear(ctx.reshape(s, h), p["self_attention"]["dense"])
    m = _layer_norm(x, p["post_attention_layernorm"])
    m = jax.nn.gelu(_linear(m, p["mlp"]["dense_h_to_4h"]), approximate=True)
    return x + _linear(m, p["mlp"]["dense_4h_to_h"])


@jax.jit
def _embed(emb, ids):
    emb = _f32(emb)
    return (emb["word_embeddings"]["embedding"][ids]
            + emb["position_embeddings"][:ids.shape[0]])


@jax.jit
def _head_losses(x, final_ln, table, labels):
    x = _layer_norm(x, _f32(final_ln))
    logits = jnp.dot(x, table.astype(jnp.float32).T, precision=_HIGHEST)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jax.nn.logsumexp(logits, axis=-1) - picked


def token_losses(params, ids, labels, *, n_head: int):
    """Cross-entropy of each position of one sequence: ``ids``, ``labels``
    ``[s]`` int32; ``params`` the system's flax tree."""
    with jax.default_matmul_precision("highest"):
        lm = params["params"]["language_model"]
        x = _embed(lm["embedding"], ids)
        stack = lm["transformer"]
        n_layer = sum(1 for k in stack if k.startswith("layer_"))
        for i in range(n_layer):
            x = _layer(x, stack[f"layer_{i}"], n_head=n_head)
        return _head_losses(
            x, stack["final_layernorm"],
            lm["embedding"]["word_embeddings"]["embedding"], labels)
