"""Plain float32 Llama-style forward (the Mistral-7B architecture: RMSNorm,
rotary positions in the rotate-half convention, grouped-query causal
attention, SwiGLU, untied head) in ``jax.numpy``: no kernels, no cache, no
batching, no bf16.  It reads the system's parameter tree and upcasts one
layer at a time, so no float32 copy of the model ever exists.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """``x [s, heads, hd]`` rotated at positions ``0..s-1``."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * jnp.cos(ang) + rotated * jnp.sin(ang)


def _mm(x, p):
    return jnp.dot(x, p["kernel"], precision=_HIGHEST)


@functools.partial(jax.jit,
                   static_argnames=("n_head", "n_kv", "theta", "eps"))
def _layer(x, p, *, n_head, n_kv, theta, eps):
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    s, h = x.shape
    hd = h // n_head
    a = _rms_norm(x, p["input_layernorm"]["scale"], eps)
    att = p["self_attn"]
    q = _rope(_mm(a, att["q_proj"]).reshape(s, n_head, hd), theta)
    k = _rope(_mm(a, att["k_proj"]).reshape(s, n_kv, hd), theta)
    v = _mm(a, att["v_proj"]).reshape(s, n_kv, hd)
    k = jnp.repeat(k, n_head // n_kv, axis=1)
    v = jnp.repeat(v, n_head // n_kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=_HIGHEST) / hd ** 0.5
    mask = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    ctx = jnp.einsum("hqk,khd->qhd", probs, v, precision=_HIGHEST)
    x = x + _mm(ctx.reshape(s, h), att["o_proj"])
    m = _rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
    mlp = p["mlp"]
    m = jax.nn.silu(_mm(m, mlp["gate_proj"])) * _mm(m, mlp["up_proj"])
    return x + _mm(m, mlp["down_proj"])


@functools.partial(jax.jit, static_argnames="eps")
def _head(x, scale, table, *, eps):
    x = _rms_norm(x, scale.astype(jnp.float32), eps)
    return jnp.dot(x, table.astype(jnp.float32).T, precision=_HIGHEST)


def logits_at(params, ids, positions, *, n_head: int, n_kv: int,
              theta: float, eps: float):
    """Next-token logits ``[len(positions), vocab]`` of the causal forward
    over one sequence ``ids [s]``, at the given positions."""
    with jax.default_matmul_precision("highest"):
        p = params["params"]
        x = p["embed_tokens"]["embedding"][jnp.asarray(ids)].astype(
            jnp.float32)
        n_layer = sum(1 for k in p if k.startswith("layers_"))
        for i in range(n_layer):
            x = _layer(x, p[f"layers_{i}"], n_head=n_head, n_kv=n_kv,
                       theta=theta, eps=eps)
        head = p["lm_head"] if "lm_head" in p else \
            p["embed_tokens"]["embedding"]
        return _head(x[jnp.asarray(positions)], p["norm"]["scale"], head,
                     eps=eps)
