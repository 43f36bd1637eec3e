"""Transformer-body component timings on the real chip at bench shapes.

Small ops sit below the per-dispatch floor, so each
measurement runs chained iterations inside a jitted lax.scan (the op
output feeds the next input, defeating DCE), and the per-iter cost is the
marginal between a 2*ITERS-length scan and an ITERS-length scan — two
separately-compiled programs whose difference cancels the per-call
dispatch/readback.

Usage: python tools/layer_bench.py [attn|attn_blk|layer|ln ...]
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ITERS = 50


def _force(out):
    """A scalar readback forces the chain."""
    return float(jax.tree.leaves(out)[0].ravel()[0])


def timed(make_run, *args):
    """make_run(n) -> jit running n chained iterations.  ms/iter from the
    marginal t(2*ITERS) - t(ITERS): identical-call marginals do NOT cancel
    the per-call dispatch floor (both calls carry it), but the scan-length
    marginal does."""
    short, long_ = make_run(ITERS), make_run(2 * ITERS)
    _force(short(*args)); _force(long_(*args))  # compile both
    t0 = time.perf_counter()
    _force(short(*args))
    t1 = time.perf_counter()
    _force(long_(*args))
    t2 = time.perf_counter()
    return ((t2 - t1) - (t1 - t0)) / ITERS * 1e3


def scan_fwd(op):
    """n -> jit of n chained op applications (shapes must match)."""

    def make(n):
        @jax.jit
        def run(x):
            def body(x, _):
                return op(x), None

            y, _ = jax.lax.scan(body, x, None, length=n)
            return y

        return run

    return make


def scan_grad(loss_fn):
    """Chained grad evaluations of loss_fn(x): x_{i+1} = x_i + 1e-30*g_i."""

    def make(n):
        @jax.jit
        def run(x):
            def body(x, _):
                g = jax.grad(loss_fn)(x)
                return jax.tree.map(
                    lambda a, b: a + 1e-30 * b.astype(a.dtype), x, g), None

            y, _ = jax.lax.scan(body, x, None, length=n)
            return y

        return run

    return make


def scan_grad2(loss_fn):
    """Chained grad evaluations of loss_fn(params, x) wrt BOTH arguments —
    wgrads are ~1/3 of a training backward and must not be DCE'd."""

    def make(n):
        @jax.jit
        def run(params, x):
            def body(carry, _):
                params, x = carry
                gp, gx = jax.grad(loss_fn, argnums=(0, 1))(params, x)
                params = jax.tree.map(
                    lambda a, b: a + 1e-30 * b.astype(a.dtype), params, gp)
                x = x + 1e-30 * gx.astype(x.dtype)
                return (params, x), None

            out, _ = jax.lax.scan(body, (params, x), None, length=n)
            return out

        return run

    return make


def main():
    from apex_tpu.ops.flash_attention import flash_attention
    from apex_tpu.ops.layer_norm import fused_layer_norm_affine
    from apex_tpu.transformer.testing.standalone_transformer_lm import (
        ParallelTransformerLayer,
    )

    b, nh, s, d, hid = 8, 16, 1024, 64, 1024
    rng = np.random.default_rng(0)
    which = sys.argv[1:] or ["attn", "layer", "ln"]
    out = {}

    def qkv_of(x):
        # cheap q/k/v from one carried tensor (keeps the scan carry small)
        return x, jnp.roll(x, 1, axis=2), jnp.roll(x, 2, axis=2)

    if "attn" in which or "attn_blk" in which:
        x0 = jnp.asarray(rng.standard_normal((b, nh, s, d)) * 0.1,
                         jnp.bfloat16)
        blocks = ([(1024, 1024)] if "attn_blk" not in which
                  else [(1024, 1024), (512, 1024), (512, 512), (256, 1024)])
        for bq, bk in blocks:
            def op(x, bq=bq, bk=bk):
                q, k, v = qkv_of(x)
                return flash_attention(q, k, v, causal=True,
                                       block_q=bq, block_k=bk)

            def loss(x, bq=bq, bk=bk):
                return op(x, bq, bk).astype(jnp.float32).sum()

            key = f"attn_{bq}x{bk}"
            out[key + "_fwd_ms"] = round(timed(scan_fwd(op), x0), 3)
            out[key + "_fwdbwd_ms"] = round(timed(scan_grad(loss), x0), 3)

    if "layer" in which:
        layer = ParallelTransformerLayer(hid, nh, params_dtype=jnp.float32)
        x0 = jnp.asarray(rng.standard_normal((s, b, hid)) * 0.1, jnp.bfloat16)
        params = layer.init(jax.random.PRNGKey(0), x0)
        params = jax.tree.map(
            lambda p: p.astype(jnp.bfloat16)
            if p.dtype == jnp.float32 and p.ndim >= 2 else p, params)

        def op(x):
            return layer.apply(params, x)

        def loss(p, x):
            return layer.apply(p, x).astype(jnp.float32).sum()

        out["layer_fwd_ms"] = round(timed(scan_fwd(op), x0), 3)
        out["layer_fwdbwd_ms"] = round(
            timed(scan_grad2(loss), params, x0), 3)
        out["layer_model_fwdbwd_ms"] = round(out["layer_fwdbwd_ms"] * 24, 1)

    if "ln" in which:
        x0 = jnp.asarray(rng.standard_normal((s * b, hid)), jnp.bfloat16)
        w = jnp.ones((hid,), jnp.float32)
        bias = jnp.zeros((hid,), jnp.float32)

        def op(x):
            return fused_layer_norm_affine(x, w, bias, (hid,)).astype(x.dtype)

        out["ln_fwd_ms"] = round(timed(scan_fwd(op), x0), 3)

    print(json.dumps(out))


if __name__ == "__main__":
    main()
