"""Openfold attention perf evidence (VERDICT r2 item 9).

Measures the Evoformer attention shapes from the reference's CanSchTriMHA
table (mha.py:36-88 — row-attention [1, 128, 8, 256, 32]-class shapes with
pair bias + mask): the Pallas pair-bias flash kernel (called DIRECTLY, so
the numbers stay reproducible regardless of attention_core's size gate)
against the materialized one-jit XLA path, on the real chip.

Prints one JSON line with per-shape times and the XLA/pallas ratio.
Recorded r3 result: XLA wins at Evoformer scale (4.5 vs 89 ms at s=256 —
tiny tiles drown in per-step grid overhead), which is why attention_core
routes to the kernel only for s >= 1024.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

# CanSchTriMHA-class Evoformer shapes: (batch, rows, heads, seq, head_dim)
SHAPES = [
    (1, 128, 8, 256, 32),    # MSA row attention
    (1, 256, 4, 128, 64),    # triangle attention-ish
]


def time_fn(fn, *args, iters=10):
    """Marginal over chained async dispatches; one scalar readback forces
    the queue."""

    def run(k):
        out = None
        for _ in range(k):
            out = fn(*args)
        return float(jax.tree.leaves(out)[0].ravel()[0])

    run(1)
    t0 = time.perf_counter(); run(iters); t1 = time.perf_counter()
    run(2 * iters); t2 = time.perf_counter()
    return ((t2 - t1) - (t1 - t0)) / iters


def main():
    from apex_tpu.ops.pair_bias_attention import pair_bias_flash_attention

    rng = np.random.default_rng(0)
    rows = []
    for (b, r, h, s, d) in SHAPES:
        q = jnp.asarray(rng.standard_normal((b, r, h, s, d)),
                        jnp.bfloat16) / d ** 0.5
        k = jnp.asarray(rng.standard_normal((b, r, h, s, d)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((b, r, h, s, d)), jnp.bfloat16)
        bias = jnp.asarray(rng.standard_normal((b, 1, h, s, s)), jnp.bfloat16)
        mask = jnp.asarray(rng.random((b, r, 1, 1, s)) > 0.1)

        def pallas_direct(q, k, v, m, bi):
            # [b, r, ...] -> rows-major [r*b, h, s, d] (kernel contract)
            to_flat = lambda x: x.transpose(1, 0, 2, 3, 4).reshape(
                r * b, h, s, d)
            kv = (m.astype(bool)[:, :, 0, 0, :].transpose(1, 0, 2)
                  .reshape(r * b, s))
            return pair_bias_flash_attention(
                to_flat(q), to_flat(k), to_flat(v), bi[:, 0], kv)

        def materialized(q, k, v, m, bi):
            sc = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32)
            sc = sc + bi.astype(jnp.float32)
            sc = jnp.where(m.astype(bool), sc, -1e9)
            p = jax.nn.softmax(sc, axis=-1)
            return jnp.einsum("...qk,...kd->...qd", p.astype(q.dtype), v)

        tf = time_fn(jax.jit(pallas_direct), q, k, v, mask, bias)
        tm = time_fn(jax.jit(materialized), q, k, v, mask, bias)
        rows.append({
            "shape": [b, r, h, s, d],
            "pallas_ms": round(tf * 1e3, 3),
            "xla_materialized_ms": round(tm * 1e3, 3),
            "xla_over_pallas": round(tm / tf, 3),
        })
    print(json.dumps({"bench": "openfold_attention", "rows": rows,
                      "device": str(jax.devices()[0].device_kind)}))


if __name__ == "__main__":
    main()
