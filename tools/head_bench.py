"""Isolated LM-head benchmark on the real chip: fused kernel vs
materialized XLA path, fwd+bwd, at the GPT-2 bench shape."""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def marginal(run, n=16):
    """run(k) dispatches k calls and reads ONE scalar back (async queue —
    a per-call blocking readback would time the round trip, not the
    chip)."""
    run(1)
    t0 = time.perf_counter(); run(n); t1 = time.perf_counter()
    run(2 * n); t2 = time.perf_counter()
    return ((t2 - t1) - (t1 - t0)) / n


def main():
    from apex_tpu.ops.fused_lm_head import (fused_lm_head_loss,
                                            lm_head_loss_reference)

    T, H, V = 8192, 1024, 50304
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((T, H)) * 0.02, jnp.bfloat16)
    e = jnp.asarray(rng.standard_normal((V, H)) * 0.02, jnp.bfloat16)
    lab = jnp.asarray(rng.integers(0, V, (T,)), jnp.int32)

    variants = {
        "fused": lambda h, e: fused_lm_head_loss(h, e, lab).mean(),
        "materialized": lambda h, e: lm_head_loss_reference(h, e, lab).mean(),
    }
    which = sys.argv[1:] or list(variants)
    out = {}
    for name in which:
        f = variants[name]
        grad = jax.jit(jax.grad(f, argnums=(0, 1)))
        fwd = jax.jit(f)

        def run_fwd(k):
            o = None
            for _ in range(k):
                o = fwd(h, e)
            return float(o)

        def run_bwd(k):
            dh = None
            for _ in range(k):
                dh, _ = grad(h, e)
            return float(dh.ravel()[0])

        out[name + "_fwd_ms"] = round(marginal(run_fwd) * 1e3, 2)
        out[name + "_fwdbwd_ms"] = round(marginal(run_bwd) * 1e3, 2)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
