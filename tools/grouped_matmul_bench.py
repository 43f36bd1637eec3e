"""Grouped matrix product over the experts a chip holds: ``jax.lax.ragged_dot``
against the ``gmm`` kernel that ships with jax, at the shapes of the
``nemotron3-super-ep4-l11`` cell (``transformer/moe.py::LatentMoE``): 128
held experts of 512, top-22, latent 1024 -> 2688 -> 1024, relu squared.

    chiprun -- python tools/grouped_matmul_bench.py

Rows are the token-expert pairs of ``tokens`` tokens (``tokens x 22``, the
static bound: nothing is dropped), sorted by expert; the pairs that land on
experts held elsewhere sort to the end and belong to no group here.  One
line of JSON a variant: milliseconds a call of both projections, and the
bytes of the touched experts' matrices over that time.
"""

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

EXPERTS, HELD, TOP_K, LATENT, WIDTH = 512, 128, 22, 1024, 2688


def routing(tokens: int, seed: int):
    """Group sizes of the held experts (and the overflow group) when every
    token chooses 22 distinct experts of 512 uniformly."""
    rng = np.random.default_rng(seed)
    chosen = np.stack([rng.choice(EXPERTS, TOP_K, replace=False)
                       for _ in range(tokens)])
    counts = np.bincount(chosen[chosen < HELD], minlength=HELD)
    return counts.astype(np.int32)


def timed(fn, *args, iters=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / iters


def main():
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    key = jax.random.key(0)
    w1 = 0.02 * jax.random.normal(key, (HELD, LATENT, WIDTH), jnp.bfloat16)
    w2 = 0.02 * jax.random.normal(key, (HELD, WIDTH, LATENT), jnp.bfloat16)
    for tokens in (64, 512):
        m = tokens * TOP_K
        sizes = routing(tokens, tokens)
        touched = int((sizes > 0).sum())
        need = touched * 2 * LATENT * WIDTH * 2
        lhs = jax.random.normal(key, (m, LATENT), jnp.bfloat16)
        # the kernel zeroes the rows of groups it does not hold when it is
        # given more group sizes than matrices: the overflow group is last
        sizes_all = jnp.asarray(np.append(sizes, m - sizes.sum()), jnp.int32)
        sizes_held = jnp.asarray(sizes, jnp.int32)

        def mlp(product):
            # the matrices are arguments: closed over, they would be baked
            # into the program as 1.4 GB constants each
            def f(x, g, w1, w2):
                h = product(x, w1, g)
                h = jnp.square(jax.nn.relu(h)).astype(jnp.bfloat16)
                return product(h, w2, g)
            return jax.jit(f)

        variants = {"ragged_dot": (mlp(lambda x, w, g: jax.lax.ragged_dot(
            x, w, g, preferred_element_type=jnp.float32)), sizes_held)}
        tms = (32, 128) if tokens == 64 else (128, 512)
        for tm in tms:
            for tk, tn in ((128, 128), (512, 512), (1024, 896)):
                def product(x, w, g, tm=tm, tk=tk, tn=tn):
                    k, n = w.shape[1], w.shape[2]
                    return gmm(x, w, g, preferred_element_type=jnp.float32,
                               tiling=(tm, min(tk, k), min(tn, n)))
                variants[f"gmm_{tm}_{tk}_{tn}"] = (mlp(product), sizes_all)
        for name, (fn, g) in variants.items():
            line = {"tokens": tokens, "rows": m, "touched": touched,
                    "pairs_here": int(sizes.sum()), "variant": name}
            try:
                ms = timed(fn, lhs, g, w1, w2)
                line.update(ms=ms, touched_gb_s=need / ms / 1e6)
            except Exception as e:  # a tiling the compiler refuses
                line.update(error=f"{type(e).__name__}: {str(e)[:200]}")
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
