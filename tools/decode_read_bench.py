"""The decode step's K/V append + read alone, every layer of a cell's cache:
the Pallas kernel that reads the stored buffers in place
(``ops/cached_decode_attention.py``) against the reference read over a
layer's view (``serving/kv_cache.py::cached_attention``), at the shapes of
the two serving cells.

    chiprun -- python tools/decode_read_bench.py

A step is what ``decode_attend`` does a layer, chained over the layers on
a donated cache: one row scatter a buffer, then the read.  Lengths are
drawn like ``chat-closed``'s (log-normal prompts, a uniform share of a
log-normal output: ~380 live rows of 2,048 a slot).  One line of JSON a
variant: milliseconds a step, the bytes of the live K/V rows over that
time, and the largest difference from the reference read's result.
``--rehearse`` is the tiny CPU run of the same lines (set
``APEX_TPU_KERNELS=interpret``); no time means anything there.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from apex_tpu.ops import cached_decode_attention as cda
from apex_tpu.serving.kv_cache import (
    append_token,
    decode_attention,
    decode_read,
    KVCache,
)

HD, MAX_LEN = 128, 2048
# layers, slots, query heads, kv heads
CELLS = {"mistral-7b-l16": (16, 16, 32, 8),
         "nemotron3-super-ep4-l11": (1, 64, 32, 2)}


def lengths(slots: int, seed: int):
    rng = np.random.default_rng(seed)
    prompt = np.clip(np.exp(rng.normal(np.log(256), 0.9, slots)), 32, 1536)
    output = np.clip(np.exp(rng.normal(np.log(96), 0.7, slots)), 16, 256)
    return (prompt + rng.uniform(0, 1, slots) * output).astype(np.int32)


def make_step(layers: int, kernel: bool):
    def step(cache, q, k_new, v_new, position):
        qt = q
        for layer in range(layers):
            cache = append_token(cache, layer, k_new, v_new, position)
            if kernel:
                ctx = cda.cached_decode_attention(qt, cache.k, cache.v,
                                                  layer, position)
            else:
                kc, vc = decode_read(cache, layer)
                ctx = decode_attention(qt, kc, vc, position)
            # the next layer's query hangs on this layer's result
            qt = q + 1e-3 * ctx
        return cache, ctx
    return jax.jit(step, donate_argnums=0)


def timed(fn, cache, *args, iters=30):
    cache, out = fn(cache, *args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        cache, out = fn(cache, *args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / iters, cache, out


def main():
    dev = jax.devices()[0]
    rehearse = "--rehearse" in sys.argv[1:]
    max_len = 256 if rehearse else MAX_LEN
    for name, (layers, slots, heads, nkv) in CELLS.items():
        if rehearse:
            layers, slots = min(layers, 2), 3
        kq, kk, kv = jax.random.split(jax.random.key(0), 3)
        shape = (layers, slots, max_len, nkv, HD)
        cache = KVCache(k=jax.random.normal(kk, shape, jnp.bfloat16),
                        v=jax.random.normal(kv, shape, jnp.bfloat16),
                        lengths=jnp.zeros((slots,), jnp.int32))
        q = jax.random.normal(kq, (slots, heads, 1, HD), jnp.bfloat16)
        k_new = jax.random.normal(kq, (slots, nkv, HD), jnp.bfloat16)
        v_new = jax.random.normal(kk, (slots, nkv, HD), jnp.bfloat16)
        position = jnp.asarray(lengths(slots, 1) % max_len)
        live = int(np.asarray(position).sum()) + slots
        need = layers * live * 2 * nkv * HD * 2
        ms, cache, ref = timed(make_step(layers, False), cache, q, k_new,
                               v_new, position)
        print(json.dumps({"cell": name, "read": "reference",
                          "ms": round(ms, 4),
                          "live_rows_a_slot": round(live / slots, 1),
                          "device": dev.device_kind}), flush=True)
        # the kernel at its own tile and at twice and half it; then what a
        # block costs and what a lane costs: every lane full, every lane
        # one row long
        default = cda.COLUMNS
        runs = [(c, "cell", position) for c in (default, 2 * default,
                                                default // 2)]
        runs += [(default, label, jnp.full((slots,), rows, jnp.int32))
                 for label, rows in (("full", max_len - 1), ("empty", 0))]
        for columns, lanes, at in runs:
            cda.COLUMNS = columns
            ms, cache, out = timed(make_step(layers, True), cache, q, k_new,
                                   v_new, at)
            line = {"cell": name, "read": "kernel", "columns": columns,
                    "block": cda.block_rows(max_len, nkv), "lanes": lanes,
                    "ms": round(ms, 4)}
            if lanes == "cell":
                line["live_GBps"] = round(need / ms / 1e6, 1)
                line["max_abs_diff"] = float(jnp.max(jnp.abs(
                    out.astype(jnp.float32) - ref.astype(jnp.float32))))
            print(json.dumps(line), flush=True)
        cda.COLUMNS = default
        del cache
    return 0


if __name__ == "__main__":
    sys.exit(main())
