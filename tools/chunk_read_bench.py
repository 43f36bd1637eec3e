"""A prompt chunk's K/V write + read alone, every layer of a cell's cache:
the three reads ``serving/kv_cache.py::prefill_attend`` chooses from - the
Pallas kernel over the visible blocks (``ops/kv_chunk_attention.py``), the
full masked extent (``cached_attention``) and the same walk as a loop
(``_kv_chunk_read``) - at the shapes of the two short-cache serving cells,
for every default bucket and for one, two and four visible key blocks.

    chiprun -- python tools/chunk_read_bench.py

A call is what ``prefill_attend`` does a layer, chained over the layers on
a donated cache: the chunk's rows scattered into one slot, then the read.
One line of JSON a (cell, bucket, offset, read): milliseconds a call and,
for the kernel, the largest difference from the full-extent read's result.
The crossover the comment beside ``_FULL_READ_BYTES`` speaks of is read off
these lines.  ``--rehearse`` is the tiny CPU run of the same lines (set
``APEX_TPU_KERNELS=interpret``); no time means anything there.
"""

import json
import os
import sys
import time
from unittest import mock

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from apex_tpu.serving import kv_cache as kvc  # noqa: E402

HD, MAX_LEN = 128, 2048
BUCKETS = (16, 32, 64, 128, 256, 512)
# layers, slots, query heads, kv heads
CELLS = {"mistral-7b-l16": (16, 16, 32, 8),
         "nemotron3-super-ep4-l11": (1, 64, 32, 2)}


def make_call(layers: int, read: str):
    def call(cache, q, k_new, v_new, slot, offset):
        qt = q
        for layer in range(layers):
            ctx, cache = kvc.prefill_attend(cache, layer, slot, qt, k_new,
                                            v_new, offset)
            # the next layer's queries hang on this layer's result
            qt = q + (1e-3 * ctx[0].transpose(1, 0, 2))[:, None].astype(
                q.dtype)
        return cache, ctx

    def traced(*args):
        # the seam's own choice, or one of the other two reads in its place
        if read == "chosen":
            return call(*args)
        with mock.patch.object(kvc, "_prefill_read", lambda c, q: read):
            return call(*args)

    return jax.jit(traced, donate_argnums=0)


def timed(fn, cache, *args, iters=20):
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
    max_len = 512 if rehearse else MAX_LEN
    buckets = (16, 64) if rehearse else BUCKETS
    for name, (layers, slots, heads, nkv) in CELLS.items():
        if rehearse:
            layers, slots = min(layers, 2), 3
        kq, kk, kv = jax.random.split(jax.random.key(0), 3)
        shape = (layers, slots, max_len, nkv, HD)
        cache = kvc.KVCache(k=jax.random.normal(kk, shape, jnp.bfloat16),
                            v=jax.random.normal(kv, shape, jnp.bfloat16),
                            lengths=jnp.zeros((slots,), jnp.int32))
        slot = jnp.int32(slots - 2)
        for m in buckets:
            q = jax.random.normal(kq, (m, 1, heads, HD), jnp.bfloat16)
            k_new = jax.random.normal(kk, (m, 1, nkv, HD), jnp.bfloat16)
            v_new = jax.random.normal(kv, (m, 1, nkv, HD), jnp.bfloat16)
            fns = {read: make_call(layers, read)
                   for read in ("full_extent", "chosen", "loop")}
            for offset in (0, max_len // 4, 3 * max_len // 4):
                if offset + m > max_len:
                    continue
                ref = None
                for read, fn in fns.items():
                    ms, cache, out = timed(fn, cache, q, k_new, v_new, slot,
                                           jnp.int32(offset))
                    line = {"cell": name, "m": m, "offset": offset,
                            "read": read, "ms": round(ms, 4),
                            "device": dev.device_kind}
                    if read == "chosen":
                        line["chosen"] = kvc._prefill_read(cache, q)
                    if ref is None:
                        ref = out.astype(jnp.float32)
                    else:
                        line["max_abs_diff"] = float(jnp.max(jnp.abs(
                            out.astype(jnp.float32) - ref)))
                    print(json.dumps(line), flush=True)
        del cache
    return 0


if __name__ == "__main__":
    sys.exit(main())
