"""A prompt chunk's read of one selecting latent layer alone: the Pallas
kernel that keeps a key block's scores in fast memory
(``ops/latent_chunk_attention.py``) against the blocked loop it replaces
(``serving/kv_cache.py::_chunk_read``), at the long-document cell's shapes
(``benchmark/configs/dots3-note-ep8-l5.json``, ``traffic/longdoc-closed.json``:
128 heads, rank 512, rows stored 640 wide, 16 slots of 32,768 rows, a 1,024-row
chunk that selects 2,048 rows a query).

    chiprun -- python tools/latent_chunk_bench.py

Both reads take the same queries, rows, matrix and selection (2,048 visible
rows a query drawn at random, through the seam's own ``_select_mask``); the
selection's cost is in neither.  One line of JSON a variant and an offset:
milliseconds a call, the visible key blocks, the products' TFLOP/s (expand +
q.k + p.v over the visible blocks, the loop's own count) and the largest
difference from the loop's result; then the slope, milliseconds a 512-row
block.  The kernel runs at its own query tile and key block and at the ones
beside them (the heads a step follow from what fits in VMEM).  ``--rehearse``
is the tiny CPU run of the same lines (set ``APEX_TPU_KERNELS=interpret``); no
time means anything there.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from apex_tpu.ops import latent_chunk_attention as lca
from apex_tpu.serving.kv_cache import _chunk_read, _key_block, _select_mask

CELL = dict(heads=128, rank=512, rope=64, nope=128, dv=128, stored=640,
            slots=16, max_len=32768, chunk=1024, top_k=2048,
            offsets=(0, 8192, 16384, 24576))
TOY = dict(heads=4, rank=128, rope=64, nope=128, dv=128, stored=256,
           slots=2, max_len=2048, chunk=256, top_k=96,
           offsets=(0, 512, 1792))
LAYER, SLOT = 1, 3


def variants(block: int, tile: int) -> list:
    """``(read, key block, query tile)``: the loop, the kernel at its own
    sizes, then at the tiles and the blocks beside them."""
    return [("loop", block, None), ("kernel", block, tile),
            ("kernel", block, tile // 2), ("kernel", block, 2 * tile),
            ("kernel", block // 2, tile), ("kernel", 2 * block, tile)]


def timed(fn, *args, iters):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / iters, out


def main():
    rehearse = "--rehearse" in sys.argv[1:]
    c = TOY if rehearse else CELL
    dev = jax.devices()[0]
    dt = jnp.bfloat16
    m, heads, max_len = c["chunk"], c["heads"], c["max_len"]
    slot = min(SLOT, c["slots"] - 1)
    kq, kl, kw, ks = jax.random.split(jax.random.key(0), 4)
    width = c["rank"] + c["rope"]
    latent = jnp.pad(
        jax.random.normal(kl, (2, c["slots"], max_len, width), dt),
        ((0, 0),) * 3 + ((0, c["stored"] - width),))
    w = (jax.random.normal(kw, (c["rank"], heads, c["nope"] + c["dv"]), dt)
         * c["rank"] ** -0.5)
    qs = (jax.random.normal(kq, (m, heads, c["nope"] + c["rope"]), dt)
          * (c["nope"] + c["rope"]) ** -0.5)
    drawn = jax.random.uniform(ks, (m, max_len), jnp.float32)
    col = jnp.arange(max_len, dtype=jnp.int32)

    @jax.jit
    def selection(offset):
        at = offset + jnp.arange(m, dtype=jnp.int32)
        return _select_mask(drawn, col[None] <= at[:, None], c["top_k"])

    def loop(block):
        return jax.jit(lambda qs, latent, sel, w, blocks: _chunk_read(
            qs, latent, sel, {"w": w, "nope": c["nope"]}, LAYER, slot,
            blocks, block=block, width=width))

    def kernel(block):
        return jax.jit(lambda qs, latent, sel, w, blocks:
                       lca.latent_chunk_attention(
                           qs, latent, sel, w, LAYER, slot, blocks,
                           nope=c["nope"], block=block))

    tile = lca.TILE
    iters = 2 if rehearse else 10
    # products of one key row for one query and one head, and of its
    # expansion for one head
    per_pair = 2 * (c["nope"] + c["rope"] + c["dv"])
    per_row = 2 * c["rank"] * (c["nope"] + c["dv"])
    want = {}

    def plan(block):
        """The kernel's own ``(rows, tile, heads a step)`` at this block."""
        return lca.plan(
            m, heads, block=block, stored=c["stored"], rank=c["rank"],
            dk=c["nope"] + c["stored"] - c["rank"],
            wide=c["nope"] + c["dv"], dv=c["dv"],
            item=jnp.dtype(dt).itemsize)

    for read, block, tiled in variants(_key_block(max_len), tile):
        if read == "kernel":
            lca.TILE = tiled
            fn = kernel(block)
        else:
            fn = loop(block)
        ms_at = {}
        for offset in c["offsets"]:
            blocks = (offset + m - 1) // block + 1
            sel = selection(jnp.int32(offset))
            ms, out = timed(fn, qs, latent, sel, w, jnp.int32(blocks),
                            iters=iters)
            ms_at[offset] = ms
            rows = blocks * block
            flop = heads * rows * (per_row + m * per_pair)
            line = {"read": read, "block": block, "offset": offset,
                    "blocks": blocks, "ms": round(ms, 3),
                    "tflops": round(flop / ms / 1e9, 2),
                    "device": dev.device_kind}
            if read == "kernel":
                line.update(tile=plan(block)[1], heads=plan(block)[2])
                line["max_abs_diff"] = float(jnp.max(jnp.abs(
                    out - want[offset])))
            else:
                want[offset] = out
            print(json.dumps(line), flush=True)
        first, last = c["offsets"][1], c["offsets"][-1]
        print(json.dumps({
            "read": read, "block": block,
            **({"tile": plan(block)[1]} if read == "kernel" else {}),
            "ms_a_512_row_block": round(
                (ms_at[last] - ms_at[first]) / (last - first) * 512, 4)}),
            flush=True)
    lca.TILE = tile
    return 0


if __name__ == "__main__":
    sys.exit(main())
