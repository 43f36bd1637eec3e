"""The seam between a model's attention and the K/V cache
(``apex_tpu/serving/kv_cache.py``): a model reaches the cache through the
seam's functions only, one constructor builds every cache from what the
model declares, and the store / load pair with each layout's index and
view round-trips rows - and drops what it promises to drop - for every
layout x storage format."""

import ast
import dataclasses
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.amp.quant import dequantize_int8, quantize_int8
from apex_tpu.models import LlamaConfig, LlamaForCausalLM
from apex_tpu.serving import kv_cache as kvc
from apex_tpu.serving.paged_kv_cache import PagedCacheConfig

PACKAGE = pathlib.Path(kvc.__file__).resolve().parents[1]
MODELS = sorted(p.name for p in (PACKAGE / "models").glob("*.py"))
CACHE_MODULES = ("serving/kv_cache.py", "serving/paged_kv_cache.py")
CACHE_CLASSES = ("KVCache", "QuantKVCache", "PagedKVCache",
                 "QuantPagedKVCache", "HybridCache", "LatentCache",
                 "WindowKVCache")
# what a model may import from apex_tpu.serving: the two calls of an
# attention layer (K/V rows, latent rows, a window ring), the state functions
# of a recurrent or counting layer, and the declarations cache_layers()
# returns
SEAM = {"decode_attend", "prefill_attend", "window_decode_attend",
        "window_prefill_attend", "latent_decode_attend",
        "latent_prefill_attend", "ring_decode_attend", "ring_prefill_attend",
        "slot_state", "write_slot_state", "write_lane_state", "add_counts",
        "KVRows", "KVWindowRows", "RecurrentRows", "LatentRows", "RingRows",
        "CallCounters"}


# ---- (a) the source: who knows what ---------------------------------------


@pytest.mark.parametrize("name", MODELS)
def test_model_reaches_the_cache_through_the_seam_only(name):
    text = (PACKAGE / "models" / name).read_text()
    named = re.findall(r"\b(?:%s)\b" % "|".join(CACHE_CLASSES), text)
    assert not named, f"models/{name} names a cache class: {set(named)}"
    for node in ast.walk(ast.parse(text)):
        if isinstance(node, ast.Import):
            mods = [a.name for a in node.names]
            assert not any(m.startswith("apex_tpu.serving") for m in mods), (
                f"models/{name}:{node.lineno} imports {mods}")
        elif isinstance(node, ast.ImportFrom) and node.module:
            names = {a.name for a in node.names}
            if node.module == "apex_tpu":
                assert "serving" not in names, (
                    f"models/{name}:{node.lineno} imports the serving package")
            elif node.module.startswith("apex_tpu.serving"):
                assert node.module == "apex_tpu.serving.kv_cache" and \
                    names <= SEAM, (
                        f"models/{name}:{node.lineno} imports {sorted(names)} "
                        f"from {node.module}: not the seam's functions")
            elif node.module.startswith("apex_tpu.models"):
                private = sorted(n for n in names if n.startswith("_"))
                assert not private, (
                    f"models/{name}:{node.lineno} imports private names "
                    f"{private} from {node.module}")


def test_only_the_cache_modules_test_a_caches_class():
    pattern = re.compile(r"isinstance\([^)]*(?:%s)" % "|".join(CACHE_CLASSES))
    found = {}
    for path in PACKAGE.rglob("*.py"):
        hits = pattern.findall(path.read_text())
        if hits:
            found[path.relative_to(PACKAGE).as_posix()] = len(hits)
    assert set(found) <= set(CACHE_MODULES), found
    assert sum(found.values()) <= 4, found


# ---- (b) one constructor, from what the model declares --------------------

CFG = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=3, num_attention_heads=4,
                  num_key_value_heads=2, max_position_embeddings=64)
SLOTS, MAX_LEN, BLOCK, BLOCKS = 3, 20, 8, 7
DENSE = (3, SLOTS, MAX_LEN, 2, 16)      # [layers, slots, max_len, kvh, hd]
POOL = (3, BLOCKS, BLOCK, 2, 16)        # [layers, blocks, block, kvh, hd]
TABLES = {".tables": ((SLOTS, 3), "int32")}     # ceil(20 / 8) blocks a slot
LENGTHS = {".lengths": ((SLOTS,), "int32")}


def _float(shape, dtype):
    return {".k": (shape, dtype), ".v": (shape, dtype)}


def _int8(shape):
    return {".k": (shape, "int8"), ".v": (shape, "int8"),
            ".k_scale": (shape[:-1], "float32"),
            ".v_scale": (shape[:-1], "float32")}


BUILDS = {
    "dense": (dict(dtype=jnp.bfloat16), "KVCache",
              {**_float(DENSE, "bfloat16"), **LENGTHS}),
    "int8": (dict(int8=True), "QuantKVCache", {**_int8(DENSE), **LENGTHS}),
    "paged": (dict(paged=PagedCacheConfig(BLOCK, BLOCKS)), "PagedKVCache",
              {**_float(POOL, "float32"), **TABLES, **LENGTHS}),
    "paged-int8": (dict(paged=PagedCacheConfig(BLOCK, BLOCKS), int8=True),
                   "QuantPagedKVCache",
                   {**_int8(POOL), **TABLES, **LENGTHS}),
}


def _build(kind, **kw):
    return kvc.init_cache(LlamaForCausalLM(CFG).cache_layers(), slots=SLOTS,
                          max_len=MAX_LEN, **{**BUILDS[kind][0], **kw})


@pytest.mark.parametrize("kind", list(BUILDS))
def test_cache_layers_build_the_cache_the_config_sized(kind):
    """The leaves, shapes and dtypes ``init_cache`` / ``init_quant_cache`` /
    ``init_paged_cache`` / ``init_quant_paged_cache`` built from a
    ``LlamaConfig``, now from ``LlamaForCausalLM.cache_layers()``.  The leaf
    ORDER is the order the tensor-parallel specs and the compiled programs'
    arguments follow."""
    _, cls, want = BUILDS[kind]
    cache = _build(kind)
    assert type(cache).__name__ == cls
    leaves = {jax.tree_util.keystr(path): (leaf.shape, leaf.dtype.name)
              for path, leaf in jax.tree_util.tree_leaves_with_path(cache)}
    assert leaves == want
    assert list(leaves) == list(want)
    assert cache.max_len == MAX_LEN and cache.num_slots == SLOTS
    assert cache.num_layers == CFG.num_hidden_layers
    # scales start at 1, everything else at 0: an unused row reads as zeros
    for name in cache.stored:
        start = 1 if name.endswith("_scale") else 0
        assert (np.asarray(getattr(cache, name)) == start).all(), name
    if "paged" in kind:
        # a pool left to size itself holds every slot's max_len + the null
        assert _build(kind, paged=PagedCacheConfig(BLOCK)).num_blocks == \
            SLOTS * 3 + 1


@pytest.mark.parametrize("kw, why", [
    (dict(int8=True), "not quantized"),
    (dict(paged=PagedCacheConfig(BLOCK)), "no rows to page")])
def test_a_recurrent_state_is_dense_floats_only(kw, why):
    layers = [kvc.KVRows(2, 16), kvc.RecurrentRows(ssm=(4, 2, 8), conv=(3, 6))]
    assert isinstance(kvc.init_cache(layers, slots=2, max_len=8),
                      kvc.HybridCache)
    with pytest.raises(ValueError, match=why):
        kvc.init_cache(layers, slots=2, max_len=8, **kw)


# ---- (c) store / load through every layout x format -----------------------


def _allocated(kind):
    """A cache whose slot 0 holds rows 0-15 (blocks 1, 2 when paged), slot 1
    rows 0-7 (block 3), and slot 2 nothing."""
    cache = _build(kind, dtype=jnp.float32)
    if "paged" in kind:
        tables = np.zeros((SLOTS, 3), np.int32)
        tables[0, :2], tables[1, 0] = (1, 2), 3
        cache = dataclasses.replace(cache, tables=jnp.asarray(tables))
    return cache


def _rows(n, seed):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((n, 2, 16)), jnp.float32)
                 for _ in range(2))


def _as_read(kind, rows):
    """What a read hands back for ``rows`` written: the rows, or their int8
    round trip (under jit like the write: an eager scale differs in the
    last bit)."""
    if "int8" not in kind:
        return np.asarray(rows)
    return np.asarray(jax.jit(
        lambda t: dequantize_int8(*quantize_int8(t, axis=-1)))(rows))


def _buffers(cache):
    return {name: np.asarray(getattr(cache, name)) for name in cache.stored}


@pytest.mark.parametrize("kind", list(BUILDS))
def test_rows_round_trip_through_the_layout_and_the_format(kind):
    """A chunk, then one appended row a lane, read back through both views:
    exactly what was stored where it was stored, zeros elsewhere, and the
    other layers untouched."""
    cache = _allocated(kind)
    k, v = _rows(6, seed=0)
    cache = jax.jit(kvc.prefill_into_slot, static_argnums=1)(
        cache, 1, np.int32(0), k, v, np.int32(3))
    kt, vt = _rows(SLOTS, seed=1)
    cache = jax.jit(kvc.append_token, static_argnums=1)(
        cache, 1, kt, vt, jnp.asarray([9, 2, -1], jnp.int32))
    want_k = np.zeros((SLOTS, MAX_LEN, 2, 16), np.float32)
    want_v = np.zeros_like(want_k)
    want_k[0, 3:9], want_v[0, 3:9] = _as_read(kind, k), _as_read(kind, v)
    want_k[0, 9], want_v[0, 9] = _as_read(kind, kt)[0], _as_read(kind, vt)[0]
    want_k[1, 2], want_v[1, 2] = _as_read(kind, kt)[1], _as_read(kind, vt)[1]
    got_k, got_v = kvc.decode_read(cache, 1)
    assert got_k.dtype == kvc.value_dtype(cache) == jnp.float32
    np.testing.assert_array_equal(np.asarray(got_k), want_k)
    np.testing.assert_array_equal(np.asarray(got_v), want_v)
    for slot in range(SLOTS):
        one_k, one_v = kvc.slot_read(cache, 1, np.int32(slot))
        np.testing.assert_array_equal(np.asarray(one_k), want_k[slot])
        np.testing.assert_array_equal(np.asarray(one_v), want_v[slot])
    for layer in (0, 2):
        assert not np.asarray(kvc.decode_read(cache, layer)[0]).any()


# (slot, start) of a four-row chunk, the slot's rows it lands on (dense,
# paged) with the chunk rows that land there, and one position a lane
EDGES = {
    # idle lanes' sentinel: dropped (plain indexing would wrap -1 to the
    # slot's last row).  A chunk never starts below 0; the table's routing
    # drops such rows all the same (rows 0, 1 written), the dense index
    # promises nothing for them (None)
    "negative row": (0, -2, (None, slice(0, 2)), slice(2, 4), [-1, -1, -1]),
    # rows 20, 21 dropped (a dynamic update would clamp the block backward
    # onto rows 16-19); rows 18, 19 written where the slot has them: the
    # paged slot 0 owns no block past row 15
    "row past max_len": (0, MAX_LEN - 2,
                         (slice(MAX_LEN - 2, MAX_LEN), slice(0, 0)),
                         slice(0, 2), [MAX_LEN, MAX_LEN + 5, MAX_LEN]),
    # slot 1 owns block 3 alone: rows 6, 7 written, rows 8, 9 (table entry
    # null) dropped; lanes: an idle one, one past its frontier, one of a
    # slot that owns nothing
    "null block": (1, 6, (None, slice(6, 8)), slice(0, 2), [-1, 8, 0]),
}


@pytest.mark.parametrize("kind, edge", [
    (kind, edge) for kind in BUILDS for edge in EDGES
    if "paged" in kind or edge != "null block"])
def test_a_row_that_has_no_place_is_dropped_not_clamped(kind, edge):
    """``mode="drop"`` at the three edges the docstrings promise: a
    negative row (an idle lane's sentinel), a row at or past ``max_len``
    (bucket padding overhanging the cache end), a row whose table entry is
    the null block (padding past the allocated frontier).  Every stored
    buffer, scales included, keeps every byte the write had no place
    for."""
    chunk_write = jax.jit(kvc.prefill_into_slot, static_argnums=1)
    cache = chunk_write(_allocated(kind), 0, np.int32(0), *_rows(16, seed=2),
                        np.int32(0))
    before = _buffers(cache)
    slot, start, landed, written, positions = EDGES[edge]
    landed = landed["paged" in kind]
    k, v = _rows(4, seed=3)

    lanes = jax.jit(kvc.append_token, static_argnums=1)(
        cache, 0, *_rows(SLOTS, seed=4), jnp.asarray(positions, jnp.int32))
    for name, buf in _buffers(lanes).items():
        np.testing.assert_array_equal(buf, before[name], err_msg=name)

    if landed is None:
        return
    chunk = chunk_write(cache, 0, np.int32(slot), k, v, np.int32(start))
    want = np.array(kvc.slot_read(cache, 0, np.int32(slot))[0])
    want[landed] = _as_read(kind, k)[written][:want[landed].shape[0]]
    np.testing.assert_array_equal(
        np.asarray(kvc.slot_read(chunk, 0, np.int32(slot))[0]), want)
    for name, buf in _buffers(chunk).items():
        # the other slots' rows (dense) / the null block (paged) and the
        # other layers never move
        other = buf[:, 0] if "paged" in kind else np.delete(buf, slot, axis=1)
        was = (before[name][:, 0] if "paged" in kind
               else np.delete(before[name], slot, axis=1))
        np.testing.assert_array_equal(other, was, err_msg=name)
        np.testing.assert_array_equal(buf[1:], before[name][1:], err_msg=name)
