"""The serving programs compiled for a described TPU v5e, without the chip.

The cached read's speed on the chip rests on a layout decision of
XLA:TPU that no CPU test can see: ``cached_attention`` fences the cache
view with ``jax.lax.optimization_barrier`` so that the two grouped
contractions read it in the layout it is stored in.  Left free, the
compiler gives the WHOLE cache a kv-head-major layout for those dots and
copies it in and out of every decode step (PERF.md §6, PR 26: 30.9 ms a
16-layer step against 19.1).  That side effect of the barrier is
unspecified, and the jaxpr-level structural test in ``test_serving.py``
sits above layout assignment; this file compiles the engine's own
programs with the TPU compiler that is installed here (nothing runs, no
time is measured) and reads the compiled text: a ``copy`` or
``transpose`` of the cache's dtype as large as one layer's slab is the
regression.  The same text says whether the decode step's append moves
rows or slabs (PERF.md §6, PR 28), and whether its read takes the stored
buffers whole (PR 30): the decode programs are compiled on the path the
chip takes - ``ops._dispatch.on_tpu`` answers for the backend that traces,
the CPU here, so these tests answer for it - where the read is the Pallas
kernel ``cached_decode_attention``.

All such compiles live in this one file and describe the topology inside
a fixture: only one process at a time may load the TPU's library.
"""

import dataclasses
import functools
import math
import os
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from apex_tpu import serving as sv
from apex_tpu.models import LlamaConfig, LlamaForCausalLM
from apex_tpu.ops import _dispatch
from apex_tpu.serving.engine import DECODE_VECTORS
from apex_tpu.serving.kv_cache import init_cache

# the serving cell's attention geometry (benchmark/configs/
# mistral-7b-l16.json and traffic/chat-closed.json): GQA 32:8, head 128,
# 16 slots of 2048 rows, 512-row chunks, bf16.  Depth, MLP width and
# vocabulary are cut: they hold no cache
CFG = LlamaConfig(vocab_size=256, hidden_size=4096, intermediate_size=256,
                  num_hidden_layers=2, num_attention_heads=32,
                  num_key_value_heads=8, max_position_embeddings=4096)
SLOTS, MAX_LEN, CHUNK = 16, 2048, 512
SLAB = SLOTS * MAX_LEN * CFG.kv_heads * (CFG.hidden_size
                                         // CFG.num_attention_heads)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def engine():
    """The engine whose jitted programs are compiled: real widths, zero
    weights (only shapes matter), and a small cache of its own — the
    cell-sized cache is handed to ``lower`` as shapes."""
    model = LlamaForCausalLM(CFG)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    params = jax.tree.map(
        lambda l: jnp.zeros(l.shape,
                            jnp.bfloat16 if l.ndim >= 2 else l.dtype),
        shapes)
    return sv.DecodeEngine(model, params, slots=2, max_len=CHUNK,
                           prefill_len=CHUNK, cache_dtype=jnp.bfloat16)


@pytest.fixture(scope="module")
def compiled_text(engine, one_chip):
    """``compiled_text(program)``: each program is compiled once for the
    module, whatever number of tests read its text."""
    return functools.cache(
        functools.partial(_compiled_text, engine, one_chip))


def _placed(one_chip):
    """``(on_chip(tree), arg(shape, dtype))``: shapes placed on the
    described chip, which is what ``lower`` is handed."""
    def on_chip(tree):
        return jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                           sharding=one_chip), tree)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return on_chip, arg


def _compiled_text(engine, one_chip, program):
    on_chip, arg = _placed(one_chip)
    cache = on_chip(jax.eval_shape(
        lambda: init_cache(engine.model.cache_layers(), slots=SLOTS,
                           max_len=MAX_LEN,
                           dtype=jnp.bfloat16)))
    params = on_chip(engine.params)
    if program == "decode":
        lowered = _lower_decode_as_on_the_chip(engine, params, cache, arg)
    else:
        lowered = engine._prefill.lower(
            params, cache, arg((1, CHUNK), jnp.int32), arg((), jnp.int32),
            arg((), jnp.int32), arg((), jnp.int32))
    return lowered.compile().as_text()


def _decode_vectors(arg, slots):
    """The decode program's ``[slots]`` operands behind params and cache."""
    return [arg((slots,), dtype) for dtype in DECODE_VECTORS]


def _lower_decode_as_on_the_chip(engine, params, cache, arg):
    """``engine._decode`` lowered with every kernel's dispatch answering
    as on a TPU backend (the described chip is no backend: left alone, the
    trace takes each ``jax.numpy`` reference)."""
    slots = cache.lengths.shape[0]
    with mock.patch.object(_dispatch, "on_tpu", lambda: True):
        return engine._decode.lower(params, cache,
                                    *_decode_vectors(arg, slots))


def _entry_ops(text, dtype="bf16"):
    """``(name, op, sizes)`` of each instruction of the compiled
    program's entry computation: ``sizes`` are the element counts of its
    results of ``dtype`` (several where it returns a tuple)."""
    for line in text[text.index("\nENTRY "):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\(", line)
        if not m:
            continue
        name, result, op = m.groups()
        yield name, op, [
            math.prod(int(d) for d in dims.split(",") if d)
            for dims in re.findall(r"\b%s\[([\d,]*)\]" % dtype, result)]


def _slab_sized_layout_copies(text, slab=SLAB):
    """Instructions of the compiled program's entry computation that
    write a buffer of the cache's dtype, at least one layer's slab large,
    as a ``copy`` or ``transpose`` (alone or as a fusion XLA names after
    one).  A ``copy`` INSIDE a convolution fusion is the dot reading its
    operand turned on the fly and writes nothing; a ``slice`` of the
    cache or a prefetch into another memory space is no layout copy."""
    return [f"{op} %{name}" for name, op, sizes in _entry_ops(text)
            if (op in ("copy", "transpose") or op == "fusion"
                and name.startswith(("copy", "transpose")))
            and any(size >= slab for size in sizes)]


def _slab_sized_cuts(text, slab=SLAB):
    """``slice`` instructions (alone or as a fusion XLA names after one)
    of the entry computation that write at least one layer's slab."""
    return [name for name, op, sizes in _entry_ops(text)
            if (op == "slice" or op == "fusion" and name.startswith("slice"))
            and any(size >= slab for size in sizes)]


def _kernel_calls(text, kernel):
    return [name for name, op, _ in _entry_ops(text)
            if op == "custom-call" and name.startswith(kernel)]


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_compiled_program_copies_no_cache_slab(compiled_text, program):
    text = compiled_text(program)
    # the check reads the TPU compiler's program, not the CPU's
    assert "bf16[%d,%d,%d,%d,%d]" % (
        CFG.num_hidden_layers, SLOTS, MAX_LEN, CFG.kv_heads,
        CFG.hidden_size // CFG.num_attention_heads) in text
    assert ":T(8,128)" in text
    copies = _slab_sized_layout_copies(text)
    assert not copies, (
        f"{program}: the compiled program copies the cache into another "
        f"layout ({len(copies)} slab-sized copies, e.g. {copies[:3]}): "
        "the cached read no longer takes it as it is stored")


def test_decode_takes_the_kept_vector_as_an_operand_and_donates_the_cache(
        engine, one_chip):
    """Decode-ahead (ISSUE 36): each slot's last sampled token is an operand
    the device already holds, chosen against the host's vector by a mask
    inside the one program; the cache, and only the cache, is donated."""
    on_chip, arg = _placed(one_chip)
    cache = on_chip(jax.eval_shape(
        lambda: init_cache(engine.model.cache_layers(), slots=SLOTS,
                           max_len=MAX_LEN, dtype=jnp.bfloat16)))
    lowered = _lower_decode_as_on_the_chip(engine, on_chip(engine.params),
                                           cache, arg)
    (params, cache_info, last, tokens, on_device, active), _ = (
        lowered.args_info)
    assert all(leaf.donated for leaf in jax.tree.leaves(cache_info))
    assert not any(leaf.donated for leaf in jax.tree.leaves(
        (params, last, tokens, on_device, active)))
    text = lowered.as_text()
    signature = text[text.index("@main("):text.index(") -> ")]
    vectors = [
        (kind, "tf.aliasing_output" in arg_text)
        for arg_text in re.split(r", (?=%arg\d+:)", signature)
        for kind in re.findall(r": tensor<%dx(i32|i1)>" % SLOTS, arg_text)]
    # the cache's lengths (aliased to their output), then the kept vector,
    # the host's tokens, which lanes take which, which lanes are active
    assert vectors == [("i32", True), ("i32", False), ("i32", False),
                       ("i1", False), ("i1", False)]
    assert re.search(r"stablehlo\.select.*tensor<%dxi32>" % SLOTS, text)


def test_one_decode_program_serves_kept_and_host_fed_lanes():
    """``decode_compiles() == 1`` after a drain that mixes lanes fed from
    the kept vector with lanes fed from the host (a settle between steps
    leaves every lane host-fed for a step; a lane joining from prefill is
    device-fed beside them) and after a host caller's
    ``engine.decode(tokens, active)``."""
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=256)
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    eng = sv.DecodeEngine(model, params, slots=3, max_len=64, prefill_len=16)
    sched = sv.ContinuousBatchingScheduler(eng)
    for i, n in enumerate((5, 9, 20, 7)):
        sched.submit(sv.Request(f"r{i}", list(range(1, n + 1)), 6 + i))
    fed = set()
    decode = eng.decode

    def spy(tokens, active, *, on_device=None):
        fed.add((bool(on_device[active].any()),
                 bool((~on_device[active]).any())))
        return decode(tokens, active, on_device=on_device)

    eng.decode = spy
    steps = 0
    while sched.queue_depth or sched.active_count:
        sched.step()
        steps += 1
        if steps == 2:
            sched._settle("test")
        if steps == 5:
            sched.cancel("r1")
    # every lane kept, or kept and host-fed lanes in one call; the host
    # caller below feeds every lane itself
    assert fed == {(True, False), (True, True)}
    assert eng.decode_compiles() == 1
    eng.decode = decode
    logits = eng.prefill(0, [3, 4, 5])
    active = np.zeros((3,), bool)
    active[0] = True
    tokens = np.zeros((3,), np.int32)
    tokens[0] = int(jnp.argmax(logits))
    eng.decode(tokens, active)
    assert eng.decode_compiles() == 1
    sched.close()


def test_decode_append_moves_rows_not_slabs(compiled_text):
    """``append_token`` is one scatter on the whole donated cache a layer
    and a buffer.  Spelt as an update of the layer's slab
    (``cache.k.at[layer].set(vmap(dynamic_update_slice)(cache.k[layer],
    ...))``) the compiled step cut the 67 MB slab out, looped over the
    slots and wrote it back, for K and for V, every layer: 8.6 GB a step
    for 1 MB of new rows (PERF.md §6, PR 28)."""
    ops = list(_entry_ops(compiled_text("decode")))
    layers = CFG.num_hidden_layers
    loops = [name for name, op, _ in ops if op == "while"]
    assert not loops, f"the decode step loops over the slots: {loops}"
    rewrites = [name for name, op, sizes in ops
                if op == "fusion" and "dynamic-update-slice" in name
                and any(size >= layers * SLAB for size in sizes)]
    assert not rewrites, (
        f"the decode step writes whole slabs back into the cache: "
        f"{rewrites}")


def test_decode_reads_the_cache_where_it_lies(compiled_text):
    """The read is one ``cached_decode_attention`` call a layer on the
    stored buffers.  Through ``cached_attention`` it took ``cache.k[layer]``
    and the compiled step cut the layer's 67 MB slab out first, for K and
    for V: 32 cuts, 4.9 of the 19.1 ms of a step in the Mistral cell
    (PERF.md §5, PR 28)."""
    text = compiled_text("decode")
    layers = CFG.num_hidden_layers
    assert len(_kernel_calls(text, "cached_decode_attention")) == layers
    cuts = _slab_sized_cuts(text)
    assert not cuts, (
        f"{len(cuts)} slab-sized cuts of the cache where the read takes "
        f"none: {cuts}")


# -- a model with recurrent state (PR 27) -----------------------------------
# the new cell's Mamba-2 geometry (benchmark/configs/
# nemotron3-super-ep4-l11.json, traffic/chat-closed-64.json): 128 heads of
# 64 x 128 float32 state, 64 slots.  No expert layer: it keeps no state

HYBRID_SLOTS = 64


@pytest.fixture(scope="module")
def hybrid_engine():
    from apex_tpu.models.nemotron_h import (
        NemotronHConfig,
        NemotronHForCausalLM,
    )

    model = NemotronHForCausalLM(NemotronHConfig(
        vocab_size=256, hidden_size=4096, hybrid_override_pattern="M*M",
        num_attention_heads=32, num_key_value_heads=2, head_dim=128,
        mamba_num_heads=128, mamba_head_dim=64, n_groups=8,
        ssm_state_size=128, conv_kernel=4, chunk_size=128),
        params_dtype=jnp.bfloat16)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    params = jax.tree.map(lambda l: jnp.zeros(l.shape, l.dtype), shapes)
    return sv.DecodeEngine(model, params, slots=2, max_len=64,
                           prefill_len=64, cache_dtype=jnp.bfloat16)


@pytest.mark.parametrize("bucket", [16])
def test_prefill_chunk_reads_one_slots_state_not_every_slots(
        hybrid_engine, one_chip, bucket):
    """A chunk belongs to one slot.  Reading that slot's state through
    ``state.ssm[layer][slot]`` made the compiled program copy the layer's
    state of all 64 slots first: a ``slice`` of 268 MB a recurrent layer,
    4.1 of the 11-29 ms of every prefill call on the chip (PERF.md §6,
    PR 27)."""
    on_chip, arg = _placed(one_chip)
    model = hybrid_engine.model
    cache = on_chip(jax.eval_shape(lambda: init_cache(
        model.cache_layers(), slots=HYBRID_SLOTS, max_len=MAX_LEN,
        dtype=jnp.bfloat16)))
    text = hybrid_engine._prefill.lower(
        on_chip(hybrid_engine.params), cache, arg((1, bucket), jnp.int32),
        arg((), jnp.int32), arg((), jnp.int32),
        arg((), jnp.int32)).compile().as_text()
    cfg = model.config
    one_slot = cfg.mamba_num_heads * cfg.mamba_head_dim * cfg.ssm_state_size
    assert "f32[2,%d,%d,%d,%d]" % (
        HYBRID_SLOTS, cfg.mamba_num_heads, cfg.mamba_head_dim,
        cfg.ssm_state_size) in text
    # the in-place write of the slot's new state is a fusion over the
    # whole (donated) array and copies nothing
    found = [f"{op} %{name}" for name, op, sizes in _entry_ops(text, "f32")
             if (op in ("slice", "copy", "transpose") or op == "fusion"
                 and name.startswith(("slice", "copy", "transpose")))
             and any(size >= HYBRID_SLOTS * one_slot // 2 for size in sizes)]
    assert not found, (
        f"the prefill program copies the state of every slot "
        f"({found[:3]}): a chunk reads and writes one slot's state")


def test_hybrid_decode_reads_the_cache_where_it_lies(hybrid_engine, one_chip):
    """The same read at the hybrid cell's attention geometry: 16 query
    heads a KV head, 2 KV heads, 64 slots - a ``[1024, 2, 128]`` tile."""
    on_chip, arg = _placed(one_chip)
    model = hybrid_engine.model
    cache = on_chip(jax.eval_shape(lambda: init_cache(
        model.cache_layers(), slots=HYBRID_SLOTS, max_len=MAX_LEN,
        dtype=jnp.bfloat16)))
    text = _lower_decode_as_on_the_chip(
        hybrid_engine, on_chip(hybrid_engine.params), cache,
        arg).compile().as_text()
    cfg = model.config
    slab = HYBRID_SLOTS * MAX_LEN * cfg.num_key_value_heads * cfg.head_dim
    assert "bf16[1,%d,%d,%d,%d]" % (
        HYBRID_SLOTS, MAX_LEN, cfg.num_key_value_heads,
        cfg.head_dim) in text
    assert len(_kernel_calls(text, "cached_decode_attention")) == 1
    found = _slab_sized_cuts(text, slab) + _slab_sized_layout_copies(
        text, slab)
    assert not found, (
        f"the hybrid decode program cuts or copies the K/V slab: {found}")


# -- a short cache's prompt chunk through the chunk kernel (PR 34) -----------
# ``chat-closed``'s and the hybrid cell's chunks score exactly the 128 MiB up
# to which ``prefill_attend`` used to attend the whole masked extent; on the
# chip they now walk the visible blocks in ``kv_chunk_attention`` like the long
# cells' chunks.  The dense program is compiled with ALL 16 layers of the cell:
# what XLA:TPU does to the stacked buffer once for all its layers (PRs 26, 33)
# does not show in a two-layer program

DENSE_LAYERS = 16


def _chunk_program_text(engine, params, cache, arg, bucket=CHUNK):
    with mock.patch.object(_dispatch, "on_tpu", lambda: True):
        return engine._prefill.lower(
            params, cache, arg((1, bucket), jnp.int32), arg((), jnp.int32),
            arg((), jnp.int32), arg((), jnp.int32)).compile().as_text()


def _score_sized_f32(text, heads, chunk, block=512):
    """Shapes of float32 arrays, anywhere in the program, as large as one
    key block's ``[heads, chunk, block]`` scores (a quarter of the full
    extent's at 2,048 rows)."""
    return sorted({
        dims for dims in re.findall(r"\bf32\[([\d,]+)\]", text)
        if math.prod(int(d) for d in dims.split(",")) >= heads * chunk * block})


def test_dense_chunk_walks_in_the_kernel_at_all_its_layers(one_chip):
    """The dense engine's 512-row ``_prefill`` at Mistral's attention widths
    and depth: one ``kv_chunk_attention`` call a layer; what is cut out of
    the cache is one slot's ``[2048, 8, 128]`` rows, K and V a layer, each
    turned head-major once (4 MB; the layout of the cut is pinned, PR 33) -
    never a layer's slab (the cut ``slot_read`` made for
    ``cached_attention`` is gone with that read) and never the stacked
    buffer; and no float32 array as large as a block's scores is left:
    ``cached_attention`` sent ``[32, 512, 2048]`` scores, 134 MB a layer,
    through HBM five times (PERF.md section 6, PR 34)."""
    on_chip, arg = _placed(one_chip)
    cfg = dataclasses.replace(CFG, num_hidden_layers=DENSE_LAYERS)
    model = LlamaForCausalLM(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    # the programs are lowered on shapes: the engine holds one dummy leaf
    params = on_chip(jax.tree.map(lambda l: jax.ShapeDtypeStruct(
        l.shape, jnp.bfloat16 if len(l.shape) >= 2 else l.dtype), shapes))
    engine = sv.DecodeEngine(model, {"w": jnp.zeros((1,), jnp.bfloat16)},
                             slots=2, max_len=CHUNK, prefill_len=CHUNK,
                             cache_dtype=jnp.bfloat16)
    cache = on_chip(jax.eval_shape(lambda: init_cache(
        model.cache_layers(), slots=SLOTS, max_len=MAX_LEN,
        dtype=jnp.bfloat16)))
    text = _chunk_program_text(engine, params, cache, arg)
    assert "bf16[%d,%d,%d,%d,128]" % (
        DENSE_LAYERS, SLOTS, MAX_LEN, cfg.kv_heads) in text
    assert len(_kernel_calls(text, "kv_chunk_attention")) == DENSE_LAYERS
    found = _slab_sized_cuts(text) + _slab_sized_layout_copies(text)
    assert not found, f"the chunk cuts or copies a layer's slab: {found}"
    one_slot = [name for name, op, sizes in _entry_ops(text)
                if MAX_LEN * cfg.kv_heads * 128 in sizes and op == "fusion"
                and name.startswith("copy")]
    assert len(one_slot) == 2 * DENSE_LAYERS, one_slot
    large = _score_sized_f32(text, cfg.num_attention_heads, CHUNK)
    assert not large, (
        f"float32 arrays as large as a block's scores in the prefill "
        f"program: {large}")


def test_hybrid_chunk_walks_in_the_kernel(hybrid_engine, one_chip):
    """The same read at the hybrid cell's attention geometry - 16 query
    heads a KV head, 2 KV heads, 64 slots -: its one attention layer's
    512-row chunk is one ``kv_chunk_attention`` call, the layer's slab is
    neither cut nor copied, and no float32 array over the 32 heads is as
    large as a block's scores (the scan's own arrays have no such axis)."""
    on_chip, arg = _placed(one_chip)
    model = hybrid_engine.model
    cache = on_chip(jax.eval_shape(lambda: init_cache(
        model.cache_layers(), slots=HYBRID_SLOTS, max_len=MAX_LEN,
        dtype=jnp.bfloat16)))
    text = _chunk_program_text(hybrid_engine, on_chip(hybrid_engine.params),
                               cache, arg)
    cfg = model.config
    slab = HYBRID_SLOTS * MAX_LEN * cfg.num_key_value_heads * cfg.head_dim
    assert len(_kernel_calls(text, "kv_chunk_attention")) == 1
    found = _slab_sized_cuts(text, slab) + _slab_sized_layout_copies(
        text, slab)
    assert not found, f"the hybrid chunk cuts or copies the slab: {found}"
    large = [dims for dims in _score_sized_f32(
        text, cfg.num_attention_heads, CHUNK)
        if str(cfg.num_attention_heads) in dims.split(",")]
    assert not large, (
        f"float32 arrays over the 32 heads as large as a block's scores: "
        f"{large}")


# -- a latent-attention model (PR 31) ---------------------------------------
# the long-document cell's attention geometry (benchmark/configs/
# dots3-note-ep8-l5.json, traffic/longdoc-closed.json): one selecting layer
# and one window layer at published widths, 16 slots of 32,768 rows, a
# 1,024-row chunk.  Both MLPs dense and narrow: they keep no rows

LATENT_SLOTS, LATENT_MAX_LEN, LATENT_CHUNK = 16, 32768, 1024


@pytest.fixture(scope="module")
def latent_engine():
    from apex_tpu.models.dots3 import Dots3NoteConfig, Dots3NoteForCausalLM

    model = Dots3NoteForCausalLM(Dots3NoteConfig(
        vocab_size=256, intermediate_size=256,
        layer_types=("full_attention", "sliding_attention"),
        first_k_dense_replace=2), params_dtype=jnp.bfloat16)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    params = jax.tree.map(lambda l: jnp.zeros(l.shape, l.dtype), shapes)
    return sv.DecodeEngine(model, params, slots=2, max_len=64,
                           prefill_len=64, cache_dtype=jnp.bfloat16)


@pytest.fixture(scope="module")
def latent_compiled(latent_engine, one_chip):
    """``latent_compiled(program)``: the latent engine's program compiled
    for the cell-sized cache on the path the chip takes, once for the
    module."""
    def compiled(program):
        on_chip, arg = _placed(one_chip)
        cache = on_chip(jax.eval_shape(lambda: init_cache(
            latent_engine.model.cache_layers(), slots=LATENT_SLOTS,
            max_len=LATENT_MAX_LEN, dtype=jnp.bfloat16)))
        assert cache.latent.shape == (1, LATENT_SLOTS, LATENT_MAX_LEN, 640)
        params = on_chip(latent_engine.params)
        with mock.patch.object(_dispatch, "on_tpu", lambda: True):
            if program == "decode":
                lowered = latent_engine._decode.lower(
                    params, cache, *_decode_vectors(arg, LATENT_SLOTS))
            else:
                lowered = latent_engine._prefill.lower(
                    params, cache, arg((1, LATENT_CHUNK), jnp.int32),
                    arg((), jnp.int32), arg((), jnp.int32),
                    arg((), jnp.int32))
        return lowered.compile()

    return functools.cache(compiled)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_latent_rows_are_stored_by_rows_and_never_copied(latent_compiled,
                                                         program):
    """A ``[max_len, 576]`` bfloat16 row array is one XLA:TPU lays out with
    ``max_len`` in the lanes (576 is no whole lane tile, 32,768 is), and
    every program that reads rows of it then copies the whole buffer in and
    out: 1.2 GB each way a call at this cell's size.  ``LatentRows`` stores
    640 (PERF.md §6, PR 31): the buffer stays row-major and no copy of its
    size is made (with ``stored_width`` = 576 this test finds two)."""
    compiled = latent_compiled(program)
    text = compiled.as_text()
    rows = "bf16[1,%d,%d,640]" % (LATENT_SLOTS, LATENT_MAX_LEN)
    assert rows + "{3,2,1,0" in text, "the latent rows are not row-major"
    slab = LATENT_SLOTS * LATENT_MAX_LEN * 640
    copies = _slab_sized_layout_copies(text, slab)
    assert not copies, (
        f"{program}: {len(copies)} copies of the whole latent buffer, e.g. "
        f"{copies[:3]}")
    # what the program keeps beside its arguments: far below the buffer
    # that a layout copy would add (1.45 GB of temporaries with one, PR 31)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2e9


def test_latent_chunk_reads_in_place_and_keeps_no_block_of_scores(
        latent_compiled):
    """A chunk's read of the selecting layer is one
    ``latent_chunk_attention`` call on the stored rows.  As a loop in plain
    ``jax.numpy`` each 512-row block's ``[128 heads, 1024, 512]`` float32
    scores went to HBM and back four times, 1.07 GB and ~1.3 ms a block
    where the products take a quarter of that (PERF.md §5, PR 31; §6,
    PR 32): no float32 array of that size over the layer's heads is left
    in any computation of the program, and no cut of the latent buffer
    feeds the kernel."""
    text = latent_compiled("prefill").as_text()
    assert len(_kernel_calls(text, "latent_chunk_attention")) == 1
    # every operand and the result in HBM: left to XLA:TPU, the
    # long-document engine's second selecting layer was handed its 32 MB
    # selection in VMEM (``S(1)`` in its layout) and kept its result there
    # (up to 32 MB, a 512-row bucket), beside the kernel's own 56 MB, and
    # the chip never came back from the warm-up (PERF.md §6, PR 32)
    call = next(line for line in text.splitlines()
                if re.match(r"\s*%latent_chunk_attention[\w.]* = ", line))
    assert "S(1)" not in call.split(" = ")[1].split(" ")[0], call[:200]
    for name in re.findall(r"%([\w.\-]+)", call.split("custom-call(")[1]
                           .split(")")[0]):
        made = next(line for line in text.splitlines()
                    if re.match(r"\s*%%%s = " % re.escape(name), line))
        assert "S(1)" not in made.split(" = ")[1].split(" ")[0], made[:200]
    slab = LATENT_SLOTS * LATENT_MAX_LEN * 640
    found = _slab_sized_cuts(text, slab) + _slab_sized_layout_copies(
        text, slab)
    assert not found, f"the chunk cuts or copies the latent buffer: {found}"
    # (the window layer's [64, 1024, 1536] scores are ``_attend``'s: its
    # own matter, PERF.md §7)
    heads = 128
    scores = heads * LATENT_CHUNK * 512
    large = sorted({
        dims for dims in re.findall(r"\bf32\[([\d,]+)\]", text)
        if str(heads) in dims.split(",")
        and math.prod(int(d) for d in dims.split(",")) >= scores})
    assert not large, (
        f"float32 arrays over the {heads} heads of a block's scores or more "
        f"({scores} elements) in the prefill program: {large}")


# -- a model with K/V rows and rings of window K/V rows (PR 33) --------------
# the repository cell's attention geometry (benchmark/configs/
# mellum2-12b-l8.json, traffic/repo-closed.json): one window layer and two
# full layers at published widths (GQA 32 over 4 heads of 128, a window of
# 1,024), 16 slots of 32,768 rows, a 1,024-row chunk.  Two full layers: what
# XLA:TPU does to the stacked buffer once for all its layers shows from two
# on.  Eight experts and a small vocabulary: they keep no rows

WINDOW_SLOTS, WINDOW_MAX_LEN, WINDOW_CHUNK = 16, 32768, 1024


@pytest.fixture(scope="module")
def window_engine():
    from apex_tpu.models.mellum import MellumConfig, MellumForCausalLM

    model = MellumForCausalLM(MellumConfig(
        vocab_size=256, layer_types=("sliding_attention", "full_attention",
                                     "full_attention"),
        num_experts=8, experts_held=(0, 8)), params_dtype=jnp.bfloat16)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    params = jax.tree.map(lambda l: jnp.zeros(l.shape, l.dtype), shapes)
    return sv.DecodeEngine(model, params, slots=2, max_len=64,
                           prefill_len=64, cache_dtype=jnp.bfloat16)


@pytest.fixture(scope="module")
def window_compiled(window_engine, one_chip):
    """``window_compiled(program)``: the engine's program compiled for the
    cell-sized cache on the path the chip takes, once for the module."""
    def compiled(program):
        on_chip, arg = _placed(one_chip)
        cache = on_chip(jax.eval_shape(lambda: init_cache(
            window_engine.model.cache_layers(), slots=WINDOW_SLOTS,
            max_len=WINDOW_MAX_LEN, dtype=jnp.bfloat16)))
        assert cache.k.shape == (2, WINDOW_SLOTS, WINDOW_MAX_LEN, 4, 128)
        assert cache.ring_k.shape == (1, WINDOW_SLOTS, 1024, 4, 128)
        params = on_chip(window_engine.params)
        with mock.patch.object(_dispatch, "on_tpu", lambda: True):
            if program == "decode":
                lowered = window_engine._decode.lower(
                    params, cache, *_decode_vectors(arg, WINDOW_SLOTS))
            else:
                lowered = window_engine._prefill.lower(
                    params, cache, arg((1, WINDOW_CHUNK), jnp.int32),
                    arg((), jnp.int32), arg((), jnp.int32),
                    arg((), jnp.int32))
        return lowered.compile()

    return functools.cache(compiled)


WINDOW_SLAB = WINDOW_SLOTS * WINDOW_MAX_LEN * 4 * 128


def test_window_decode_reads_rows_and_ring_where_they_lie(window_compiled):
    """A decode step reads the full layer's rows and the window layer's ring
    through the same in-place kernel, one call each, on the stored buffers:
    no cut and no copy of either, and four KV heads take no padding (the
    buffers' tiles are ``T(4,128)``: the cache is the 2.35 GB it is reckoned
    at, not twice that)."""
    text = window_compiled("decode").as_text()
    assert len(_kernel_calls(text, "cached_decode_attention")) == 3
    assert "bf16[2,%d,%d,4,128]{4,3,2,1,0:T(4,128)(2,1)}" % (
        WINDOW_SLOTS, WINDOW_MAX_LEN) in text
    found = _slab_sized_cuts(text, WINDOW_SLAB) + _slab_sized_layout_copies(
        text, WINDOW_SLAB)
    assert not found, f"the decode step cuts or copies the rows: {found}"
    # the ring is smaller than a weight matrix, whose prefetch is a copy:
    # told by its shape (this model's one ring is small enough for XLA:TPU
    # to prefetch whole, ``copy-start`` / ``copy-done``: no layout copy)
    ring = re.compile(r"bf16\[(1,)?%d,1024,4,128\]" % WINDOW_SLOTS)
    moved = []
    for line in text[text.index("\nENTRY "):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\(", line)
        if m and ring.search(m.group(2)) and (
                m.group(3) in ("copy", "transpose", "slice")
                or m.group(3) == "fusion" and m.group(1).startswith(
                    ("copy", "transpose", "slice"))):
            moved.append(line.strip()[:120])
    assert not moved, f"the decode step cuts or copies the ring: {moved}"


def test_window_chunk_walks_in_the_kernel_and_keeps_no_block_of_scores(
        window_compiled):
    """A 1,024-row chunk reads the full layers' visible blocks and the
    window layer's short extent through ``kv_chunk_attention``, one call
    each, operands and result in HBM; what is cut out of the cache is one
    slot's rows head-major (``[4, 32768, 128]``, K and V, a full layer),
    never a layer's slab and never the stacked buffer - whose layout the
    cut's is pinned against: with a fence alone XLA:TPU turned all 1.07 GB
    of K and of V head-major once a chunk, 6 of its 40 ms on the chip
    (PERF.md §6, PR 33) -; and no float32 array as large as one block's
    scores is left anywhere (as a loop in ``jax.numpy`` the walk sent a
    block's ``[4, 8192, 512]`` scores to HBM and back four or five times,
    the window layer ``[4, 8192, 2048]`` at once: PERF.md §6, PR 33)."""
    compiled = window_compiled("prefill")
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if re.match(r"\s*%kv_chunk_attention[\w.]* = ", line)]
    assert len(calls) == 3
    for call in calls:
        assert "S(1)" not in call.split(" = ")[1].split(" ")[0], call[:200]
        for name in re.findall(r"%([\w.\-]+)", call.split("custom-call(")[1]
                               .split(")")[0]):
            made = next(line for line in text.splitlines()
                        if re.match(r"\s*%%%s = " % re.escape(name), line))
            assert "S(1)" not in made.split(" = ")[1].split(" ")[0], made[:200]
    found = (_slab_sized_cuts(text, WINDOW_SLAB)
             + _slab_sized_layout_copies(text, WINDOW_SLAB))
    assert not found, f"the chunk cuts or copies the layer's slab: {found}"
    one_slot = [name for name, op, sizes in _entry_ops(text)
                if WINDOW_MAX_LEN * 4 * 128 in sizes and op == "fusion"
                and name.startswith("copy")]
    assert len(one_slot) == 4, one_slot
    scores = 32 * WINDOW_CHUNK * 512
    large = sorted({
        dims for dims in re.findall(r"\bf32\[([\d,]+)\]", text)
        if math.prod(int(d) for d in dims.split(",")) >= scores
        and dims.split(",")[:2] == ["4", "8192"]})
    assert not large, (
        f"float32 arrays as large as a block's scores over a KV head's 8 x "
        f"1,024 query rows in the prefill program: {large}")
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


# -- latent rows read whole: multi-head latent attention without a selector
# (Xing4.0) -------------------------------------------------------------------
# the mladoc cell's attention geometry (benchmark/configs/xing4-29b-l6.json,
# traffic/mladoc-closed.json): two layers at published attention widths (32
# heads, rank 512 + rope 64 stored 640 wide, a query LoRA of 768), 16 slots
# of 24,576 rows, a 1,024-row chunk.  Dense and narrow MLPs: they keep no
# rows

MLA_SLOTS, MLA_MAX_LEN, MLA_CHUNK, MLA_LAYERS = 16, 24576, 1024, 2


@pytest.fixture(scope="module")
def mla_compiled(one_chip):
    """``mla_compiled(program)``: the program compiled for the cell-sized
    cache on the path the chip takes, once for the module."""
    from apex_tpu.models.xing import XingConfig, XingForCausalLM

    model = XingForCausalLM(XingConfig(
        vocab_size=256, intermediate_size=256, num_hidden_layers=MLA_LAYERS,
        first_k_dense_replace=MLA_LAYERS), params_dtype=jnp.bfloat16)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    params = jax.tree.map(lambda l: jnp.zeros(l.shape, l.dtype), shapes)
    engine = sv.DecodeEngine(model, params, slots=2, max_len=64,
                             prefill_len=64, cache_dtype=jnp.bfloat16)

    def compiled(program):
        on_chip, arg = _placed(one_chip)
        cache = on_chip(jax.eval_shape(lambda: init_cache(
            model.cache_layers(), slots=MLA_SLOTS, max_len=MLA_MAX_LEN,
            dtype=jnp.bfloat16)))
        assert cache.latent.shape == (MLA_LAYERS, MLA_SLOTS, MLA_MAX_LEN,
                                      640)
        with mock.patch.object(_dispatch, "on_tpu", lambda: True):
            if program == "decode":
                lowered = engine._decode.lower(
                    on_chip(params), cache,
                    *_decode_vectors(arg, MLA_SLOTS))
            else:
                lowered = engine._prefill.lower(
                    on_chip(params), cache, arg((1, MLA_CHUNK), jnp.int32),
                    arg((), jnp.int32), arg((), jnp.int32),
                    arg((), jnp.int32))
        return lowered.compile()

    return functools.cache(compiled)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_latent_rows_read_whole_are_never_copied_or_cut(mla_compiled,
                                                        program):
    """Every live row is read where it lies: the buffer stays row-major, no
    copy or cut of its size is made, and what the program keeps beside its
    arguments is far below one layer's rows (0.5 GB here)."""
    compiled = mla_compiled(program)
    text = compiled.as_text()
    rows = "bf16[%d,%d,%d,640]" % (MLA_LAYERS, MLA_SLOTS, MLA_MAX_LEN)
    assert rows + "{3,2,1,0" in text, "the latent rows are not row-major"
    slab = MLA_SLOTS * MLA_MAX_LEN * 640
    found = _slab_sized_layout_copies(text, slab) + _slab_sized_cuts(
        text, slab)
    assert not found, f"{program} copies or cuts the latent rows: {found}"
    limit = 64e6 if program == "decode" else 0.4e9
    assert compiled.memory_analysis().temp_size_in_bytes < limit


def test_decode_reads_whole_rows_in_a_loop_bounded_by_the_lanes(
        mla_compiled):
    """The decode step's read of each layer is a loop over blocks of rows
    that takes the whole buffer as it lies and stops at a bound computed
    from the lanes' lengths (its condition compares the counter with a
    value of the loop's state, not a constant: no block past the longest
    lane is read)."""
    text = mla_compiled("decode").as_text()
    rows = "bf16[%d,%d,%d,640]" % (MLA_LAYERS, MLA_SLOTS, MLA_MAX_LEN)
    loops = [line for line in text.splitlines()
             if re.match(r"\s*%while[\w.]* = \(", line) and rows in
             line.split(" while(")[0]]
    assert len(loops) == MLA_LAYERS, len(loops)
    for line in loops:
        cond = re.search(r"condition=%([\w.\-]+)", line).group(1)
        body = text[text.index(f"\n%{cond} "):]
        body = body[:body.index("\n}")]
        root = next(l for l in body.splitlines() if "ROOT" in l)
        assert "compare(" in root and root.count("get-tuple-element") == 2, (
            root[:200])


def test_mla_chunk_reads_through_the_kernel_under_a_causal_mask(
        mla_compiled):
    """A chunk of a layer without a selector is read by the chunk kernel,
    once a layer, on the stored rows."""
    text = mla_compiled("prefill").as_text()
    assert len(_kernel_calls(text, "latent_chunk_attention")) == MLA_LAYERS


# -- the head of a prompt chunk on the one row the engine keeps -------------
# ``_prefill`` asks the model for the chunk's last real row (``keep=``): the
# final norm and the head run on that row alone, where they once multiplied
# every row of the bucket and dropped all but one (5.05 ms of a 46.6 ms
# 1,024-row chunk at a vocabulary of 131,072 on one v5e).  ``_verify`` keeps
# every row: the acceptance reads each.  A vocabulary no other width of the model
# shares, so that a dot of ``rows x vocab`` elements is the head's

HEAD_CFG = LlamaConfig(vocab_size=1000, hidden_size=256, intermediate_size=384,
                       num_hidden_layers=1, num_attention_heads=2,
                       num_key_value_heads=1, max_position_embeddings=4096)


def _dot_results(text):
    """Element counts of the results of every ``dot`` and ``convolution``
    of the compiled program, in fused computations too."""
    return [math.prod(int(d) for d in dims.split(",") if d)
            for dims in re.findall(
                r"= \w+\[([\d,]*)\]\S* (?:dot|convolution)\(", text)]


@pytest.mark.parametrize("program", ["prefill", "verify"])
def test_only_verify_runs_the_head_on_every_row(one_chip, program):
    model = LlamaForCausalLM(HEAD_CFG)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    params = jax.tree.map(
        lambda l: jnp.zeros(l.shape,
                            jnp.bfloat16 if l.ndim >= 2 else l.dtype),
        shapes)
    engine = sv.DecodeEngine(model, params, slots=2, max_len=CHUNK,
                             prefill_len=CHUNK, cache_dtype=jnp.bfloat16)
    on_chip, arg = _placed(one_chip)
    cache = on_chip(jax.eval_shape(lambda: init_cache(
        model.cache_layers(), slots=2, max_len=MAX_LEN,
        dtype=jnp.bfloat16)))
    lower = engine._prefill if program == "prefill" else engine._verify
    text = lower.lower(
        on_chip(engine.params), cache, arg((1, CHUNK), jnp.int32),
        arg((), jnp.int32), arg((), jnp.int32),
        arg((), jnp.int32)).compile().as_text()
    dots = _dot_results(text)
    assert dots, "no dot found in the compiled text: the reader is stale"
    every_row = CHUNK * HEAD_CFG.vocab_size
    # on the chip one row's product is a multiply-reduce, no dot at all
    if program == "prefill":
        assert every_row not in dots, (
            "the prefill program multiplies every row of its bucket by the "
            "head: the chunk's kept row no longer reaches the model")
    else:
        assert every_row in dots


# -- K/V rows beside a convolution tail: compressed convolutional attention
# (ZAYA1) ---------------------------------------------------------------------
# the reason cell's geometry (benchmark/configs/zaya1-8b-l10.json,
# traffic/reason-closed.json): two layers at the published widths (8 query
# and 2 K/V heads of 128, 16 experts of 2,048), 64 slots of 8,192 rows, a
# 512-row chunk.  A small vocabulary: it keeps no rows.  The weights are
# shapes alone: a program is lowered, nothing runs

CCA_SLOTS, CCA_MAX_LEN, CCA_CHUNK, CCA_LAYERS = 64, 8192, 512, 2


@pytest.fixture(scope="module")
def cca_compiled(one_chip):
    """``cca_compiled(program)``: the program compiled for the cell-sized
    cache on the path the chip takes, once for the module."""
    from apex_tpu.models.zaya import ZayaConfig, ZayaForCausalLM

    model = ZayaForCausalLM(ZayaConfig(vocab_size=256,
                                       num_hidden_layers=CCA_LAYERS),
                            params_dtype=jnp.bfloat16)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    engine = sv.DecodeEngine(model, {"w": jnp.zeros((1,), jnp.bfloat16)},
                             slots=2, max_len=64, prefill_len=64,
                             cache_dtype=jnp.bfloat16)

    def compiled(program):
        on_chip, arg = _placed(one_chip)
        cache = on_chip(jax.eval_shape(lambda: init_cache(
            model.cache_layers(), slots=CCA_SLOTS, max_len=CCA_MAX_LEN,
            dtype=jnp.bfloat16)))
        assert cache.k.shape == (CCA_LAYERS, CCA_SLOTS, CCA_MAX_LEN, 2, 128)
        assert cache.state.conv.shape == (CCA_LAYERS, CCA_SLOTS, 2688)
        assert cache.state.ssm.size == 0
        with mock.patch.object(_dispatch, "on_tpu", lambda: True):
            if program == "decode":
                lowered = engine._decode.lower(
                    on_chip(shapes), cache,
                    *_decode_vectors(arg, CCA_SLOTS))
            else:
                lowered = engine._prefill.lower(
                    on_chip(shapes), cache, arg((1, CCA_CHUNK), jnp.int32),
                    arg((), jnp.int32), arg((), jnp.int32),
                    arg((), jnp.int32))
        return lowered.compile()

    return functools.cache(compiled)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_cca_programs_copy_no_rows_and_no_tails(cca_compiled, program):
    """The K/V rows are read in place by the seam's kernels, one call a
    layer, and neither they nor the convolution tails are cut out, turned
    or copied whole: a decode step writes its lanes' tails into the donated
    buffer, a chunk one slot's."""
    text = cca_compiled(program).as_text()
    kernel = ("cached_decode_attention" if program == "decode"
              else "kv_chunk_attention")
    assert len(_kernel_calls(text, kernel)) == CCA_LAYERS
    slab = CCA_SLOTS * CCA_MAX_LEN * 2 * 128
    found = _slab_sized_layout_copies(text, slab) + _slab_sized_cuts(
        text, slab)
    assert not found, f"{program} cuts or copies the K/V rows: {found}"
    # a decode step reads one layer's tails of every lane, [64, 2688]: what
    # it must; the whole buffer is every layer's
    tails = re.compile(r"bf16\[%d,%d,2688\]" % (CCA_LAYERS, CCA_SLOTS))
    moved = []
    for line in text[text.index("\nENTRY "):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\(", line)
        if m and tails.search(m.group(2)) and (
                m.group(3) in ("copy", "transpose", "slice")
                or m.group(3) == "fusion" and m.group(1).startswith(
                    ("copy", "transpose", "slice"))):
            moved.append(line.strip()[:120])
    assert not moved, f"{program} copies the tails whole: {moved}"
