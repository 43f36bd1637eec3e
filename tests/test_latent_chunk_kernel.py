"""A prompt chunk's read of a selecting latent layer
(``ops/latent_chunk_attention.py``) against the blocked loop it replaces
(``serving/kv_cache.py::_chunk_read``), with the kernel's own code run by the
Pallas interpreter on the CPU: first the kernel alone on the same buffers,
then through the seam (``latent_prefill_attend``), then whole engines whose
prefill programs take it.

Kernel and loop run the same recurrence over the same 128-row blocks (the
toy cache's ``_key_block``), but a head at a time against all heads in one
contraction, so they are compared to a tolerance:

- float32: ``F32_ATOL`` = 5e-6 on results of O(1) (the scale of
  ``tests/conftest.py::LOGITS_ATOL``, which compares two programs over the
  same dot products);
- bf16: ``BF16_TOL`` = 2e-2, absolute and relative: expanded K and V and the
  probabilities are rounded to bf16 on both sides, and a bf16 value of
  magnitude 2-4 has an ulp of 1.6e-2.

What the comparisons guard - a row read past the chunk's end, a row no query
selected, the wrong layer or slot - is a NaN here, not a small error: the
kernel's buffers hold NaN in every such row, the loop's are clean.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import _logging
from apex_tpu import serving as sv
from apex_tpu.models.dots3 import Dots3NoteConfig, Dots3NoteForCausalLM
from apex_tpu.ops import latent_chunk_attention as lca
from apex_tpu.serving import kv_cache as kvc

F32_ATOL = 5e-6
BF16_TOL = 2e-2
# widths in whole lane tiles (the kernel's predicate), everything else tiny:
# 4 heads in groups of 2, queries in tiles of 64, rows stored 256 wide, 4
# blocks of 128 rows
HEADS, RANK, ROPE, NOPE, DV = 4, 128, 64, 128, 128
WIDTH = RANK + ROPE
MAX_LEN, LAYERS, SLOTS, LAYER, SLOT = 512, 2, 3, 1, 2
BLOCK = kvc._key_block(MAX_LEN)
BUCKETS = [16, 64, 128]
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


@pytest.fixture(autouse=True)
def _interpret_kernels(monkeypatch):
    monkeypatch.setenv("APEX_TPU_KERNELS", "interpret")
    # two groups of heads, and two tiles of queries in the 128-row bucket
    monkeypatch.setattr(lca, "GROUP", 2)
    monkeypatch.setattr(lca, "TILE", 64)
    yield


@pytest.fixture
def dispatched():
    """Each ``latent_chunk_attention`` dispatch event."""
    seen = []

    def sink(event):
        if (event["event"] == "kernel_dispatch"
                and event["op"] == "latent_chunk_attention"):
            seen.append(event)

    _logging.add_event_sink(sink)
    yield seen
    _logging.remove_event_sink(sink)


def _close(got, want, dtype, msg=""):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert got.shape == want.shape, msg
    assert np.isfinite(got).all(), f"{msg}: the kernel read a NaN row"
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=0, atol=F32_ATOL,
                                   err_msg=msg)
    else:
        np.testing.assert_allclose(got, want, rtol=BF16_TOL, atol=BF16_TOL,
                                   err_msg=msg)


def _offsets(m):
    """The start of the cache, inside a block, several blocks in, and the
    chunk that ends with ``max_len``."""
    return [0, 37, 2 * BLOCK + 5, MAX_LEN - m]


def _selection(kind, m, offset, rng):
    """``[m, MAX_LEN]`` bool: of the rows ``idx <= offset + i`` query ``i``
    sees, all of them, a scattered tenth, or exactly one."""
    at = offset + np.arange(m)
    visible = np.arange(MAX_LEN)[None] <= at[:, None]
    if kind == "all":
        return visible
    if kind == "tenth":
        chosen = visible & (rng.random((m, MAX_LEN)) < 0.1)
        chosen[np.arange(m), at] = True         # never an empty row
        return chosen
    one = np.zeros((m, MAX_LEN), bool)
    one[np.arange(m), (rng.random(m) * (at + 1)).astype(int)] = True
    return one


def _buffers(dtype, selected, seed=0):
    """Queries, the expansion's matrix, clean latent rows for the loop, and
    the same rows with NaN wherever the kernel must not look: every row no
    query selected (those past the chunk's end among them), every other
    layer and slot."""
    rng = np.random.default_rng(seed)
    m = selected.shape[0]
    stored = kvc.LatentRows(WIDTH, 8, 8).stored_width
    latent = np.zeros((LAYERS, SLOTS, MAX_LEN, stored), np.float32)
    latent[..., :WIDTH] = rng.standard_normal(latent.shape[:-1] + (WIDTH,))
    planted = np.full_like(latent, np.nan)
    live = selected.any(0)
    planted[LAYER, SLOT, live] = latent[LAYER, SLOT, live]
    q = rng.standard_normal((m, HEADS, NOPE + ROPE)) * (NOPE + ROPE) ** -0.5
    w = rng.standard_normal((RANK, HEADS, NOPE + DV)) * RANK ** -0.5
    return tuple(jnp.asarray(a, dtype) for a in (q, w, latent, planted))


@functools.cache
def _reads(m, dtype):
    """One program of each read a (bucket, dtype): layer, slot, the bound
    and the selection are operands."""
    del m, dtype

    def loop(q, latent, selected, w, layer, slot, blocks):
        return kvc._chunk_read(q, latent, selected, {"w": w, "nope": NOPE},
                               layer, slot, blocks, block=BLOCK, width=WIDTH)

    def kernel(q, latent, selected, w, layer, slot, blocks):
        return lca.latent_chunk_attention(q, latent, selected, w, layer,
                                          slot, blocks, nope=NOPE,
                                          block=BLOCK)

    return jax.jit(loop), jax.jit(kernel)


@pytest.mark.parametrize("kind", ["all", "tenth", "one"])
@pytest.mark.parametrize("dtype", list(DTYPES))
def test_kernel_matches_the_loop_and_reads_only_selected_rows(dtype, kind):
    """Every bucket at every offset.  (One case a dtype and a selection,
    the buckets inside: a file of fewer cases is handed to a worker later,
    and this one's interpreter runs kept every core busy beside
    ``tests/test_serving_slo.py``'s wall-clock bound.)"""
    dt = DTYPES[dtype]
    for m in BUCKETS:
        loop, kernel = _reads(m, dtype)
        rng = np.random.default_rng(m)
        for offset in _offsets(m):
            selected = _selection(kind, m, offset, rng)
            q, w, latent, planted = _buffers(dt, selected, seed=offset)
            blocks = (offset + m - 1) // BLOCK + 1
            args = (jnp.asarray(selected), w, jnp.int32(LAYER),
                    jnp.int32(SLOT), jnp.int32(blocks))
            want = loop(q, latent, *args)
            got = kernel(q, planted, *args)
            assert got.dtype == jnp.float32
            _close(got, want, dt, f"bucket {m} offset {offset}")
        # offset, layer, slot and the bound are operands: one program a
        # bucket
        assert kernel._cache_size() == 1


def test_a_querys_result_does_not_hang_on_its_neighbours_selection():
    m, offset = 64, 200
    rng = np.random.default_rng(1)
    selected = _selection("tenth", m, offset, rng)
    q, w, latent, _ = _buffers(jnp.float32, selected)
    _, kernel = _reads(m, "float32")
    blocks = jnp.int32((offset + m - 1) // BLOCK + 1)
    first = np.asarray(kernel(q, latent, jnp.asarray(selected), w, LAYER,
                              SLOT, blocks))
    moved = selected.copy()
    moved[1::2] = _selection("tenth", m, offset, rng)[1::2]
    second = np.asarray(kernel(q, latent, jnp.asarray(moved), w, LAYER, SLOT,
                               blocks))
    assert np.array_equal(first[0::2], second[0::2])
    assert not np.array_equal(first[1::2], second[1::2])


# ---- through the seam -------------------------------------------------------

TOP_K, INDEX, J = 40, 16, 2


def _seam_inputs(dtype, n, seed=0, **sizes):
    rank, nope = sizes.get("rank", RANK), sizes.get("nope", NOPE)
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    return dict(
        q=normal(n, HEADS, nope + ROPE), rows=normal(n, rank + ROPE),
        w=normal(rank, HEADS, nope + DV) * rank ** -0.5,
        select={"q": normal(n, J, INDEX), "key": normal(n, INDEX),
                "w": jnp.asarray(rng.standard_normal((n, J)), jnp.float32)})


def _seam_cache(dtype, max_len=MAX_LEN, rank=RANK):
    return kvc.init_cache([kvc.LatentRows(rank + ROPE, INDEX, TOP_K)] * LAYERS,
                          slots=SLOTS, max_len=max_len, dtype=dtype)


def _chunks(cache, inputs, cuts, nope=NOPE):
    """The prompt of ``inputs`` through ``latent_prefill_attend`` in chunks
    that end at ``cuts``: every chunk's context, stacked."""
    out, start = [], 0
    for end in cuts:
        part = lambda a: a[start:end]           # noqa: E731
        ctx, cache = kvc.latent_prefill_attend(
            cache, LAYER, SLOT, part(inputs["q"]), part(inputs["rows"]),
            start, scale=(nope + ROPE) ** -0.5,
            expand={"w": inputs["w"], "nope": nope},
            select=dict({k: part(v) for k, v in inputs["select"].items()},
                        top_k=TOP_K, scale=0.1))
        out.append(ctx)
        start = end
    return jnp.concatenate(out)


@pytest.mark.parametrize("dtype", list(DTYPES))
def test_splitting_a_prompt_into_chunks_gives_the_same_context(dtype,
                                                               dispatched):
    """320 rows (eight times ``top_k``) as one chunk after another of the
    toy engine's buckets: every split hands the kernel other queries, bounds
    and masks, and every row's context stays within the tolerance - of the
    same chunks through the loop too."""
    dt = DTYPES[dtype]
    inputs = _seam_inputs(dt, 320)
    whole = _chunks(_seam_cache(dt), inputs, [128, 256, 320])
    assert dispatched and {e["path"] for e in dispatched} == {"pallas"}
    assert {e["m"] for e in dispatched} == {128, 64}
    split = _chunks(_seam_cache(dt), inputs,
                    [16, 80, 208, 224, 240, 304, 320])
    _close(split, whole, dt, "split against whole")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("APEX_TPU_KERNELS", "0")
        looped = _chunks(_seam_cache(dt), inputs, [128, 256, 320])
    _close(whole, looped, dt, "kernel against loop")


def test_kernel_and_loop_are_handed_the_same_selection(monkeypatch):
    """The selection is made before the read and does not know which read
    follows: both are handed the same mask, to the bit."""
    seen = {}

    def spy(name, fn, at):
        def inner(*args, **kw):
            seen.setdefault(name, []).append(np.asarray(args[at]))
            return fn(*args, **kw)
        return inner

    monkeypatch.setattr(kvc, "latent_chunk_attention",
                        spy("kernel", kvc.latent_chunk_attention, 2))
    monkeypatch.setattr(kvc, "_chunk_read", spy("loop", kvc._chunk_read, 2))
    inputs = _seam_inputs(jnp.bfloat16, 192, seed=3)
    _chunks(_seam_cache(jnp.bfloat16), inputs, [64, 192])
    monkeypatch.setenv("APEX_TPU_KERNELS", "0")
    _chunks(_seam_cache(jnp.bfloat16), inputs, [64, 192])
    assert len(seen["kernel"]) == len(seen["loop"]) == 2
    for a, b in zip(seen["kernel"], seen["loop"]):
        assert a.dtype == bool and a.sum() > 0 and np.array_equal(a, b)


@pytest.mark.parametrize("why,build", [
    ("a rank of no whole lane tile", dict(rank=96)),
    ("a head's K half of no whole lane tile", dict(nope=64)),
    ("blocks under 128 rows", dict(max_len=256)),
    ("a bucket of no whole sublane tile", dict(n=12)),
    ("rows stored in another type than the queries'",
     dict(cache_dtype=jnp.bfloat16)),
])
def test_shapes_the_kernel_does_not_take_go_through_the_loop(why, build,
                                                             dispatched):
    sizes = {k: build[k] for k in ("rank", "nope") if k in build}
    inputs = _seam_inputs(jnp.float32, build.get("n", 16), **sizes)
    cache = _seam_cache(build.get("cache_dtype", jnp.float32),
                        build.get("max_len", MAX_LEN),
                        sizes.get("rank", RANK))
    ctx = _chunks(cache, inputs, [build.get("n", 16)],
                  nope=sizes.get("nope", NOPE))
    assert np.isfinite(np.asarray(ctx)).all()
    assert [e["path"] for e in dispatched] == ["reference"], why


def test_with_kernels_disabled_nothing_is_dispatched(dispatched, monkeypatch):
    monkeypatch.setenv("APEX_TPU_KERNELS", "0")
    inputs = _seam_inputs(jnp.float32, 16)
    _chunks(_seam_cache(jnp.float32), inputs, [16])
    assert not dispatched


# ---- engines whose prefill programs take the kernel ------------------------

# the full layers at the kernel's widths (2 heads, rank 128, K and V halves
# 128, rope 64), a window layer between them, every other size as
# tests/test_dots3.py::TOY
FULL, WINDOW = "full_attention", "sliding_attention"
TOY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96,
    layer_types=(FULL, WINDOW, FULL), first_k_dense_replace=1,
    num_attention_heads=2, q_lora_rank=16, kv_lora_rank=RANK,
    qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE, v_head_dim=DV,
    rope_theta=8e7, index_n_heads=2, index_head_dim=ROPE, index_topk=24,
    swa_num_attention_heads=2, swa_q_lora_rank=16, swa_kv_lora_rank=16,
    swa_qk_nope_head_dim=12, swa_qk_rope_head_dim=4, swa_v_head_dim=8,
    swa_rope_theta=5e4, sliding_window_size=5, n_routed_experts=16,
    num_experts_per_tok=2, moe_intermediate_size=24,
    routed_scaling_factor=1.0, rms_norm_eps=1e-5, experts_held=(4, 4))
# float32 logits of O(1) through two full layers whose reads differ by
# F32_ATOL-scale rounding
ENGINE_ATOL = 1e-4


def _drive(engine):
    """Three prompts through chunks of several buckets at offsets up to
    three blocks in, then greedy steps: every prefill's logits and the
    streams."""
    rng = np.random.default_rng(0)
    logits, last = [], np.zeros((3,), np.int32)
    for slot, n in enumerate((9, 150, 400)):
        row = np.asarray(engine.prefill(slot, rng.integers(0, 256, n).tolist()))
        logits.append(row)
        last[slot] = int(np.argmax(row))
    streams = [[int(t)] for t in last]
    for _ in range(4):
        out = np.asarray(engine.decode(last, np.ones((3,), bool)))
        logits.append(out)
        last = np.argmax(out, -1).astype(np.int32)
        for slot in range(3):
            streams[slot].append(int(last[slot]))
    return streams, logits


def test_engine_prefill_through_the_kernel_is_the_loops(dispatched,
                                                        monkeypatch):
    model = Dots3NoteForCausalLM(Dots3NoteConfig(**TOY))
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    params = jax.tree.map(lambda l: l if l.ndim == 1 else 5 * l, params)

    def engine():
        return sv.DecodeEngine(model, params, slots=3, max_len=MAX_LEN,
                               prefill_len=64)

    with monkeypatch.context() as mp:
        mp.setenv("APEX_TPU_KERNELS", "0")
        want_streams, want_logits = _drive(engine())
    assert not dispatched
    eng = engine()
    got_streams, got_logits = _drive(eng)
    assert eng.decode_compiles() == 1
    assert eng.prefill_compiles() <= len(eng.prefill_buckets)
    # every bucket the prompts used, both full layers, all on the kernel
    assert {e["m"] for e in dispatched} == {16, 32, 64}
    assert all(e["path"] == "pallas" and e["block"] == BLOCK
               and e["heads"] == 2 and e["stored"] == 256 for e in dispatched)
    assert len(dispatched) == 2 * eng.prefill_compiles()
    assert got_streams == want_streams
    for step, (got, want) in enumerate(zip(got_logits, want_logits)):
        np.testing.assert_allclose(got, want, rtol=0, atol=ENGINE_ATOL,
                                   err_msg=f"call {step}")


def test_engine_without_a_selector_prefills_through_the_kernel(dispatched,
                                                               monkeypatch):
    """A model whose latent layers read every row (Xing4.0's MLA,
    ``FullLatentRows``) at the kernel's widths: its chunks hand the kernel
    the causal mask for a selection, and prefill and decode agree with the
    loop's."""
    from apex_tpu.models.xing import XingConfig, XingForCausalLM

    model = XingForCausalLM(XingConfig(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, first_k_dense_replace=1, num_attention_heads=2,
        q_lora_rank=16, kv_lora_rank=RANK, qk_nope_head_dim=NOPE,
        qk_rope_head_dim=ROPE, v_head_dim=DV, n_routed_experts=8,
        experts_held=(0, 8), num_experts_per_tok=2, moe_intermediate_size=24))
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    params = jax.tree.map(lambda l: l if l.ndim == 1 else 5 * l, params)

    def engine():
        return sv.DecodeEngine(model, params, slots=3, max_len=MAX_LEN,
                               prefill_len=64)

    with monkeypatch.context() as mp:
        mp.setenv("APEX_TPU_KERNELS", "0")
        want_streams, want_logits = _drive(engine())
    assert not dispatched
    eng = engine()
    got_streams, got_logits = _drive(eng)
    assert {e["m"] for e in dispatched} == {16, 32, 64}
    assert all(e["path"] == "pallas" and e["stored"] == 256
               for e in dispatched)
    assert len(dispatched) == 2 * eng.prefill_compiles()
    assert got_streams == want_streams
    for step, (got, want) in enumerate(zip(got_logits, want_logits)):
        np.testing.assert_allclose(got, want, rtol=0, atol=ENGINE_ATOL,
                                   err_msg=f"call {step}")
