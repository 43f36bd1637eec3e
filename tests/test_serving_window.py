"""A model whose slots keep K/V rows for its full layers and rings of the
window's K/V rows for the others through ``DecodeEngine`` and the scheduler
at their defaults: toy Mellum on the CPU in float32, against the plain
reference's full forward - past several wraps of the ring, with a chunk
longer than the window, under every split into chunks and under both reads a
chunk of a full layer may take; then the two new reads alone against the
reads they stand beside, with NaN planted wherever they must not look."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu import _logging, obs  # noqa: E402
from apex_tpu import serving as sv  # noqa: E402
from apex_tpu.ops import cached_decode_attention as cda  # noqa: E402
from apex_tpu.serving import kv_cache as kvc  # noqa: E402
from benchmark.reference import mellum as ref  # noqa: E402
from test_mellum import TOY, TOY_REF, ids_of, make, rel_err  # noqa: E402

# a chunk of two windows; 96 rows are twelve windows, six rings
SLOTS, MAX_LEN, CHUNK = 3, 96, 16
WINDOW = TOY["sliding_window"]


@pytest.fixture(scope="module")
def served():
    return make()


def engine(served, **kw):
    model, params = served
    return sv.DecodeEngine(model, params, **{
        "slots": SLOTS, "max_len": MAX_LEN, "prefill_len": CHUNK, **kw})


@pytest.fixture(scope="module")
def eng(served):
    """One engine for the tests that leave its slots free again."""
    return engine(served)


@pytest.fixture(params=["full_extent", "blocked_walk"])
def read(request, monkeypatch):
    """Both reads a chunk of a full layer may take: at toy sizes the scores
    of a whole extent are small, so the walk is chosen by lowering the size
    it is chosen from."""
    if request.param == "blocked_walk":
        monkeypatch.setattr(kvc, "_FULL_READ_BYTES", 0)
    return request.param


def decode_one(eng, slot, token):
    tokens = np.zeros((eng.slots,), np.int32)
    active = np.zeros((eng.slots,), bool)
    tokens[slot], active[slot] = token, True
    return eng.decode(tokens, active)[slot]


def reference(served, ids, positions, **changed):
    return ref.logits_at(served[1], np.asarray(ids, np.int32), positions,
                         dict(TOY_REF, **changed))


def test_cache_keeps_max_len_rows_for_full_layers_and_a_ring_for_the_rest(
        eng):
    cache = eng.cache
    assert isinstance(cache, kvc.WindowKVCache)
    # two full layers keep every row; six window layers keep the window (8)
    # in whole 16-row tiles, whatever max_len and the chunk are
    assert cache.k.shape == cache.v.shape == (2, SLOTS, MAX_LEN, 2, 16)
    assert cache.ring_k.shape == cache.ring_v.shape == (6, SLOTS, 16, 2, 16)
    assert cache.counters.shape == (8, 5)
    assert cache.lengths.shape == (SLOTS,)
    assert eng.other_state == ["CallCounters: call counters",
                               "KVWindowRows: a ring of window K/V rows"]
    assert not eng.recurrent_state


def test_ring_is_the_window_whatever_the_cache_holds():
    from apex_tpu.models.mellum import MellumConfig, MellumForCausalLM

    model = MellumForCausalLM(MellumConfig(
        layer_types=("sliding_attention",) * 3 + ("full_attention",)))
    layers = model.cache_layers()                          # published widths
    rings = [l for l in layers if isinstance(l, kvc.KVWindowRows)]
    assert rings == [kvc.KVWindowRows(4, 128, 1024)] * 3
    assert rings[0].rows == 1024
    assert [l for l in layers if isinstance(l, kvc.KVRows)] == [
        kvc.KVRows(4, 128)]
    shapes = jax.eval_shape(lambda: kvc.init_cache(
        layers, slots=16, max_len=32768, dtype=jnp.bfloat16))
    assert shapes.k.shape == (1, 16, 32768, 4, 128)
    assert shapes.ring_k.shape == (3, 16, 1024, 4, 128)
    # a window of no whole tile is rounded up, never to max_len
    assert kvc.KVWindowRows(4, 128, 1000).rows == 1008


def test_chunked_prefill_then_decode_match_the_reference(served, read):
    """Five chunks (16 x 4 + a padded 11) and eleven tokens through the
    cache: 86 positions, ten windows, five wraps of the 16-row ring, every
    chunk two windows long."""
    eng = engine(served)
    ids = ids_of(86, seed=1)
    at = [15, 31, 47, 63, 74] + list(range(75, 86))
    want = reference(served, ids, at)
    got = [eng.prefill_chunk(1, ids[a:min(a + CHUNK, 75)].tolist())
           for a in range(0, 75, CHUNK)]
    for t in range(75, 86):
        got.append(decode_one(eng, 1, int(ids[t])))
    for g, w in zip(got, want):
        assert rel_err(g, w) < 1e-5, read
    assert rel_err(np.stack(got), want) < 1e-5


@pytest.mark.parametrize("fault", [
    "window_one_wider", "window_layer_read_at_full_extent",
    "full_layer_windowed", "attention_factor_left_out", "top_k_less_one"])
def test_cached_path_fails_a_reference_with_one_mechanism_changed(
        served, eng, fault):
    from test_mellum import mutated_reference

    ids = ids_of(60, seed=2)
    first = eng.prefill(0, ids[:56].tolist())
    last = first
    for t in range(56, 60):
        last = decode_one(eng, 0, int(ids[t]))
    eng.release(0)
    got = np.stack([first, last])
    assert rel_err(got, reference(served, ids, [55, 59])) < 1e-5
    bad = mutated_reference(fault, served[1], ids, [55, 59])
    assert rel_err(got, bad) > 100 * 1e-5, fault


# one chunk against many: the same rows under the same masks, summed in
# another order (a ring read in ring order, a block at a time in the walk)
SPLIT_TOL = 2e-6


@pytest.mark.parametrize("chunk", [4, 64])
def test_splitting_a_prompt_into_chunks_moves_no_logit_past_the_tolerance(
        served, eng, read, chunk):
    """One 64-row chunk (eight windows in one call) against sixteen of 4
    (half a window each) against the shared engine's four of 16."""
    ids = ids_of(61, seed=3).tolist()
    want = np.asarray(eng.prefill(2, ids))
    want_next = np.asarray(decode_one(eng, 2, 7))
    eng.release(2)
    other = engine(served, prefill_len=chunk)
    got = other.prefill(0, ids)
    assert rel_err(got, want) < SPLIT_TOL, (read, chunk)
    assert rel_err(decode_one(other, 0, 7), want_next) < SPLIT_TOL


def test_lanes_do_not_read_each_others_rows_nor_a_slots_last_request(
        served, eng):
    a, b = ids_of(30, seed=5).tolist(), ids_of(44, seed=6).tolist()
    eng.prefill(0, a)
    alone_a = np.asarray(decode_one(eng, 0, 9))
    eng.release(0)
    eng.prefill(2, b)
    alone_b = np.asarray(decode_one(eng, 2, 11))
    eng.release(2)
    eng.prefill(0, b[:20])            # stale rows under the next request
    eng.release(0)
    eng.prefill(0, a)
    eng.prefill(2, b)
    both = eng.decode(np.asarray([9, 0, 11], np.int32),
                      np.asarray([True, False, True]))
    eng.release(0)
    eng.release(2)
    assert rel_err(both[0], alone_a) < SPLIT_TOL
    assert rel_err(both[2], alone_b) < SPLIT_TOL


def test_an_idle_lanes_write_leaves_its_window_alone(served, eng):
    """Decode steps of another slot between the chunks of a prompt: the lane
    in prefill is idle in them and writes a row at its length, in a ring the
    row of a position that has just left the window."""
    ids = ids_of(50, seed=8).tolist()
    want = np.asarray(eng.prefill(1, ids))
    eng.release(1)
    eng.prefill(0, ids_of(9, seed=9).tolist())
    got = None
    for a in range(0, 50, CHUNK):
        got = eng.prefill_chunk(1, ids[a:a + CHUNK])
        decode_one(eng, 0, 5)
    eng.release(0)
    eng.release(1)
    assert rel_err(got, want) < SPLIT_TOL


def test_scheduler_serves_it_with_one_program_a_bucket(served):
    eng = engine(served, slots=2, prefill_buckets=(8, 16))
    sched = sv.ContinuousBatchingScheduler(eng)
    prompts = {f"r{i}": ids_of(n, seed=10 + i).tolist()
               for i, n in enumerate((45, 7, 23, 38))}
    for rid, prompt in prompts.items():
        sched.submit(sv.Request(rid, prompt, 5))
    results = sched.run()
    assert {rid: len(r.tokens) for rid, r in results.items()} == dict.fromkeys(
        prompts, 5)
    assert eng.decode_compiles() == 1
    assert eng.prefill_compiles() <= 2
    # greedy streams are the reference's argmax, teacher-forced
    for rid, prompt in prompts.items():
        seq = prompt + results[rid].tokens
        want = reference(served, seq, list(range(len(prompt) - 1,
                                                 len(seq) - 1)))
        assert np.asarray(want).argmax(-1).tolist() == results[rid].tokens
    stats = eng.moe_stats()
    assert set(stats) == {"steps", "tokens", "pairs", "touched", "max_load"}
    assert stats["steps"].shape == (8,) and (stats["tokens"] > 0).all()
    # every expert is held: each token's two choices land here
    assert (stats["pairs"] == 2 * stats["tokens"]).all()
    sched.close()


def test_decode_span_counts_the_rows_the_step_reads(served):
    eng = engine(served)
    eng.prefill(0, ids_of(20).tolist())
    eng.prefill(1, ids_of(3).tolist())
    before = eng.rows_read()
    with obs.trace.recording() as rec:
        eng.decode(np.asarray([1, 2, 0], np.int32),
                   np.asarray([True, True, False]))
        eng.prefill_chunk(2, ids_of(5).tolist())
        eng.prefill_chunk(2, ids_of(4).tolist())
    evs = {}
    for e in rec.to_chrome_trace()["traceEvents"]:
        evs.setdefault(e["name"], []).append(e["args"])
    step = evs["engine.decode"][0]
    # the rows a full layer reads, a lane: 20 and 3 cached before the append
    assert (step["lanes"], step["kv_tokens"]) == (2, 23)
    # live rows with the appended one, 21 and 4, under a window of 8, in six
    # window layers
    assert step["window_rows"] == 6 * (8 + 4)
    assert step["window_live_rows"] == 6 * (21 + 4)
    assert [c["offset"] for c in evs["engine.prefill_chunk"]] == [0, 5]
    after = eng.rows_read()
    assert set(after) == {"window_rows", "window_live_rows"}
    assert after["window_rows"] - before.get("window_rows", 0) == 72


@pytest.mark.parametrize("kwargs, mechanism", [
    ({"paged": sv.PagedCacheConfig(block_size=8)}, "paged="),
    ({"tp": sv.TPConfig(size=2)}, "tp="),
    ({"quant": sv.QuantConfig(weights=False, kv=True)}, "kv=True"),
])
def test_engine_refuses_what_moves_kv_rows_at_construction(served, kwargs,
                                                           mechanism):
    with pytest.raises(ValueError) as e:
        engine(served, **kwargs)
    assert mechanism in str(e.value) and "per-layer state" in str(e.value)
    assert "KVWindowRows: a ring of window K/V rows" in str(e.value)


@pytest.mark.parametrize("kwargs, mechanism", [
    ({"speculation": sv.SpeculationConfig(max_draft=2)}, "speculation="),
    ({"prefix_caching": sv.PrefixCacheConfig()}, "prefix_caching="),
    ({"policy": sv.SchedulingPolicy()}, "preemption"),
])
def test_scheduler_refuses_what_moves_kv_rows_at_construction(eng, kwargs,
                                                              mechanism):
    with pytest.raises(ValueError) as e:
        sv.ContinuousBatchingScheduler(eng, **kwargs)
    assert mechanism in str(e.value)
    assert "a ring of window K/V rows" in str(e.value)


@pytest.mark.parametrize("call, mechanism", [
    (lambda e: e.capture_slot(0), "capture_slot"),
    (lambda e: e.read_region(0, 0, 4), "read_region"),
    (lambda e: e.restore_prefix(1, (None, None), 4), "restore_prefix"),
    (lambda e: e.fork_slot(0, 1), "fork_slot"),
    (lambda e: e.verify_draft(0, [1, 2]), "verify_draft"),
])
def test_engine_methods_that_move_kv_rows_refuse(eng, call, mechanism):
    eng.prefill(0, ids_of(8).tolist())
    with pytest.raises(ValueError) as e:
        call(eng)
    eng.release(0)
    assert mechanism in str(e.value)
    assert "a ring of window K/V rows" in str(e.value)


def test_window_rings_mix_with_kv_rows_and_counters_alone():
    ring = kvc.KVWindowRows(2, 16, 8)
    for other, kwargs in (
            (kvc.RecurrentRows((4, 4), (3, 8)), {}),
            (kvc.LatentRows(12, 8, 4), {}), (kvc.RingRows(12, 5), {}),
            (None, {"int8": True}),
            (None, {"paged": sv.PagedCacheConfig(block_size=8)})):
        with pytest.raises(ValueError, match="rings of window K/V rows"):
            kvc.init_cache([ring, other], slots=2, max_len=8, **kwargs)
    with pytest.raises(ValueError, match="different K/V window rings"):
        kvc.init_cache([ring, kvc.KVWindowRows(2, 16, 4)], slots=2,
                       max_len=8)
    cache = kvc.init_cache([ring, None], slots=2, max_len=8,
                           dtype=jnp.bfloat16)           # no full layer
    assert cache.ring_k.shape == (1, 2, 16, 2, 16)
    assert cache.k.shape == (0, 2, 8, 2, 16) and cache.counters.shape == (0, 0)


# ---- the blocked chunk read against the full-extent one ---------------------

F32_TOL, BF16_TOL = 5e-6, 2e-2


def _close(got, want, dtype, msg=""):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all(), f"{msg}: a row past the bound was read"
    tol = F32_TOL if dtype == jnp.float32 else BF16_TOL
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol, err_msg=msg)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("offset", [0, 3, 13, 16, 37, 48, 56],
                         ids=lambda o: f"offset{o}")
def test_blocked_chunk_read_matches_the_full_extent_read(dtype, offset):
    """``_kv_chunk_read`` against ``cached_attention`` on the same rows: a
    chunk of 8 queries over a slot of 64 rows in blocks of 16, at offsets
    inside a block, across two and in the last; every row past the chunk's
    end, every other slot and every other layer is NaN."""
    heads, nkv, hd, s, max_len = 4, 2, 16, 8, 64
    assert kvc._key_block(max_len) == 16
    keys = jax.random.split(jax.random.key(offset), 3)
    q = jax.random.normal(keys[0], (heads, s, hd), jnp.float32).astype(dtype)
    k, v = (jax.random.normal(key, (2, 3, max_len, nkv, hd),
                              jnp.float32).astype(dtype) for key in keys[1:])
    live = jnp.arange(max_len) < offset + s
    mine = jnp.zeros((2, 3), bool).at[1, 2].set(True)
    hidden = ~(mine[:, :, None] & live[None, None])[..., None, None]
    bounds = (offset + jnp.arange(s, dtype=jnp.int32))[None]
    want = kvc.cached_attention(
        q[None], jnp.where(live[:, None, None], k[1, 2], 0)[None],
        jnp.where(live[:, None, None], v[1, 2], 0)[None], bounds)[0]
    got = jax.jit(kvc._kv_chunk_read, static_argnums=3)(
        q, jnp.where(hidden, jnp.nan, k), jnp.where(hidden, jnp.nan, v), 1,
        jnp.int32(2), jnp.int32(offset))
    assert got.shape == (heads, s, hd) and got.dtype == dtype
    _close(got, want, dtype, f"offset {offset}")


def test_chunk_read_is_chosen_from_the_shapes_in_hand(monkeypatch):
    """With kernels on, a dense float cache in the queries' dtype whose
    shapes the kernel takes is walked by it at ANY extent - ``chat-closed``'s
    128 MiB of scores and a 16-row bucket's 4 MiB as the long cell's 4 GiB;
    what the kernel refuses keeps the rule of the score bytes: a verify's odd
    row count the full extent within 128 MiB, a bucket of 384 rows the loop
    past it; rows that are no floats a slot owns: the full extent whatever
    the size.  With kernels off the score bytes alone decide.  Said as a
    ``read_dispatch`` and a ``kernel_dispatch`` event."""
    monkeypatch.setenv("APEX_TPU_KERNELS", "interpret")
    seen = []

    def sink(event):
        if event["event"] in ("read_dispatch", "kernel_dispatch"):
            seen.append(event)

    def cache_of(max_len, **kw):
        return jax.eval_shape(lambda: kvc.init_cache(
            [kvc.KVRows(4, 128)], slots=1, max_len=max_len,
            dtype=jnp.bfloat16, **kw))

    def q(chunk, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((chunk, 1, 32, 128), dtype)

    _logging.add_event_sink(sink)
    try:
        assert kvc._prefill_read(cache_of(2048), q(512)) == "kernel"
        assert kvc._prefill_read(cache_of(32768), q(1024)) == "kernel"
        assert {kvc._prefill_read(cache_of(n), q(b)) for n in (2048, 32768)
                for b in (16, 32, 64, 128, 256, 512)} == {"kernel"}
        del seen[4:]
        assert kvc._prefill_read(cache_of(2048), q(9)) == "full_extent"
        assert kvc._prefill_read(cache_of(32768), q(384)) == "loop"
        assert kvc._prefill_read(cache_of(2048), q(512, jnp.float32)) == (
            "full_extent")
        assert kvc._prefill_read(cache_of(32768, int8=True), q(1024)) == (
            "full_extent")
        assert kvc._prefill_read(cache_of(
            32768, paged=sv.PagedCacheConfig(block_size=128)), q(1024)) == (
            "full_extent")
        told = [(e["event"], e["op"], e["path"]) for e in seen]
        del seen[:]
        monkeypatch.setenv("APEX_TPU_KERNELS", "0")
        assert kvc._prefill_read(cache_of(2048), q(512)) == "full_extent"
        assert [kvc._prefill_read(cache_of(32768), q(b))
                for b in (16, 32, 64, 512)] == [
            "full_extent", "full_extent", "loop", "loop"]
        assert seen == []
    finally:
        _logging.remove_event_sink(sink)
    kernel = ("kernel_dispatch", "kv_chunk_attention")
    read = ("read_dispatch", "prefill_attend")
    assert told == [
        kernel + ("pallas",), read + ("blocked_walk",),
        kernel + ("pallas",), read + ("blocked_walk",),
        kernel + ("reference",), read + ("full_extent",),
        kernel + ("reference",), read + ("blocked_walk",),
        kernel + ("reference",), read + ("full_extent",),
        kernel + ("reference",), read + ("full_extent",),
        kernel + ("reference",), read + ("full_extent",)]
    assert 32 * 512 * 2048 * 4 == kvc._FULL_READ_BYTES == 1 << 27


# ---- the ring's decode read -------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_ring_decode_read_matches_a_gather_of_the_windows_rows(monkeypatch,
                                                               dtype):
    """``window_decode_attend`` on a ring of exactly the window, its read
    the in-place kernel run by the Pallas interpreter, against the window's
    rows gathered in position order and read by ``cached_attention``: lanes
    before the first wrap, at it and several wraps on; the other layer of
    the ring is NaN, and so is every row a lane has not written yet."""
    monkeypatch.setenv("APEX_TPU_KERNELS", "interpret")
    window = 32
    heads, nkv, hd = 8, 2, 128
    position = jnp.asarray([0, 5, 31, 32, 33, 100, 191], jnp.int32)
    lanes = position.shape[0]
    assert cda.block_rows(window, nkv) == window
    keys = jax.random.split(jax.random.key(0), 5)
    rows_k, rows_v = (jax.random.normal(key, (lanes, 192, nkv, hd),
                                        jnp.float32).astype(dtype)
                      for key in keys[:2])       # by position, all lanes
    q, k, v = (jax.random.normal(key, (1, lanes, n, hd),
                                 jnp.float32).astype(dtype)
               for key, n in zip(keys[2:], (heads, nkv, nkv)))
    # the ring before the step: position p < position[lane] at row p mod 32
    p = jnp.arange(192)
    at = (position[:, None] - 1) - (position[:, None] - 1 - p[None, :32]
                                    ) % window   # newest p' = r mod 32
    written = (at >= 0)[..., None, None]
    lane = jnp.arange(lanes)[:, None]
    ring_k, ring_v = (jnp.where(written, rows[lane, at.clip(0)], jnp.nan)
                      for rows in (rows_k, rows_v))
    nan = jnp.full_like(ring_k, jnp.nan)
    cache = kvc.WindowKVCache(
        k=jnp.zeros((0, lanes, 64, nkv, hd), dtype),
        v=jnp.zeros((0, lanes, 64, nkv, hd), dtype),
        lengths=position, ring_k=jnp.stack([nan, ring_k]),
        ring_v=jnp.stack([nan, ring_v]), counters=jnp.zeros((0, 0), jnp.int32))
    seen = []

    def sink(event):
        if event["event"] == "kernel_dispatch":
            seen.append((event["op"], event["path"], event.get("window")))

    _logging.add_event_sink(sink)
    try:
        got, after = jax.jit(kvc.window_decode_attend,
                             static_argnames=("layer", "window"))(
            cache, layer=1, q=q, k=k, v=v, position=position, window=window)
    finally:
        _logging.remove_event_sink(sink)
    assert seen == [("cached_decode_attention", "pallas", window)]
    # the window's rows in position order, the new row last, then padding
    order = position[:, None] - (window - 1) + jnp.arange(window)[None]
    real = order >= 0
    gather_k, gather_v = (
        jnp.where(real[..., None, None], jnp.where(
            (order == position[:, None])[..., None, None], new[0][:, None],
            rows[lane, order.clip(0)]), 0)
        for rows, new in ((rows_k, k), (rows_v, v)))
    # cached_attention reads rows idx <= bound: the real rows first
    first = jnp.argsort(~real, axis=1, stable=True)
    gather_k, gather_v = (jnp.take_along_axis(g, first[..., None, None], 1)
                          for g in (gather_k, gather_v))
    want = kvc.decode_attention(q.transpose(1, 2, 0, 3), gather_k, gather_v,
                                real.sum(1) - 1)
    assert got.shape == (lanes, heads, 1, hd)
    _close(got, want, dtype)
    # the step wrote one row a lane, at position mod ring, in its layer
    row = after.ring_k[1, jnp.arange(lanes), position % window]
    assert (np.asarray(row, np.float32) == np.asarray(k[0], np.float32)).all()
    assert np.isnan(np.asarray(after.ring_k[0], np.float32)).all()


def test_a_ring_rounded_up_past_its_window_is_read_under_a_mask(monkeypatch):
    """A window that is no whole tile: the ring holds rows that have left
    the window, the kernel's one bound cannot tell them, and the masked read
    is taken (and says so)."""
    monkeypatch.setenv("APEX_TPU_KERNELS", "interpret")
    seen = []

    def sink(event):
        if event["event"] == "kernel_dispatch":
            seen.append((event["op"], event["path"]))

    cache = jax.eval_shape(lambda: kvc.init_cache(
        [kvc.KVWindowRows(2, 128, 24)], slots=2, max_len=64,
        dtype=jnp.bfloat16))
    assert cache.ring_k.shape[2] == 32
    q = jax.ShapeDtypeStruct((1, 2, 8, 128), jnp.bfloat16)
    _logging.add_event_sink(sink)
    try:
        assert not kvc._ring_reads_in_place(cache, q, 24)
    finally:
        _logging.remove_event_sink(sink)
    assert seen == [("cached_decode_attention", "reference")]


def test_window_reads_never_see_a_row_outside_the_window():
    """The masked reads with NaN in every ring row that holds no position of
    the window: a decode step over a ring twice the window, and a chunk over
    a ring whose rows before the window are another request's."""
    window, ring, nkv, hd, heads = 8, 16, 2, 16, 4
    layer = kvc.KVWindowRows(nkv, hd, window)
    assert layer.rows == ring
    position = jnp.asarray([3, 8, 21, 40], jnp.int32)
    lanes = position.shape[0]
    keys = jax.random.split(jax.random.key(1), 5)
    rows_k, rows_v = (jax.random.normal(key, (lanes, 64, nkv, hd),
                                        jnp.float32) for key in keys[:2])
    q, k, v = (jax.random.normal(key, (1, lanes, n, hd), jnp.float32)
               for key, n in zip(keys[2:], (heads, nkv, nkv)))
    r = jnp.arange(ring)
    held = (position[:, None] - 1) - (position[:, None] - 1 - r[None]) % ring
    inside = (held >= 0) & (held > position[:, None] - window)
    lane = jnp.arange(lanes)[:, None]
    ring_k, ring_v = (
        jnp.where(inside[..., None, None], rows[lane, held.clip(0)], jnp.nan)
        for rows in (rows_k, rows_v))
    cache = kvc.WindowKVCache(
        k=jnp.zeros((0, lanes, 64, nkv, hd)), v=jnp.zeros((0, lanes, 64, nkv,
                                                           hd)),
        lengths=position, ring_k=ring_k[None], ring_v=ring_v[None],
        counters=jnp.zeros((0, 0), jnp.int32))
    got, _ = kvc.window_decode_attend(cache, 0, q, k, v, position,
                                      window=window)
    order = position[:, None] - (window - 1) + jnp.arange(window)[None]
    real = order >= 0
    gk, gv = (jnp.where(real[..., None, None], jnp.where(
        (order == position[:, None])[..., None, None], new[0][:, None],
        rows[lane, order.clip(0)]), 0)
        for rows, new in ((rows_k, k), (rows_v, v)))
    first = jnp.argsort(~real, axis=1, stable=True)
    gk, gv = (jnp.take_along_axis(g, first[..., None, None], 1)
              for g in (gk, gv))
    want = kvc.decode_attention(q.transpose(1, 2, 0, 3), gk, gv,
                                real.sum(1) - 1)
    _close(got, want, jnp.float32)
