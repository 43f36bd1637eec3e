"""A model whose slots keep latent rows with selector keys and rings of
window rows through ``DecodeEngine`` and the scheduler at their defaults:
toy dots3-note on the CPU in float32, against the plain reference's full
forward - past the selector's ``index_topk``, past several wraps of the
ring, under every split into chunks."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu import obs  # noqa: E402
from apex_tpu import serving as sv  # noqa: E402
from apex_tpu.serving import kv_cache as kvc  # noqa: E402
from benchmark.reference import dots3 as ref  # noqa: E402
from test_dots3 import HELD, TOY, TOY_REF, ids_of, make, rel_err  # noqa: E402

SLOTS, MAX_LEN, CHUNK = 3, 64, 16


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


def decode_one(eng, slot, token):
    tokens = np.zeros((eng.slots,), np.int32)
    active = np.zeros((eng.slots,), bool)
    tokens[slot], active[slot] = token, True
    return eng.decode(tokens, active)[slot]


def reference(served, ids, positions, **changed):
    return ref.logits_at(served[1], np.asarray(ids, np.int32), positions,
                         dict(TOY_REF, **changed), held=HELD[0])


def test_cache_keeps_latent_rows_selector_keys_and_window_rings(eng):
    cache = eng.cache
    assert isinstance(cache, kvc.LatentCache)
    # two full layers: a 12-wide latent row (8 + 4 rope) in one lane tile,
    # and an 8-wide selector key; three window layers: a ring of the
    # window (5) in whole 16-row tiles, whatever max_len and the chunk are
    assert cache.latent.shape == (2, SLOTS, MAX_LEN, 128)
    assert cache.index.shape == (2, SLOTS, MAX_LEN, 8)
    assert cache.ring.shape == (3, SLOTS, 16, 20)
    assert cache.ring.shape[2] <= TOY["sliding_window_size"] + CHUNK
    assert cache.counters.shape == (4, 5)
    assert cache.lengths.shape == (SLOTS,)
    assert eng.other_state == [
        "CallCounters: call counters",
        "LatentRows: latent rows with selector keys",
        "RingRows: a ring of window rows"]
    assert not eng.recurrent_state


def test_ring_is_the_window_whatever_the_cache_holds():
    from apex_tpu.models.dots3 import Dots3NoteConfig, Dots3NoteForCausalLM

    model = Dots3NoteForCausalLM(Dots3NoteConfig())      # published widths
    layers = model.cache_layers()
    rings = [l for l in layers if isinstance(l, kvc.RingRows)]
    rows = [l for l in layers if isinstance(l, kvc.LatentRows)]
    assert rings == [kvc.RingRows(1088, 513)] * 3
    assert rows == [kvc.LatentRows(576, 128, 2048)] * 2
    assert rings[0].rows == 528 <= 513 + 1024
    assert rows[0].stored_width == 640
    assert sum(isinstance(l, kvc.CallCounters) for l in layers) == 4


def test_chunked_prefill_then_decode_match_the_reference(served, eng):
    """Three chunks (16 + 16 + a padded 5) and eleven tokens through the
    cache: 48 positions, 6 x ``index_topk``, three wraps of the ring."""
    ids = ids_of(48, seed=1)
    want = reference(served, ids, list(range(36, 48)))
    got = [eng.prefill(1, ids[:37].tolist())]
    for t in range(37, 48):
        got.append(decode_one(eng, 1, int(ids[t])))
    eng.release(1)
    assert rel_err(np.stack(got), want) < 1e-5
    for g, w in zip(got, want):
        assert rel_err(g, w) < 1e-5


@pytest.mark.parametrize("left_out", [
    {"index_topk": 10 ** 6}, {"index_topk": TOY["index_topk"] - 1},
    {"attention_gate_type": None, "swa_attention_gate_type": None},
    {"apply_mla_qkv_lora_rescale": False},
    {"sliding_window_size": TOY["sliding_window_size"] + 1},
    {"swa_rope_theta": TOY["rope_theta"]},
], ids=lambda d: "+".join(d))
def test_cached_path_fails_a_reference_without_one_mechanism(served, eng,
                                                             left_out):
    ids = ids_of(44, seed=2)
    first = eng.prefill(0, ids[:40].tolist())
    last = first
    for t in range(40, 44):
        last = decode_one(eng, 0, int(ids[t]))
    eng.release(0)
    good = reference(served, ids, [39, 43])
    bad = reference(served, ids, [39, 43], **left_out)
    assert rel_err(np.stack([first, last]), good) < 1e-5
    assert rel_err(np.stack([first, last]), bad) > 10 * 1e-5, left_out


@pytest.mark.parametrize("chunk", [8, 48])
def test_splitting_a_prompt_into_chunks_changes_no_logit(served, eng,
                                                         same_logits, chunk):
    """One 48-row chunk against six of 8 against the shared engine's three
    of 16: the same selection, the same window, the same logits."""
    ids = ids_of(45, seed=3).tolist()
    want = eng.prefill(2, ids)
    want_next = decode_one(eng, 2, 7)
    eng.release(2)
    other = engine(served, prefill_len=chunk)
    got = other.prefill(0, ids)
    same_logits(got, want, f"chunks of {chunk}")
    same_logits(decode_one(other, 0, 7), want_next, "the step after")


def test_absorbed_decode_read_matches_the_explicit_chunk_read(served, eng,
                                                              same_logits):
    """The last token of a prompt read by the chunk program (per-head K and
    V expanded from the rows) and by the decode program (the query absorbed
    through ``W_kvb``, scored against the rows as stored)."""
    ids = ids_of(41, seed=4).tolist()
    explicit = eng.prefill(0, ids)
    eng.release(0)
    eng.prefill(0, ids[:-1])
    absorbed = decode_one(eng, 0, ids[-1])
    eng.release(0)
    same_logits(absorbed, explicit)


def test_lanes_do_not_read_each_others_rows(served, eng, same_logits):
    """Two slots at different depths decode in one step; each gets what it
    gets alone, and a slot's next request reads nothing of its last one."""
    a, b = ids_of(30, seed=5).tolist(), ids_of(44, seed=6).tolist()
    eng.prefill(0, a)
    alone_a = decode_one(eng, 0, 9)
    eng.release(0)
    eng.prefill(2, b)
    alone_b = decode_one(eng, 2, 11)
    eng.release(2)
    eng.prefill(0, b[:20])            # stale rows under the next request
    eng.release(0)
    eng.prefill(0, a)
    eng.prefill(2, b)
    tokens = np.asarray([9, 0, 11], np.int32)
    both = eng.decode(tokens, np.asarray([True, False, True]))
    eng.release(0)
    eng.release(2)
    same_logits(both[0], alone_a)
    same_logits(both[2], alone_b)


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
    assert stats["steps"].shape == (4,) and (stats["tokens"] > 0).all()
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
    # live rows with the appended one: 21 and 4; top-8, window 5
    assert (step["lanes"], step["kv_tokens"]) == (2, 23)
    assert step["index_rows"] == 2 * (21 + 4)
    assert step["attended_rows"] == 2 * (8 + 4)
    assert step["window_rows"] == 3 * (5 + 4)
    assert [c["offset"] for c in evs["engine.prefill_chunk"]] == [0, 5]
    after = eng.rows_read()
    assert {k: after[k] - before.get(k, 0) for k in after} == {
        k: step[k] for k in ("index_rows", "attended_rows", "window_rows")}


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
    assert "LatentRows" in str(e.value) and "RingRows" in str(e.value)


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
    assert "latent rows with selector keys" in str(e.value)


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
    assert "a ring of window rows" in str(e.value)


def test_latent_rows_mix_with_no_other_rows():
    with pytest.raises(ValueError, match="no K/V rows"):
        kvc.init_cache([kvc.LatentRows(12, 8, 4), kvc.KVRows(2, 16)],
                       slots=2, max_len=8)
    with pytest.raises(ValueError, match="dense floats"):
        kvc.init_cache([kvc.RingRows(12, 5)], slots=2, max_len=8, int8=True)
    cache = kvc.init_cache([kvc.RingRows(12, 5), None], slots=2, max_len=8,
                           dtype=jnp.bfloat16)
    assert cache.ring.shape == (1, 2, 16, 12) and cache.latent.shape[0] == 0
