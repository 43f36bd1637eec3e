"""A model with two kinds of per-slot state (K/V rows and a recurrent
state) through ``DecodeEngine`` and the scheduler at their defaults: toy
Nemotron-H on the CPU in float32, against the plain reference's full
forward."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu import serving as sv  # noqa: E402
from apex_tpu.models.nemotron_h import (  # noqa: E402
    NemotronHConfig,
    NemotronHForCausalLM,
)
from apex_tpu.serving import kv_cache as kvc  # noqa: E402
from benchmark.reference import nemotron_h as ref  # noqa: E402
from test_nemotron_h import TOY  # noqa: E402

HELD = (4, 8)


@pytest.fixture(scope="module")
def served():
    model = NemotronHForCausalLM(NemotronHConfig(**TOY, experts_held=HELD))
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    return model, params


def engine(served, **kw):
    model, params = served
    return sv.DecodeEngine(model, params, **{
        "slots": 4, "max_len": 64, "prefill_len": 16, **kw})


def prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).tolist()


def decode_one(eng, slot, token):
    tokens = np.zeros((eng.slots,), np.int32)
    active = np.zeros((eng.slots,), bool)
    tokens[slot], active[slot] = token, True
    return eng.decode(tokens, active)[slot]


def state_of(eng, slot):
    st = eng.cache.state
    return np.asarray(st.ssm[:, slot]), np.asarray(st.conv[:, slot])


def test_cache_is_built_from_what_the_layers_declare(served):
    eng = engine(served)
    cache = eng.cache
    assert isinstance(cache, kvc.HybridCache) and eng.recurrent_state
    # one attention layer of five has K/V rows, two Mamba layers a state,
    # two expert layers counters
    assert cache.k.shape == (1, 4, 64, 2, 16)
    assert cache.state.ssm.shape == (2, 4, 8, 8, 16)
    assert cache.state.ssm.dtype == jnp.float32
    assert cache.state.conv.shape == (2, 4, 3, 64 + 2 * 2 * 16)
    assert cache.counters.shape == (2, 5)


def test_prefill_and_decode_match_the_reference_full_forward(served):
    """Three chunks (16 + 16 + a padded 5), then five tokens through the
    cache and the state."""
    eng = engine(served)
    seq = prompt(37)
    first = logits = eng.prefill(1, seq)
    for _ in range(5):
        seq.append(int(jnp.argmax(logits)))
        logits = decode_one(eng, 1, seq[-1])
    want = ref.logits_at(served[1], np.asarray(seq), [36, len(seq) - 1], TOY,
                         held=HELD[0])
    assert float(jnp.abs(first - want[0]).max()) <= 1e-5
    assert float(jnp.abs(logits - want[1]).max()) <= 1e-5
    assert eng.decode_compiles() == 1
    assert eng.prefill_compiles() == len(eng.prefill_buckets) == 1


def test_chunked_prefill_is_one_shot_prefill(served):
    """Not bit for bit: a chunk boundary changes where the scan's sums are
    split (one masked product inside a chunk, a carried state across), so
    float32 rounds differently; 1e-5 of logits whose largest is ~0.7."""
    tokens = prompt(45, seed=1)
    chunked = engine(served, prefill_len=16).prefill(0, tokens)
    one_shot = engine(served, prefill_len=64).prefill(0, tokens)
    assert float(jnp.abs(chunked - one_shot).max()) <= 1e-5


def test_padded_bucket_leaves_the_state_of_its_real_rows(served):
    """Padding must leave no trace: the same 11 real tokens padded to 16
    with zeros and with other ids leave the same state bit for bit, and
    the state the reference's scan reaches."""
    model, params = served
    eng = engine(served)
    real = prompt(11, seed=2)
    eng.prefill(0, real)                      # the engine pads with id 0
    empty = jax.tree.map(jnp.zeros_like, eng.cache)

    @jax.jit
    def chunk(ids):
        return model.apply(params, ids, kv_cache=empty, slot=np.int32(0),
                           position=np.int32(0), length=np.int32(11))[1].state

    zeros, other = (chunk(np.asarray([real + pad], np.int32))
                    for pad in ([0] * 5, prompt(5, seed=3)))
    np.testing.assert_array_equal(np.asarray(zeros.ssm), np.asarray(other.ssm))
    np.testing.assert_array_equal(np.asarray(zeros.conv),
                                  np.asarray(other.conv))
    # against the reference: layer 0's mixer reads norm(embedding)
    p = params["params"]
    x = p["embed_tokens"]["embedding"][jnp.asarray(real)]
    h = ref.normed(x, p["layers_0"], TOY)
    _, (ssm, tail) = ref.mamba2(h, p["layers_0"]["mixer"], TOY)
    np.testing.assert_allclose(state_of(eng, 0)[0][0], ssm, atol=1e-5)
    np.testing.assert_allclose(state_of(eng, 0)[1][0], tail, atol=1e-6)


def test_inactive_lane_keeps_its_state_bit_for_bit(served):
    eng = engine(served)
    eng.prefill(0, prompt(9, seed=4))
    eng.prefill(2, prompt(20, seed=5))
    before_idle, before_live = state_of(eng, 2), state_of(eng, 0)
    decode_one(eng, 0, 7)
    for was, now in zip(before_idle, state_of(eng, 2)):
        np.testing.assert_array_equal(was, now)
    assert not np.array_equal(before_live[0], state_of(eng, 0)[0])
    assert eng.lengths().tolist() == [10, 0, 20, 0]


def test_reused_slot_starts_from_a_zero_state(served):
    eng = engine(served)
    logits = eng.prefill(0, prompt(30, seed=6))
    for _ in range(3):
        logits = decode_one(eng, 0, int(jnp.argmax(logits)))
    eng.release(0)
    assert np.abs(state_of(eng, 0)[0]).max() > 0    # release clears nothing
    again = eng.prefill(0, prompt(12, seed=7))
    fresh = eng.prefill(3, prompt(12, seed=7))       # a slot never used
    np.testing.assert_array_equal(np.asarray(again), np.asarray(fresh))
    eng.reset()
    assert np.abs(state_of(eng, 0)[0]).max() == 0
    assert not eng.moe_stats()["steps"].any()


def test_scheduler_at_its_defaults_serves_the_reference_tokens(served):
    """More requests than slots, prompts of one to three chunks: every
    emitted token is the reference's greedy choice (to within float32
    rounding of its largest logit), one decode program, prefill programs
    within the buckets."""
    eng = engine(served, prefill_buckets=(8, 16))
    sched = sv.ContinuousBatchingScheduler(eng)
    prompts = {f"r{i}": prompt(n, seed=10 + i)
               for i, n in enumerate((5, 23, 40, 16, 9, 31))}
    for rid, toks in prompts.items():
        sched.submit(sv.Request(rid, toks, 6))
    results = sched.run()
    assert set(results) == set(prompts)
    for rid, res in results.items():
        assert res.finish_reason in sv.SERVED_REASONS and len(res.tokens) == 6
        seq = prompts[rid] + list(res.tokens)
        at = list(range(len(prompts[rid]) - 1, len(seq) - 1))
        want = np.asarray(ref.logits_at(served[1], np.asarray(seq), at, TOY,
                                        held=HELD[0]))
        chosen = want[np.arange(6), res.tokens]
        assert (chosen >= want.max(-1) - 1e-5).all(), rid
    assert eng.decode_compiles() == 1
    assert eng.prefill_compiles() <= len(eng.prefill_buckets)
    stats = eng.moe_stats()
    assert (stats["steps"] > 0).all() and len(stats["steps"]) == 2
    # 8 of 16 experts held: about half of a token's 3 choices land here
    share = stats["pairs"].sum() / (3 * stats["tokens"].sum())
    assert 0.3 < share < 0.7
    assert (stats["touched"] <= stats["pairs"]).all()
    sched.close()


def test_llama_has_no_recurrent_state_and_no_moe_stats():
    from apex_tpu.models import LlamaConfig, LlamaForCausalLM

    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=1,
        max_position_embeddings=32))
    params = model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))
    eng = sv.DecodeEngine(model, params, slots=2, max_len=16, prefill_len=8)
    assert not eng.recurrent_state and eng.moe_stats() == {}
    assert type(eng.cache) is kvc.KVCache


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


@pytest.mark.parametrize("kwargs, mechanism", [
    ({"speculation": sv.SpeculationConfig(max_draft=2)}, "speculation="),
    ({"prefix_caching": sv.PrefixCacheConfig()}, "prefix_caching="),
    ({"policy": sv.SchedulingPolicy()}, "preemption"),
])
def test_scheduler_refuses_what_moves_kv_rows_at_construction(served, kwargs,
                                                              mechanism):
    with pytest.raises(ValueError) as e:
        sv.ContinuousBatchingScheduler(engine(served), **kwargs)
    assert mechanism in str(e.value) and "recurrent state" in str(e.value)


@pytest.mark.parametrize("call, mechanism", [
    (lambda e: e.capture_slot(0), "capture_slot"),
    (lambda e: e.read_region(0, 0, 4), "read_region"),
    (lambda e: e.restore_prefix(1, (None, None), 4), "restore_prefix"),
    (lambda e: e.fork_slot(0, 1), "fork_slot"),
    (lambda e: e.verify_draft(0, [1, 2]), "verify_draft"),
])
def test_engine_methods_that_move_kv_rows_refuse(served, call, mechanism):
    eng = engine(served)
    eng.prefill(0, prompt(8))
    with pytest.raises(ValueError) as e:
        call(eng)
    assert mechanism in str(e.value) and "recurrent state" in str(e.value)


def test_policy_without_preemption_is_served(served):
    sched = sv.ContinuousBatchingScheduler(
        engine(served), policy=sv.SchedulingPolicy(preemption=False))
    sched.submit(sv.Request("a", prompt(6), 2))
    assert len(sched.run()["a"].tokens) == 2
    sched.close()
