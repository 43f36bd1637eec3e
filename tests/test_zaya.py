"""The ZAYA1 decoder (compressed convolutional attention over grouped K/V
heads with a convolution tail a slot, top-1 experts chosen by a router MLP
with a depth carry, tied head) against the plain float32 reference, at toy
widths on the CPU: the uncached forward; a prompt chunked across the tail and
then decode through the engine's cache against the reference's full forward;
each part of the mathematics left out of the system fails that comparison;
an idle lane keeps its tail and rows bit for bit, a released slot's next
prompt reads a zero tail; the cache declaration and its refusals; the partial
rope at the published widths."""

import dataclasses
import json
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
from apex_tpu.models import zaya  # noqa: E402
from apex_tpu.serving import kv_cache as kvc  # noqa: E402
from apex_tpu.transformer.moe import GatedMoE  # noqa: E402
from benchmark.reference import zaya as ref  # noqa: E402
from benchmark.runners import serve_cca  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs", "tiny-zaya.json")) as f:
    CONFIG = json.load(f)
# three chunks of 16 (the tail crosses two chunk ends), then eight steps
CHUNK, PROMPT, STEPS, MAX_LEN = 16, 48, 8, 64
# float32 against float32: the two programs sum in other orders
TOLERANCE = 1e-5


def rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.fixture(scope="module")
def toy():
    model = serve_cca.build_model(CONFIG)
    params = serve_cca.draw_params(model, CONFIG, 5)
    ids = np.random.default_rng(0).integers(
        0, CONFIG["vocab_size"], PROMPT + STEPS).astype(np.int32)
    want = ref.logits_at(params, ids, list(range(PROMPT + STEPS)), CONFIG)
    return model, params, ids, np.asarray(want)


def served(model, params, ids):
    """The logits of the last row of each chunk of the first ``PROMPT``
    ids, then of ``STEPS`` decode steps fed the rest, through
    ``DecodeEngine``'s programs (slot 1 of 2: the idle lane decodes too)."""
    eng = sv.DecodeEngine(model, params, slots=2, max_len=MAX_LEN,
                          prefill_len=CHUNK)
    got = [eng.prefill_chunk(1, ids[s:s + CHUNK].tolist())
           for s in range(0, PROMPT, CHUNK)]
    active = np.array([False, True])
    for t in range(STEPS):
        tokens = np.zeros((2,), np.int32)
        tokens[1] = ids[PROMPT + t]
        got.append(eng.decode(tokens, active)[1])
    return np.stack([np.asarray(g) for g in got]), eng


def at_served(want):
    """The reference's rows at the positions :func:`served` hands back."""
    return want[[s + CHUNK - 1 for s in range(0, PROMPT, CHUNK)]
                + list(range(PROMPT, PROMPT + STEPS))]


def test_uncached_forward_matches_the_reference(toy):
    model, params, ids, want = toy
    got = model.apply(params, ids[None])[:, 0]
    assert got.shape == want.shape and rel_err(got, want) < TOLERANCE


def test_chunked_prefill_then_decode_matches_the_reference(toy):
    model, params, ids, want = toy
    got, eng = served(model, params, ids)
    for g, w in zip(got, at_served(want)):
        assert rel_err(g, w) < TOLERANCE
    # one expert a token, every step, in each of the three layers
    stats = eng.moe_stats()
    assert stats["steps"].tolist() == [STEPS] * 3
    assert stats["pairs"].tolist() == [STEPS] * 3


def test_a_prompt_chunked_otherwise_reads_the_same(toy):
    """Chunks of 16, 8 + 24 + 16 and one of 48 leave the same tail: the
    logits of the last prompt row and of the next step agree."""
    model, params, ids, want = toy
    for cuts in ((8, 32, 48), (48,)):
        eng = sv.DecodeEngine(model, params, slots=2, max_len=MAX_LEN,
                              prefill_len=PROMPT)
        start = 0
        for stop in cuts:
            last = eng.prefill_chunk(0, ids[start:stop].tolist())
            start = stop
        assert rel_err(last, want[PROMPT - 1]) < TOLERANCE
        tokens = np.zeros((2,), np.int32)
        tokens[0] = ids[PROMPT]
        step = eng.decode(tokens, np.array([True, False]))[0]
        assert rel_err(step, want[PROMPT]) < TOLERANCE


def test_an_idle_lane_keeps_its_tail_and_rows_bit_for_bit(toy):
    model, params, ids, _ = toy
    eng = sv.DecodeEngine(model, params, slots=2, max_len=MAX_LEN,
                          prefill_len=CHUNK)
    eng.prefill(0, ids[:20].tolist())
    eng.prefill(1, ids[20:40].tolist())
    before = jax.tree.map(np.asarray, eng._cache)
    tokens = np.zeros((2,), np.int32)
    tokens[1] = ids[40]
    eng.decode(tokens, np.array([False, True]))
    after = jax.tree.map(np.asarray, eng._cache)
    assert (after.state.conv[:, 0] == before.state.conv[:, 0]).all()
    assert (after.k[:, 0] == before.k[:, 0]).all()
    assert (after.v[:, 0] == before.v[:, 0]).all()
    # the active lane's tail moved in every layer
    assert all((after.state.conv[i, 1] != before.state.conv[i, 1]).any()
               for i in range(CONFIG["num_hidden_layers"]))


def test_a_released_slots_next_prompt_reads_a_zero_tail(toy):
    """A slot that served a prompt and is released serves the next prompt as
    a fresh engine would: its tail is not cleared, a chunk at offset 0 reads
    zeros."""
    model, params, ids, _ = toy
    eng = sv.DecodeEngine(model, params, slots=2, max_len=MAX_LEN,
                          prefill_len=CHUNK)
    eng.prefill(1, ids[:30].tolist())
    assert np.abs(np.asarray(eng._cache.state.conv[:, 1])).max() > 0
    eng.release(1)
    reused = eng.prefill(1, ids[30:50].tolist())
    fresh = sv.DecodeEngine(model, params, slots=2, max_len=MAX_LEN,
                            prefill_len=CHUNK).prefill(1, ids[30:50].tolist())
    assert (np.asarray(reused) == np.asarray(fresh)).all()


def _replace(model, **changes):
    return type(model)(dataclasses.replace(model.config, **changes),
                       params_dtype=model.params_dtype)


def _patched(name, fn):
    def omit(model, monkeypatch):
        monkeypatch.setattr(zaya, name, fn)
        return model
    return omit


OMISSIONS = {
    "no_sequence_convolution": _patched(
        "sequence_conv",
        lambda u_ext, kernel, bias, rows: u_ext[-rows:].astype(jnp.float32)),
    "no_channel_convolution": _patched(
        "channel_conv",
        lambda c_ext, kernel, bias, rows: c_ext[-rows:].astype(jnp.float32)),
    "no_qk_mean": _patched(
        "qk_mean", lambda q, k: jnp.zeros(k.shape, jnp.float32)),
    "no_value_shift": _patched(
        "shifted_values", lambda prev_row, rows: rows),
    "no_l2_norms": _patched("unit", lambda t: t.astype(jnp.float32)),
    "every_channel_rotated": lambda m, mp: _replace(
        m, partial_rotary_factor=1.0),
    "no_depth_carry": _patched(
        "depth_carry", lambda r, gamma, carry: r),
    "one_router_layer_fewer": _patched(
        "router_logits", lambda rbar, mats: jnp.dot(
            jax.nn.gelu(jnp.dot(rbar, mats[0]), approximate=False),
            mats[-1])),
}


@pytest.mark.parametrize("omission", sorted(OMISSIONS))
def test_each_omission_fails_the_comparison(toy, omission, monkeypatch):
    """A system that left one part of the mathematics out is caught by the
    comparison above, by far more than its tolerance."""
    model, params, ids, want = toy
    model = OMISSIONS[omission](model, monkeypatch)
    got, _ = served(model, params, ids)
    assert rel_err(got, at_served(want)) > 10 * TOLERANCE, omission


def test_the_temperature_is_part_of_the_scores(toy):
    """Temperatures that differ by head (as trained ones do) read through:
    a system that scored with ``d^-1/2`` alone would differ."""
    model, params, ids, _ = toy
    p = jax.tree_util.tree_map_with_path(
        lambda path, a: a * jnp.linspace(0.5, 3.0, a.shape[0])
        if "temperature" in jax.tree_util.keystr(path) else a, params)
    want = ref.logits_at(p, ids, list(range(PROMPT + STEPS)), CONFIG)
    got, _ = served(model, p, ids)
    assert rel_err(got, at_served(np.asarray(want))) < TOLERANCE
    plain, _ = served(model, params, ids)
    assert rel_err(plain, at_served(np.asarray(want))) > 10 * TOLERANCE


def test_the_cache_keeps_rows_tails_and_counters_a_layer(toy):
    model = toy[0]
    layers = model.cache_layers()
    assert layers[:3] == [kvc.KVRows(2, 16),
                          kvc.RecurrentRows(ssm=(0,), conv=(208,)),
                          kvc.CallCounters(zaya.MOE_COUNTERS)]
    cache = kvc.init_cache(layers, slots=3, max_len=32, dtype=jnp.bfloat16)
    assert isinstance(cache, kvc.HybridCache)
    assert cache.k.shape == (3, 3, 32, 2, 16)
    assert cache.state.conv.shape == (3, 3, 208)
    assert cache.state.conv.dtype == jnp.bfloat16
    assert cache.counters.shape == (3, len(zaya.MOE_COUNTERS))
    # at the published widths: u and c of the token before, and the
    # shifted value head's row
    assert zaya.ZayaConfig().tail_width == 2688


def test_the_tail_state_holds_no_ssm_values_through_the_engine(toy):
    """The tail is a recurrent state whose ssm part is empty: a chunk and a
    decode step keep it empty, and a slot's tail is all it carries."""
    model, params, ids, _ = toy
    eng = sv.DecodeEngine(model, params, slots=2, max_len=MAX_LEN,
                          prefill_len=CHUNK)
    assert eng.recurrent_state
    eng.prefill(0, ids[:20].tolist())
    tokens = np.zeros((2,), np.int32)
    tokens[0] = ids[20]
    eng.decode(tokens, np.array([True, False]))
    state = eng._cache.state
    assert state.ssm.shape == (CONFIG["num_hidden_layers"], 2, 0)
    assert state.conv.shape == (CONFIG["num_hidden_layers"], 2, 208)
    assert np.abs(np.asarray(state.conv[:, 0])).max() > 0


def test_what_moves_kv_rows_refuses_the_tail(toy):
    model, params = toy[:2]
    with pytest.raises(ValueError, match="RecurrentRows: a recurrent state"):
        sv.DecodeEngine(model, params, slots=2, max_len=MAX_LEN,
                        prefill_len=CHUNK,
                        paged=sv.PagedCacheConfig(block_size=16))
    eng = sv.DecodeEngine(model, params, slots=2, max_len=MAX_LEN,
                          prefill_len=CHUNK)
    assert "RecurrentRows: a recurrent state" in eng.other_state
    for kwargs in ({"prefix_caching": sv.PrefixCacheConfig()},):
        with pytest.raises(ValueError, match="recurrent state"):
            sv.ContinuousBatchingScheduler(eng, **kwargs)


def test_gated_moe_takes_a_route_only_where_it_is_given():
    x = jax.random.normal(jax.random.key(0), (6, 8))
    chosen = jnp.asarray([[0], [2], [1], [2], [0], [1]], jnp.int32)
    weights = jnp.linspace(0.2, 0.7, 6)[:, None]
    given = GatedMoE(num_experts=3, experts_held=(0, 3), top_k=1,
                     hidden_size=8, expert_width=4, shared_width=0,
                     scoring="given")
    params = given.init(jax.random.key(1), x, route=(chosen, weights))
    assert "router_kernel" not in params["params"]
    out, counts = given.apply(params, x, route=(chosen, weights))
    p = params["params"]
    want = []
    for t in range(6):
        e = int(chosen[t, 0])
        hid = (jax.nn.silu(x[t] @ p["experts_gate"][e])
               * (x[t] @ p["experts_up"][e]))
        want.append(weights[t, 0] * (hid @ p["experts_down"][e]))
    assert rel_err(out, jnp.stack(want)) < 1e-5
    assert counts.tolist() == [1, 6, 6, 3, 2]
    with pytest.raises(ValueError, match="route="):
        given.apply(params, x)
    softmax = GatedMoE(num_experts=3, experts_held=(0, 3), top_k=1,
                       hidden_size=8, expert_width=4, shared_width=0,
                       scoring="softmax")
    sp = softmax.init(jax.random.key(1), x)
    assert "router_kernel" in sp["params"]
    with pytest.raises(ValueError, match="route="):
        softmax.apply(sp, x, route=(chosen, weights))


@pytest.mark.parametrize("rotated", ["first_64", "all_128"])
def test_partial_rope_matches_the_reference_at_the_published_widths(
        rotated):
    """The model's rope over the first 64 of 128 channels (theta 5e6) at
    every position of the cell's ``max_len`` against the reference's; all
    128 channels rotated is caught."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "zaya1-8b-l10.json")) as f:
        real = json.load(f)
    cfg = serve_cca.build_model(real).config
    if rotated == "all_128":
        cfg = dataclasses.replace(cfg, partial_rotary_factor=1.0)
    x = jax.random.normal(jax.random.key(0), (8192, 1, 2, 128))
    err = rel_err(zaya._rope(x, cfg, None)[:, 0], ref._rope(x[:, 0], real))
    assert err < 1e-4 if rotated == "first_64" else err > 1e-2
