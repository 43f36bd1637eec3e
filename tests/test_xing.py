"""The Xing4.0 decoder (multi-head latent attention over every latent row,
a residual stream of four streams mixed by manifold-constrained
hyper-connections around each sublayer, sigmoid top-k experts with a shared
expert) against the plain float32 reference, at toy widths on the CPU: the
uncached forward; chunked prefill and then decode through the engine's cache
against the reference's full forward; each part of the mathematics left out
of the system fails that comparison; the rope at the published widths
against the reference's own YaRN ramp; the read of a layer without a selector
against a full masked read at every lane length."""

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
from apex_tpu.models import dots3, mellum, xing  # noqa: E402
from apex_tpu.serving import kv_cache as kvc  # noqa: E402
from benchmark.reference import xing as ref  # noqa: E402
from benchmark.runners import serve_mhc  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs", "tiny-xing.json")) as f:
    TINY = json.load(f)
# the residual mappings' group moved twenty times as far by its input, so
# that some of the clamped logits pass +-30 and the clamp is part of the
# result
CONFIG = dict(TINY, assumed=dict(TINY["assumed"], hc_init=dict(
    TINY["assumed"]["hc_init"], alpha_times_phi_rms=[1.0, 1.0, 20.0])))
CHUNK, PROMPT, STEPS, MAX_LEN = 16, 48, 8, 64
# float32 against float32: the two programs sum in other orders
TOLERANCE = 1e-5


def rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.fixture(scope="module")
def toy():
    model = serve_mhc.build_model(CONFIG)
    params = serve_mhc.draw_params(model, CONFIG, 5)
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
    assert rel_err(got, at_served(want)) < TOLERANCE
    for g, w in zip(got, at_served(want)):
        assert rel_err(g, w) < TOLERANCE
    # every live row of the active lane, the appended one among them, in
    # each of the three layers
    assert eng.rows_read() == {"latent_rows": 3 * sum(
        range(PROMPT + 1, PROMPT + STEPS + 1))}
    assert eng.moe_stats()["steps"].tolist() == [STEPS, STEPS]


def test_the_mappings_move_with_the_input(toy):
    """At the seeded draw no mapping is constant: a wrapper that did nothing
    would be caught."""
    _, params, ids, _ = toy
    x = ref.attention_sublayer(ref.streams(ref.embed(params, ids), CONFIG),
                               params["params"]["layers_0"], CONFIG)
    pre, post, res = ref.maps(x, params["params"]["layers_0"]["mlp_hc"],
                              CONFIG)
    for h in (pre, post, res):
        assert float(jnp.std(h, axis=0).min()) > 1e-3
    # columns are normalised last
    assert np.allclose(res.sum(-2), 1, atol=1e-5)
    # at the configuration's own gains (logits of unit spread) 20
    # iterations leave the rows' sums at 1 too: doubly stochastic
    model = serve_mhc.build_model(TINY)
    params = serve_mhc.draw_params(model, TINY, 5)
    x = ref.attention_sublayer(ref.streams(ref.embed(params, ids), TINY),
                               params["params"]["layers_0"], TINY)
    _, _, res = ref.maps(x, params["params"]["layers_0"]["mlp_hc"], TINY)
    assert float(jnp.std(res, axis=0).min()) > 1e-3
    assert np.allclose(res.sum(-1), 1, atol=1e-3)
    assert np.allclose(res.sum(-2), 1, atol=1e-5)


def _replace(model, **changes):
    return type(model)(dataclasses.replace(model.config, **changes),
                       params_dtype=model.params_dtype)


def _once_per_layer(monkeypatch):
    """The MLP's sublayer handed the attention's mappings: one mix a
    layer."""
    real, kept = xing.mappings, []

    def mappings(x, *args):
        if len(kept) % 2 == 0:
            kept.append(real(x, *args))
            return kept[-1]
        kept.append(None)
        return kept[-2]

    monkeypatch.setattr(xing, "mappings", mappings)


def _one_row_fewer(monkeypatch):
    real = kvc._latent_full_read
    monkeypatch.setattr(kvc, "_latent_full_read",
                        lambda q, latent, layer, bound, **kw: real(
                            q, latent, layer, bound - 1, **kw))


OMISSIONS = {
    "one_sinkhorn_iteration_fewer": lambda m, mp: _replace(
        m, hc_sinkhorn_iters=m.config.hc_sinkhorn_iters - 1),
    "no_clamp": lambda m, mp: _replace(
        m, mhc_h_res_clamp_min=-np.inf, mhc_h_res_clamp_max=np.inf),
    "h_post_without_its_factor_2": lambda m, mp: (
        mp.setattr(xing, "POST_GAIN", 1.0), m)[1],
    "plain_sum_read_out": lambda m, mp: (mp.setattr(
        xing, "read_out", lambda x, *a: x.sum(1)), m)[1],
    "the_mix_once_per_layer": lambda m, mp: (_once_per_layer(mp), m)[1],
    "no_yarn_mscale_in_the_scale": lambda m, mp: (mp.setattr(
        xing, "softmax_scale", lambda cfg: (
            cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5), m)[1],
    "routed_scaling_factor_1": lambda m, mp: _replace(
        m, routed_scaling_factor=1.0),
    "shared_expert_dropped": lambda m, mp: _replace(m, n_shared_experts=0),
    "one_row_fewer_in_the_latent_read": lambda m, mp: (
        _one_row_fewer(mp), m)[1],
}


@pytest.mark.parametrize("omission", sorted(OMISSIONS))
def test_each_omission_fails_the_comparison(toy, omission, monkeypatch):
    """A system that left one part of the mathematics out is caught by the
    comparison above, by far more than its tolerance."""
    model, params, ids, want = toy
    model = OMISSIONS[omission](model, monkeypatch)
    got, _ = served(model, params, ids)
    assert rel_err(got, at_served(want)) > 10 * TOLERANCE, omission


def test_unselected_decode_read_is_a_full_masked_read():
    """``latent_decode_attend`` with no selector against ``_attend`` over
    every row under ``idx <= position``, lanes at lengths 0, 1, in the
    middle of a block, at a block's edge and at ``max_len - 1``."""
    lanes, heads, rank, width, max_len = 6, 3, 16, 24, 64
    cache = kvc.init_cache([kvc.FullLatentRows(width)] * 2, slots=lanes,
                           max_len=max_len, dtype=jnp.float32)
    assert cache.latent.shape == (2, lanes, max_len, 128)
    keys = jax.random.split(jax.random.key(3), 4)
    old = jax.random.normal(keys[0], cache.latent.shape)
    # rows past a lane's length are garbage: a nan among them must not leak
    old = old.at[:, :, -1, 0].set(jnp.nan)
    cache = dataclasses.replace(cache, latent=old)
    position = jnp.asarray([0, 1, 13, 31, 40, max_len - 1], jnp.int32)
    q = jax.random.normal(keys[1], (lanes, heads, width))
    row = jax.random.normal(keys[2], (lanes, width))
    ctx, cache = kvc.latent_decode_attend(cache, 1, q, row, position,
                                          scale=0.3, rank=rank, select=None)
    rows = cache.latent[1][:, :, None, :width]
    assert (np.asarray(rows[jnp.arange(lanes), position, 0])
            == np.asarray(row)).all()
    rows = jnp.where(jnp.isnan(rows), 0, rows)
    seen = jnp.arange(max_len)[None] <= position[:, None]
    want = kvc._attend(q[:, None], rows, rows[..., :rank], seen[:, None],
                       0.3)[:, 0]
    assert ctx.shape == (lanes, heads, rank)
    assert rel_err(ctx, want) < 1e-6
    assert cache.index.size == 0


@pytest.mark.parametrize("ramp", ["yarn", "left_out"])
def test_rope_matches_the_reference_at_the_published_widths(ramp,
                                                            monkeypatch):
    """The model's rope (YaRN at factor 64 over 64 channels) at every
    position of the cell's ``max_len`` against the reference's rope, which
    writes the ramp out itself; the model's frequencies without the ramp
    are caught (at toy widths the scores are too flat to show them)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "xing4-29b-l6.json")) as f:
        real = json.load(f)
    cfg = serve_mhc.build_model(real).config
    if ramp == "left_out":
        monkeypatch.setattr(mellum, "yarn_inv_freq", lambda dim, theta, **_:
                            1.0 / theta ** (jnp.arange(0, dim, 2,
                                                       dtype=jnp.float32)
                                            / dim))
    width = cfg.qk_rope_head_dim
    x = jax.random.normal(jax.random.key(0), (24576, 1, 1, width))
    err = rel_err(dots3._rope(x, cfg.rope(), None, width)[:, 0],
                  ref._rope(x[:, 0], real))
    # 5e-5 where right: the two write a frequency in forms an ulp apart,
    # and an angle of 24,576 radians keeps that ulp
    assert err < 1e-3 if ramp == "yarn" else err > 1e-2
