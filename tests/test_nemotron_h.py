"""Nemotron-H at toy size on the CPU, float32: every layer kind and the
whole forward against the plain reference (``benchmark/reference/
nemotron_h.py``), the chunked scan against the one-token update, and the
expert layer's shares against the uncut layer."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu.models.nemotron_h import (  # noqa: E402
    NemotronHConfig,
    NemotronHForCausalLM,
    ssd_chunked,
    ssd_step,
)
from apex_tpu.transformer.moe import LatentMoE  # noqa: E402
from benchmark.reference import nemotron_h as ref  # noqa: E402

TOY = dict(vocab_size=256, hidden_size=64, hybrid_override_pattern="MEM*E",
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
           ssm_state_size=16, conv_kernel=4, chunk_size=8,
           n_routed_experts=16, num_experts_per_tok=3, moe_latent_size=32,
           moe_intermediate_size=48, moe_shared_expert_intermediate_size=96,
           routed_scaling_factor=2.5, layer_norm_epsilon=1e-5)


def toy(pattern="MEM*E", held=(4, 8)):
    cfg = dict(TOY, hybrid_override_pattern=pattern)
    model = NemotronHForCausalLM(NemotronHConfig(**cfg, experts_held=held))
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    return cfg, model, params


@pytest.mark.parametrize("pattern", ["M", "*", "E", "MEM*E"])
def test_forward_matches_the_reference(pattern):
    cfg, model, params = toy(pattern)
    ids = jax.random.randint(jax.random.key(1), (1, 21), 0, 256)
    got = model.apply(params, ids)[:, 0]
    want = ref.logits_at(params, np.asarray(ids[0]), list(range(21)), cfg,
                         held=4)
    assert float(jnp.abs(got - want).max()) <= 1e-5


def _scan_inputs(s, seed=0):
    keys = jax.random.split(jax.random.key(seed), 6)
    heads, hd, groups, n = 8, 4, 2, 16
    x = jax.random.normal(keys[0], (s, heads, hd))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (s, heads)))
    a = -jnp.exp(jax.random.normal(keys[2], (heads,)))
    b = jax.random.normal(keys[3], (s, groups, n))
    c = jax.random.normal(keys[4], (s, groups, n))
    s0 = jax.random.normal(keys[5], (heads, hd, n))
    return x, dt, a, b, c, s0


def _sequential(x, dt, a, b, c, s0):
    ys, state = [], s0[None]
    for t in range(x.shape[0]):
        y, state = ssd_step(x[t][None], dt[t][None], a, b[t][None],
                            c[t][None], state)
        ys.append(y[0])
    return jnp.stack(ys), state[0]


@pytest.mark.parametrize("s", [1, 5, 8, 13, 16, 24])
def test_chunked_scan_is_the_sequential_scan(s):
    """At every chunk offset (chunks of 8: inside one, on a boundary, a
    ragged tail) and from a carried state."""
    args = _scan_inputs(s)
    y, s1 = ssd_chunked(*args, chunk=8)
    want_y, want_s1 = _sequential(*args)
    np.testing.assert_allclose(y, want_y, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(s1, want_s1, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("split", [3, 8, 11])
def test_chunked_scan_carries_its_state(split):
    x, dt, a, b, c, s0 = _scan_inputs(19, seed=1)
    whole_y, whole_s = ssd_chunked(x, dt, a, b, c, s0, chunk=8)
    y1, mid = ssd_chunked(x[:split], dt[:split], a, b[:split], c[:split], s0,
                          chunk=8)
    y2, end = ssd_chunked(x[split:], dt[split:], a, b[split:], c[split:],
                          mid, chunk=8)
    np.testing.assert_allclose(jnp.concatenate([y1, y2]), whole_y, atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(end, whole_s, atol=1e-5, rtol=1e-5)


def test_rows_with_dt_zero_are_no_steps():
    """What the padded rows of a prefill bucket rest on: dt = 0 leaves the
    state exactly as the real rows left it."""
    x, dt, a, b, c, s0 = _scan_inputs(16, seed=2)
    real = 11
    _, want = ssd_chunked(x[:real], dt[:real], a, b[:real], c[:real], s0,
                          chunk=8)
    _, got = ssd_chunked(x, dt.at[real:].set(0.0), a, b, c, s0, chunk=8)
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-6)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four layers holding experts 0-3, 4-7, 8-11, 12-15 of 16, the shared
    expert counted once, sum to the reference's whole expert layer."""
    kw = dict(num_experts=16, top_k=3, hidden_size=64, latent_size=32,
              expert_width=48, shared_width=96, routed_scaling_factor=2.5)
    whole = LatentMoE(experts_held=(0, 16), **kw)
    u = jax.random.normal(jax.random.key(3), (10, 64))
    params = whole.init(jax.random.key(4), u)["params"]
    # a selection bias that is not zero, so that choice and weight differ
    params = dict(params, router_bias=0.3 * jax.random.normal(
        jax.random.key(5), (16,)))
    want = ref.latent_moe(u, params, TOY, held=0)
    no_shared = dict(params, shared_down={
        "kernel": jnp.zeros_like(params["shared_down"]["kernel"])})

    def share(lo, tree):
        mine = dict(tree, experts_w1=tree["experts_w1"][lo:lo + 4],
                    experts_w2=tree["experts_w2"][lo:lo + 4])
        out, counts = LatentMoE(experts_held=(lo, 4), **kw).apply(
            {"params": mine}, u)
        return out, counts

    routed = sum(share(lo, no_shared)[0] for lo in (0, 4, 8, 12))
    with_shared, _ = share(0, params)
    shared_once = with_shared - share(0, no_shared)[0]
    np.testing.assert_allclose(routed + shared_once, want, atol=1e-5,
                               rtol=1e-5)
    # every pair lands on exactly one share
    pairs = sum(int(share(lo, params)[1][2]) for lo in (0, 4, 8, 12))
    assert pairs == 10 * 3


def test_expert_layer_counts_only_valid_rows():
    kw = dict(num_experts=16, experts_held=(4, 8), top_k=3, hidden_size=64,
              latent_size=32, expert_width=48, shared_width=96)
    layer = LatentMoE(**kw)
    u = jax.random.normal(jax.random.key(6), (6, 64))
    params = layer.init(jax.random.key(7), u)
    valid = jnp.array([True, False, True, True, False, False])
    out, counts = layer.apply(params, u, valid)
    alone, counts_alone = layer.apply(params, u[valid])
    np.testing.assert_allclose(out[valid], alone, atol=1e-6)
    assert counts.tolist() == counts_alone.tolist()
    steps, tokens, pairs, touched, max_load = counts.tolist()
    assert (steps, tokens) == (1, 3) and 0 < touched <= pairs <= 9
    assert 1 <= max_load <= 3


def test_config_refuses_an_unknown_layer_kind():
    with pytest.raises(ValueError, match="M .Mamba-2."):
        NemotronHConfig(hybrid_override_pattern="MXE")
