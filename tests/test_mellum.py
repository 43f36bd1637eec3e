"""The Mellum decoder (grouped-query attention under a window on three
layers in four and at full extent under YaRN on the fourth, softmax-routed
gated experts) against the plain float32 reference, at toy widths on the
CPU: the uncached forward, the part each mechanism plays (a reference with
one changed must disagree), YaRN's table at the published sizes, the expert
shares adding up to the uncut layer, and ``GatedMoE``'s sigmoid users
unmoved by the routing it now chooses between."""

import os
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu.models.mellum import (  # noqa: E402
    MellumConfig,
    MellumForCausalLM,
    RopeParameters,
)
from apex_tpu.ops.rope import yarn_inv_freq  # noqa: E402
from apex_tpu.transformer import moe  # noqa: E402
from benchmark.reference import mellum as ref  # noqa: E402

FULL, WINDOW = "full_attention", "sliding_attention"
# hidden 64, 4 query / 2 KV heads of 16, a window of 8, YaRN from an original
# length of 16 by a factor of 4, 8 experts top-2 of width 32, two periods
YARN = dict(rope_type="yarn", rope_theta=1e4, factor=4.0,
            original_max_position_embeddings=16, beta_fast=4.0,
            beta_slow=1.0, attention_factor=0.1 * np.log(4.0) + 1.0)
PLAIN = dict(rope_type="default", rope_theta=1e4)
TOY = dict(vocab_size=128, hidden_size=64,
           layer_types=(WINDOW, WINDOW, WINDOW, FULL) * 2,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           sliding_window=8, num_experts=8, num_experts_per_tok=2,
           moe_intermediate_size=32, rms_norm_eps=1e-6)
# the same sizes as the reference reads them: the published config's keys
TOY_REF = dict(TOY, layer_types=list(TOY["layer_types"]),
               norm_topk_prob=True,
               rope_parameters={FULL: YARN, WINDOW: PLAIN})
SEQ = 70                        # past eight windows, four original lengths


def make(held=(0, 8), seed=0, **changed):
    """The toy model and weights in which every mechanism matters: matrices
    five times the initialiser's, norm scales off their 1."""
    model = MellumForCausalLM(MellumConfig(**{
        **TOY, "full_attention_rope": RopeParameters(**YARN),
        "sliding_attention_rope": RopeParameters(**PLAIN),
        "experts_held": held, **changed}))
    params = model.init(jax.random.key(seed), jnp.zeros((1, 8), jnp.int32))
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    return model, jax.tree.unflatten(treedef, [
        l + 0.1 * jax.random.normal(k, l.shape, l.dtype) if l.ndim == 1
        else 5 * l for l, k in zip(leaves, keys)])


def rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def ids_of(n=SEQ, seed=0):
    return np.random.default_rng(seed).integers(0, 128, n).astype(np.int32)


def with_rope(kind, **changed):
    """``TOY_REF`` with one block of ``rope_parameters`` changed."""
    blocks = TOY_REF["rope_parameters"]
    return {"rope_parameters": {**blocks, kind: {**blocks[kind], **changed}}}


@pytest.fixture(scope="module")
def toy():
    model, params = make()
    ids = ids_of()
    return model, params, ids, model.apply(params, ids[None])[:, 0]


def test_uncached_forward_matches_the_reference(toy):
    _, params, ids, got = toy
    want = ref.logits_at(params, ids, list(range(SEQ)), TOY_REF)
    assert got.shape == (SEQ, 128) and rel_err(got, want) < 1e-5


def _fewer_experts(params):
    """The tree with each layer's last expert taken away."""
    tree = dict(params["params"])
    for name, layer in tree.items():
        if name.startswith("layers_"):
            tree[name] = dict(layer, mlp=dict(layer["mlp"], **{
                k: layer["mlp"][k][:-1]
                for k in ("experts_gate", "experts_up", "experts_down")}))
    return {"params": tree}


MUTATIONS = {
    "window_one_wider": {"sliding_window": TOY["sliding_window"] + 1},
    "window_layer_read_at_full_extent": {"layer_types": [FULL] * 8},
    "full_layer_windowed": {"layer_types": [WINDOW] * 8},
    "plain_rope_on_a_full_layer": {
        "rope_parameters": {FULL: PLAIN, WINDOW: PLAIN}},
    "attention_factor_left_out": with_rope(FULL, attention_factor=1.0),
    "yarn_on_a_window_layer": {
        "rope_parameters": {FULL: YARN, WINDOW: YARN}},
    "sigmoid_for_softmax": {},
    "weights_not_renormalised": {"norm_topk_prob": False},
    "top_k_less_one": {"num_experts_per_tok": 1},
    "one_expert_fewer": {},
}


def mutated_reference(fault, params, ids, positions):
    """The reference's logits with one part of the architecture changed.
    Two of the faults are no key of the config: the router's scoring and an
    expert dropped from the tree.  A layer's kind also chooses its rope
    block, so the two faults that change ``layer_types`` keep each layer's
    own rope: a full layer windowed still turns under YaRN."""
    config = dict(TOY_REF, **MUTATIONS[fault])
    if fault == "sigmoid_for_softmax":
        with mock.patch.object(
                ref, "router_probs",
                lambda u, kernel: jax.nn.sigmoid(ref._mm(u, kernel))):
            return ref.logits_at(params, ids, positions, config)
    if fault == "one_expert_fewer":
        params = _fewer_experts(params)
    if fault in ("window_layer_read_at_full_extent", "full_layer_windowed"):
        return _with_kinds_but_own_rope(params, ids, positions, config)
    return ref.logits_at(params, ids, positions, config)


def _with_kinds_but_own_rope(params, ids, positions, config):
    """``ref.logits_at`` with the mask of ``config["layer_types"]`` and each
    layer's rope as ``TOY_REF`` has it."""
    plain_attention = ref.attention

    def attention(u, p, cfg, kind, *, own):
        blocks = TOY_REF["rope_parameters"]
        cfg = dict(cfg, rope_parameters={kind: blocks[own]})
        return plain_attention(u, p, cfg, kind)

    with jax.default_matmul_precision("highest"):
        p = params["params"]
        x = ref.embed(params, ids)
        for i, kind in enumerate(config["layer_types"]):
            layer = p[f"layers_{i}"]
            x = x + attention(
                ref.normed(x, layer["input_layernorm"], config),
                layer["self_attn"], config, kind,
                own=TOY_REF["layer_types"][i])
            x = x + ref.mlp_out(
                ref.normed(x, layer["post_attention_layernorm"], config),
                layer, config)
        return ref._head(x[jnp.asarray(positions)], p["norm"]["scale"],
                         p["lm_head"], eps=config["rms_norm_eps"])


@pytest.mark.parametrize("fault", sorted(MUTATIONS))
def test_a_reference_with_one_mechanism_changed_disagrees(toy, fault):
    """Each part of the architecture moves the logits by far more than the
    comparison allows: a system that got it wrong would be caught."""
    _, params, ids, got = toy
    want = mutated_reference(fault, params, ids, list(range(SEQ)))
    assert rel_err(got, want) > 100 * 1e-5, fault


def test_yarn_table_at_the_published_sizes():
    """Factor 16 from 8,192 positions, theta 500,000, a head of 128: pairs up
    to 18 keep their frequency, pairs from 35 are interpolated sixteenfold,
    the ramp between; the attention factor is 0.1 ln 16 + 1."""
    theta, dim = 5e5, 128
    inv = np.asarray(yarn_inv_freq(
        dim, theta, factor=16.0, original_max_position_embeddings=8192,
        beta_fast=32.0, beta_slow=1.0))
    plain = theta ** (-2.0 * np.arange(dim // 2) / dim)
    share = inv / plain
    np.testing.assert_allclose(share[:19], 1.0, rtol=1e-6)
    np.testing.assert_allclose(share[35:], 1 / 16, rtol=1e-6)
    assert (np.diff(share[18:36]) < 0).all()
    np.testing.assert_allclose(share[19], 1 - (15 / 16) / 17, rtol=1e-5)
    published = MellumConfig().full_attention_rope
    got, factor = published.table(dim)
    np.testing.assert_allclose(np.asarray(got), inv, rtol=1e-6)
    assert factor == pytest.approx(0.1 * np.log(16.0) + 1.0, rel=1e-9)
    np.testing.assert_allclose(
        np.asarray(ref.inv_freq(TOY_REF["rope_parameters"][FULL], 16)[0]),
        np.asarray(RopeParameters(**YARN).table(16)[0]), rtol=1e-6)
    plain_table, one = MellumConfig().sliding_attention_rope.table(dim)
    np.testing.assert_allclose(np.asarray(plain_table), plain, rtol=1e-6)
    assert one == 1.0
    with pytest.raises(ValueError, match="rope_type"):
        RopeParameters(rope_type="linear")


def test_expert_shares_add_up_to_the_uncut_layer():
    """The parts that four chips' shares of two experts give are the whole
    softmax-routed layer's output: reference against reference, and the
    system's layer against each share (the training-path cut the catalog
    names; the benchmark's cell holds every expert)."""
    _, whole = make()
    mixer = whole["params"]["layers_2"]["mlp"]
    h = jax.random.normal(jax.random.key(7), (24, 64), jnp.float32)
    want = ref.experts(h, mixer, TOY_REF)
    parts = jnp.zeros_like(want)
    pairs = 0
    for lo in range(0, 8, 2):
        share = dict(mixer, **{k: mixer[k][lo:lo + 2] for k in (
            "experts_gate", "experts_up", "experts_down")})
        part = ref.experts(h, share, TOY_REF, held=lo)
        parts = parts + part
        layer = moe.GatedMoE(
            num_experts=8, experts_held=(lo, 2), top_k=2, hidden_size=64,
            expert_width=32, shared_width=0, scoring="softmax")
        got, counts = layer.apply({"params": share}, h)
        assert rel_err(got, part) < 1e-5
        assert counts[1] == 24
        pairs += int(counts[2])
    assert pairs == 24 * 2                 # every choice lands on one share
    assert rel_err(parts, want) < 1e-5
    whole_layer = moe.GatedMoE(
        num_experts=8, experts_held=(0, 8), top_k=2, hidden_size=64,
        expert_width=32, shared_width=0, scoring="softmax")
    got, counts = whole_layer.apply({"params": mixer}, h)
    assert rel_err(got, want) < 1e-5 and int(counts[2]) == 48


def test_softmax_route_scores_all_experts_and_renormalises():
    x = jax.random.normal(jax.random.key(1), (12, 64), jnp.float32)
    kernel = jax.random.normal(jax.random.key(2), (64, 8), jnp.float32)
    chosen, weights = moe.topk_softmax_route(x, kernel, 3)
    probs = np.asarray(jax.nn.softmax(
        jnp.dot(x, kernel, precision=jax.lax.Precision.HIGHEST), -1))
    want = np.argsort(-probs, axis=-1)[:, :3]
    assert (np.asarray(chosen) == want).all() and chosen.dtype == jnp.int32
    picked = np.take_along_axis(probs, want, -1)
    np.testing.assert_allclose(np.asarray(weights),
                               picked / picked.sum(-1, keepdims=True),
                               rtol=1e-6)
    assert weights.dtype == jnp.float32
    # a softmax-routed layer has no selection bias and, at width 0, no
    # shared expert; another scoring is refused
    layer = moe.GatedMoE(num_experts=8, experts_held=(0, 8), top_k=2,
                         hidden_size=64, expert_width=32, shared_width=0,
                         scoring="softmax")
    assert sorted(layer.init(jax.random.key(0), x)["params"]) == [
        "experts_down", "experts_gate", "experts_up", "router_kernel"]
    with pytest.raises(ValueError, match="scoring"):
        moe.GatedMoE(num_experts=8, experts_held=(0, 8), top_k=2,
                     hidden_size=64, expert_width=32, shared_width=0,
                     scoring="tanh").init(jax.random.key(0), x)


def _gated_moe_as_it_was(p, x, valid, *, held, k, scale):
    """``GatedMoE.__call__`` as PR 31 wrote it, before it chose between two
    routings: the same operations in the same order."""
    chosen, weights = moe.topk_sigmoid_route(
        x, p["router_kernel"], p["router_bias"], k, scale)
    pairs = moe.held_pairs(chosen, held, valid)
    rows = x[pairs.token_of]
    gate = moe.grouped_matmul(rows, p["experts_gate"], pairs.sizes)
    up = moe.grouped_matmul(rows, p["experts_up"], pairs.sizes)
    hid = (jax.nn.silu(gate) * up).astype(x.dtype)
    out = moe.grouped_matmul(hid, p["experts_down"], pairs.sizes)
    routed = pairs.combine(out, weights).astype(x.dtype)
    shared = (jax.nn.silu(x @ p["shared_gate"]["kernel"])
              * (x @ p["shared_up"]["kernel"]))
    return routed + shared @ p["shared_down"]["kernel"], pairs.counts


@pytest.mark.parametrize("masked", [False, True])
def test_sigmoid_routed_gated_moe_is_bit_identical(masked):
    layer = moe.GatedMoE(
        num_experts=16, experts_held=(4, 8), top_k=3, hidden_size=64,
        expert_width=48, shared_width=96, routed_scaling_factor=2.5)
    x = jax.random.normal(jax.random.key(11), (20, 64), jnp.float32)
    valid = (jnp.arange(20) % 3 != 0) if masked else None
    params = layer.init(jax.random.key(12), x)
    assert "router_bias" in params["params"]
    params = jax.tree.map(lambda l: 5 * l, params)
    got, counts = jax.jit(layer.apply)(params, x, valid)
    want, want_counts = jax.jit(
        lambda p, x, v: _gated_moe_as_it_was(
            p, x, v, held=(4, 8), k=3, scale=2.5))(params["params"], x, valid)
    assert (np.asarray(got) == np.asarray(want)).all()
    assert np.asarray(counts).tolist() == np.asarray(want_counts).tolist()


def test_cache_layers_declare_rows_rings_and_counters():
    from apex_tpu.serving import kv_cache as kvc

    model, _ = make()
    layers = model.cache_layers()
    assert len(layers) == 16
    kinds = [type(l).__name__ for l in layers[::2]]
    assert kinds == ["KVWindowRows"] * 3 + ["KVRows"] + [
        "KVWindowRows"] * 3 + ["KVRows"]
    assert all(isinstance(l, kvc.CallCounters) for l in layers[1::2])
    assert layers[0] == kvc.KVWindowRows(2, 16, 8) and layers[0].rows == 16
    assert layers[6] == kvc.KVRows(2, 16)
    with pytest.raises(ValueError, match="layer_types"):
        MellumConfig(layer_types=("full", "sliding_attention"))
    with pytest.raises(ValueError, match="group"):
        MellumConfig(num_attention_heads=6, num_key_value_heads=4)
