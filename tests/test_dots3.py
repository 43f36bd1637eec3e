"""The dots3-note decoder (latent attention with a learned key selector on
the full layers and a window on the others, gated routed experts) against
the plain float32 reference, at toy widths on the CPU: the uncached forward,
the parts each mechanism plays (a reference without it must disagree), the
expert shares adding up to the uncut layer, and ``LatentMoE`` unmoved by the
dispatch it now shares with ``GatedMoE``."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu.models.dots3 import (  # noqa: E402
    Dots3NoteConfig,
    Dots3NoteForCausalLM,
)
from apex_tpu.transformer import moe  # noqa: E402
from benchmark.reference import dots3 as ref  # noqa: E402

FULL, WINDOW = "full_attention", "sliding_attention"
# hidden 64, 2 + 2 heads, ranks 16 / 8, the selector's top-8 of 2 heads, a
# window of 5, 16 experts top-2
TOY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96,
    layer_types=(FULL, WINDOW, WINDOW, WINDOW, FULL),
    first_k_dense_replace=1, num_attention_heads=2, q_lora_rank=16,
    kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    rope_theta=8e7, index_n_heads=2, index_head_dim=8, index_topk=8,
    swa_num_attention_heads=2, swa_q_lora_rank=16, swa_kv_lora_rank=16,
    swa_qk_nope_head_dim=12, swa_qk_rope_head_dim=4, swa_v_head_dim=8,
    swa_rope_theta=5e4, sliding_window_size=5, n_routed_experts=16,
    num_experts_per_tok=2, moe_intermediate_size=24,
    routed_scaling_factor=1.0, rms_norm_eps=1e-5)
# the same sizes as the reference reads them: the published config's keys
TOY_REF = dict(TOY, layer_types=list(TOY["layer_types"]),
               attention_gate_type="headwise",
               swa_attention_gate_type="headwise",
               apply_mla_qkv_lora_rescale=True, n_shared_experts=1)
HELD = (4, 4)
SEQ = 48                        # 6 x index_topk, 3 wraps of the 16-row ring


def make(held=HELD, seed=0):
    """The toy model and weights in which every mechanism matters: matrices
    five times the initialiser's, norm scales and biases off their 1 and 0."""
    model = Dots3NoteForCausalLM(Dots3NoteConfig(**TOY, experts_held=held))
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
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


@pytest.fixture(scope="module")
def toy():
    model, params = make()
    ids = ids_of()
    return model, params, ids, model.apply(params, ids[None])[:, 0]


def test_uncached_forward_matches_the_reference(toy):
    _, params, ids, got = toy
    want = ref.logits_at(params, ids, list(range(SEQ)), TOY_REF, held=HELD[0])
    assert got.shape == (SEQ, 256) and rel_err(got, want) < 1e-5


@pytest.mark.parametrize("left_out", [
    {"index_topk": 10 ** 6},                # selection dropped: every row read
    {"index_topk": TOY["index_topk"] - 1},
    {"attention_gate_type": None, "swa_attention_gate_type": None},
    {"attention_gate_type": None},          # the full layers' gate alone
    {"apply_mla_qkv_lora_rescale": False},
    {"sliding_window_size": TOY["sliding_window_size"] + 1},
    {"swa_rope_theta": TOY["rope_theta"]},  # the full layers' theta
    {"num_experts_per_tok": 1},
], ids=lambda d: "+".join(d))
def test_a_reference_without_one_mechanism_disagrees(toy, left_out):
    """Each part of the architecture moves the logits by far more than the
    comparison allows: a system that skipped it would be caught."""
    _, params, ids, got = toy
    config = dict(TOY_REF, **left_out)
    want = ref.logits_at(params, ids, list(range(SEQ)), config, held=HELD[0])
    assert rel_err(got, want) > 10 * 1e-5, left_out


@pytest.mark.parametrize("levels", [4, 10 ** 6])
def test_selection_keeps_what_top_k_keeps_ties_and_all(levels):
    """``_select`` (indices, for a decode step's gather) and
    ``_select_mask`` (a threshold found bit by bit, for a chunk's mask)
    against ``lax.top_k`` scattered into a mask: with many equal scores, with
    rows that see fewer keys than they may select, with negative scores."""
    from apex_tpu.serving.kv_cache import _select, _select_mask

    rng = np.random.default_rng(0)
    scores = jnp.asarray(rng.integers(-levels, levels, (14, 24)) / 7,
                         jnp.float32)
    scores = jnp.where(jnp.arange(24) % 5 == 0, -0.0 * scores, scores)
    at = jnp.arange(24)
    visible = at[None] <= (at[:14, None] * 2)         # 1, 3, 5, ... keys
    values, want = jax.lax.top_k(jnp.where(visible, scores, -jnp.inf), 5)
    mask = jnp.zeros((14, 24), bool).at[jnp.arange(14)[:, None], want].set(
        values > -jnp.inf)
    index, chosen = _select(scores, visible, 5)
    assert (np.asarray(index) == np.asarray(want)).all()
    assert (np.asarray(chosen) == np.asarray(values > -jnp.inf)).all()
    selected = jax.jit(_select_mask, static_argnums=2)(scores, visible, 5)
    assert (np.asarray(selected) == np.asarray(mask)).all()
    assert np.asarray(selected).sum(1).tolist() == [1, 3] + [5] * 12


def test_expert_shares_add_up_to_the_uncut_layer():
    """The routed parts that four chips' shares give, plus the shared expert
    once, are the whole layer's output: reference against reference, and the
    system's layer against each share."""
    whole_model, whole = make(held=(0, 16))
    mixer = whole["params"]["layers_2"]["mlp"]
    h = jax.random.normal(jax.random.key(7), (24, 64), jnp.float32)
    want = ref.gated_moe(h, mixer, TOY_REF, held=0)
    parts = jnp.zeros_like(want)
    for lo in range(0, 16, 4):
        share = dict(mixer, **{k: mixer[k][lo:lo + 4] for k in (
            "experts_gate", "experts_up", "experts_down")})
        part = ref.gated_moe(h, share, TOY_REF, held=lo, shared=False)
        parts = parts + part
        layer = moe.GatedMoE(
            num_experts=16, experts_held=(lo, 4), top_k=2, hidden_size=64,
            expert_width=24, shared_width=24)
        got, counts = layer.apply({"params": share}, h)
        with_shared = ref.gated_moe(h, share, TOY_REF, held=lo)
        assert rel_err(got, with_shared) < 1e-5
        assert counts[1] == 24 and 0 < counts[2] <= 48
    shared_only = ref.gated_moe(h, dict(mixer, **{
        k: mixer[k][:0] for k in ("experts_gate", "experts_up",
                                  "experts_down")}), TOY_REF)
    assert rel_err(parts + shared_only, want) < 1e-5


def test_gated_moe_counts_only_valid_rows():
    _, params = make()
    mixer = params["params"]["layers_1"]["mlp"]
    layer = moe.GatedMoE(num_experts=16, experts_held=HELD, top_k=2,
                         hidden_size=64, expert_width=24, shared_width=24)
    h = jax.random.normal(jax.random.key(3), (10, 64), jnp.float32)
    valid = jnp.arange(10) < 6
    out, counts = layer.apply({"params": mixer}, h, valid)
    alone, alone_counts = layer.apply({"params": mixer}, h[:6])
    assert rel_err(out[:6], alone) < 1e-6
    assert np.asarray(counts).tolist() == np.asarray(alone_counts).tolist()
    with pytest.raises(ValueError, match="experts_held"):
        moe.GatedMoE(num_experts=16, experts_held=(14, 4), top_k=2,
                     hidden_size=64, expert_width=24,
                     shared_width=24).init(jax.random.key(0), h)


def _latent_moe_as_it_was(p, x, valid, *, lo, held, k, scale):
    """``LatentMoE.__call__`` as PR 27 wrote it, before the dispatch became
    ``held_pairs``: the same operations in the same order."""
    t = x.shape[0]
    chosen, weights = moe.topk_sigmoid_route(
        x, p["router_kernel"], p["router_bias"], k, scale)
    here = (chosen >= lo) & (chosen < lo + held)
    if valid is not None:
        here &= valid[:, None]
    key = jnp.where(here, chosen - lo, held).reshape(t * k)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)
    token_of = order // k
    latent = x @ p["latent_down"]["kernel"]
    rows = latent[token_of]
    hid = moe.grouped_matmul(rows, p["experts_w1"], sizes)
    hid = jnp.square(jax.nn.relu(hid)).astype(x.dtype)
    out = moe.grouped_matmul(hid, p["experts_w2"], sizes)
    out = jnp.where((key[order] < held)[:, None],
                    out * weights.reshape(t * k)[order][:, None], 0.0)
    inverse = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    routed = out[inverse].reshape(t, k, -1).sum(1)
    routed = routed.astype(x.dtype) @ p["latent_up"]["kernel"]
    shared = jnp.square(jax.nn.relu(x @ p["shared_up"]["kernel"]))
    shared = shared @ p["shared_down"]["kernel"]
    load = sizes[:held]
    tokens = t if valid is None else valid.sum()
    counts = jnp.stack([jnp.int32(1), jnp.asarray(tokens, jnp.int32),
                        load.sum(), (load > 0).sum().astype(jnp.int32),
                        load.max()])
    return routed + shared, counts


@pytest.mark.parametrize("masked", [False, True])
def test_latent_moe_is_bit_identical_after_the_dispatch_refactor(masked):
    layer = moe.LatentMoE(
        num_experts=16, experts_held=(4, 8), top_k=3, hidden_size=64,
        latent_size=32, expert_width=48, shared_width=96,
        routed_scaling_factor=2.5)
    x = jax.random.normal(jax.random.key(11), (20, 64), jnp.float32)
    valid = (jnp.arange(20) % 3 != 0) if masked else None
    params = layer.init(jax.random.key(12), x)
    params = jax.tree.map(lambda l: 5 * l, params)
    got, counts = jax.jit(layer.apply)(params, x, valid)
    want, want_counts = jax.jit(
        lambda p, x, v: _latent_moe_as_it_was(
            p, x, v, lo=4, held=8, k=3, scale=2.5))(params["params"], x, valid)
    assert (np.asarray(got) == np.asarray(want)).all()
    assert np.asarray(counts).tolist() == np.asarray(want_counts).tolist()
