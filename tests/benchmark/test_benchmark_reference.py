"""The two plain float32 references against the system at toy size, on
seeded random weights (the chip run compares them at the published widths,
behind the measured window)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import gpt2, llama


@pytest.fixture(scope="module")
def gpt():
    from apex_tpu.transformer.testing import GPTModel

    model = GPTModel(num_layers=2, hidden_size=64, num_attention_heads=4,
                     vocab_size=128, max_sequence_length=32)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 128)
    params = model.init(jax.random.PRNGKey(0), ids)
    # biases and norm parameters away from their 0 / 1 start, so that a
    # reference that dropped one would be caught
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    leaves = [l + 0.1 * jax.random.normal(k, l.shape) if l.ndim == 1 else l
              for l, k in zip(leaves, keys)]
    return model, jax.tree.unflatten(treedef, leaves), ids


def test_gpt2_reference_matches_the_systems_token_losses(gpt):
    model, params, ids = gpt
    labels = jnp.roll(ids, -1, axis=1)
    system = np.asarray(model.apply(params, ids, labels=labels))
    for b in range(2):
        ref = np.asarray(gpt2.token_losses(params, ids[b], labels[b],
                                           n_head=4))
        # float32 on both sides: only the order of sums differs
        np.testing.assert_allclose(system[b], ref, rtol=2e-5, atol=2e-5)


def test_gpt2_reference_notices_a_wrong_model(gpt):
    model, params, ids = gpt
    labels = jnp.roll(ids, -1, axis=1)
    ref = np.asarray(gpt2.token_losses(params, ids[0], labels[0], n_head=4))
    wrong = np.asarray(gpt2.token_losses(params, ids[0], labels[0],
                                         n_head=2))
    err = np.linalg.norm(wrong - ref) / np.linalg.norm(ref - ref.mean())
    assert err > 0.05          # the runner's tolerance on the chip


@pytest.fixture(scope="module")
def mistral():
    from apex_tpu.models import LlamaConfig, LlamaForCausalLM

    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=160,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5, rope_theta=1e6))
    ids = jax.random.randint(jax.random.PRNGKey(3), (1, 24), 0, 128)
    params = model.init(jax.random.PRNGKey(0), ids)
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    leaves = [l + 0.1 * jax.random.normal(k, l.shape) if l.ndim == 1 else l
              for l, k in zip(leaves, keys)]
    return model, jax.tree.unflatten(treedef, leaves), ids


KW = dict(n_head=4, n_kv=2, theta=1e6, eps=1e-5)


def test_llama_reference_matches_the_uncached_forward(mistral):
    model, params, ids = mistral
    system = np.asarray(model.apply(params, ids))[:, 0]      # [s, vocab]
    ref = np.asarray(llama.logits_at(params, ids[0], list(range(24)), **KW))
    np.testing.assert_allclose(system, ref, rtol=2e-4, atol=2e-5)


def test_llama_reference_matches_prefill_then_decode_through_the_cache(
        mistral):
    from apex_tpu import serving as sv

    model, params, ids = mistral
    eng = sv.DecodeEngine(model, params, slots=2, max_len=64, prefill_len=16)
    seq = [int(t) for t in ids[0, :20]]
    logits = eng.prefill(0, seq)                 # chunked: 16 + 4
    first = np.asarray(logits)
    active = np.array([True, False])
    for _ in range(4):
        seq.append(int(jnp.argmax(logits)))
        logits = eng.decode(np.array([seq[-1], 0], np.int32), active)[0]
    ref = np.asarray(llama.logits_at(params, np.asarray(seq, np.int32),
                                     [19, 23], **KW))
    scale = np.abs(ref).max()
    assert np.abs(first - ref[0]).max() / scale < 1e-4
    assert np.abs(np.asarray(logits) - ref[1]).max() / scale < 1e-4


def test_llama_reference_notices_a_wrong_rope_base(mistral):
    _, params, ids = mistral
    ref = np.asarray(llama.logits_at(params, ids[0], [23], **KW))
    wrong = np.asarray(llama.logits_at(params, ids[0], [23],
                                       **dict(KW, theta=1e4)))
    assert np.abs(wrong - ref).max() / np.abs(ref).max() > 0.03
