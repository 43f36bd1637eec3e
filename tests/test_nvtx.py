"""nvtx-compat trace annotations + named_scope labels survive into HLO."""

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.utils import nvtx


def _hlo_with_labels(lowered):
    """Scope labels live in the lowering's debug info."""
    return lowered.as_text(debug_info=True)


def test_range_context_and_stack():
    with nvtx.range("outer"):
        depth = nvtx.range_push("inner")
        assert depth == 1
        assert nvtx.range_pop() == 1
    with pytest.raises(RuntimeError):
        nvtx.range_pop()


def test_named_scope_labels_reach_hlo():
    def fn(x):
        with nvtx.range("my_hot_region"):
            return jnp.sum(x * 2.0)

    hlo = _hlo_with_labels(jax.jit(fn).lower(jnp.ones((8,))))
    assert "my_hot_region" in hlo


def test_model_scopes_reach_hlo():
    from apex_tpu.transformer.testing import GPTModel

    model = GPTModel(num_layers=1, hidden_size=32, num_attention_heads=2,
                     vocab_size=64, max_sequence_length=16)
    ids = jnp.zeros((2, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)
    hlo = _hlo_with_labels(jax.jit(lambda p, i: model.apply(p, i)).lower(
        params, ids))
    assert "parallel_attention" in hlo
    assert "parallel_mlp" in hlo
