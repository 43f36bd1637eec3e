"""The decode step's in-place K/V read (``ops/cached_decode_attention.py``)
against the reference read (``serving/kv_cache.py::cached_attention``), with
the kernel's own code run by the Pallas interpreter on the CPU: first the
kernel alone on the same buffers, then whole engines whose decode program
takes it.

Blockwise sums round differently from one ``max_len``-wide reduction, so
kernel and reference are compared to a tolerance:

- float32: ``F32_ATOL`` = 5e-6 on results of O(1) (measured <= 7e-7; the
  scale of ``tests/conftest.py::LOGITS_ATOL``, which compares two programs
  over the same dot products);
- bf16: ``BF16_TOL`` = 2e-2, absolute and relative: a bf16 result of
  magnitude 2-4 has an ulp of 2**-6 = 1.6e-2, and the probabilities are
  rounded to bf16 before the second product on both sides.

What the comparisons guard - a row read past the bound, the wrong layer, a
neighbour's rows - is a NaN here, not a small error: every row past each
lane's bound and every other layer of the buffers is NaN.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import _logging
from apex_tpu import serving as sv
from apex_tpu.models import LlamaConfig, LlamaForCausalLM
from apex_tpu.ops import cached_decode_attention as cda
from apex_tpu.serving import kv_cache as kvc

F32_ATOL = 5e-6
BF16_TOL = 2e-2
HD = 128
# (rep, kv_heads): Mistral's grouping, Nemotron-H's, plain multi-head
GROUPINGS = [(4, 8), (16, 2), (1, 4)]


@pytest.fixture(autouse=True)
def _interpret_kernels(monkeypatch):
    monkeypatch.setenv("APEX_TPU_KERNELS", "interpret")
    yield


def _close(got, want, dtype, msg=""):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all(), f"{msg}: the kernel read a NaN row"
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=0, atol=F32_ATOL,
                                   err_msg=msg)
    else:
        np.testing.assert_allclose(got, want, rtol=BF16_TOL, atol=BF16_TOL,
                                   err_msg=msg)


def _buffers(rep, nkv, dtype, bounds, layers=2, seed=0):
    """Random ``q``, clean ``k`` / ``v`` for the reference, and the same
    buffers with every row past a lane's bound made NaN."""
    max_len = 3 * cda.block_rows(1 << 20, nkv)
    lanes = len(bounds)
    rng = np.random.default_rng(seed)
    shape = (layers, lanes, max_len, nkv, HD)
    k = rng.standard_normal(shape).astype(np.float32)
    v = rng.standard_normal(shape).astype(np.float32)
    q = rng.standard_normal((lanes, rep * nkv, 1, HD)).astype(np.float32)
    k_nan, v_nan = k.copy(), v.copy()
    for lane, bound in enumerate(bounds):
        k_nan[:, lane, bound + 1:] = np.nan
        v_nan[:, lane, bound + 1:] = np.nan
    return tuple(jnp.asarray(a, dtype) for a in (q, k, v, k_nan, v_nan))


def _edge_bounds(nkv):
    block = cda.block_rows(1 << 20, nkv)
    max_len = 3 * block
    # max_len itself is an idle lane at lengths == max_len: clamped
    return np.asarray([0, 1, block - 1, block, block + 1, 2 * block,
                       max_len - 1, max_len], np.int32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("rep,nkv", GROUPINGS)
def test_kernel_matches_the_reference_read_and_stays_inside_the_bound(
        rep, nkv, dtype):
    bounds = _edge_bounds(nkv)
    q, k, v, k_nan, v_nan = _buffers(rep, nkv, dtype, bounds)
    traces = []

    @jax.jit
    def read(*args):
        traces.append(1)
        return cda.cached_decode_attention(*args)

    for layer in (1, 0):
        want = kvc.decode_attention(q, k[layer], v[layer], bounds)
        # the other layer is NaN throughout
        other = jnp.full_like(k_nan[0], jnp.nan)
        kk = k_nan.at[1 - layer].set(other)
        vv = v_nan.at[1 - layer].set(other)
        got = read(q, kk, vv, jnp.int32(layer), bounds)
        assert got.shape == want.shape and got.dtype == want.dtype
        _close(got, want, dtype, f"layer {layer}")
    # the layer is an operand: two layers, one program
    assert len(traces) == 1


@pytest.mark.parametrize("rep,nkv", GROUPINGS)
def test_a_lanes_result_does_not_hang_on_its_neighbours(rep, nkv):
    bounds = _edge_bounds(nkv)[:6]
    q, k, v, _, _ = _buffers(rep, nkv, jnp.float32, bounds, layers=1)
    read = jax.jit(cda.cached_decode_attention)
    first = np.asarray(read(q, k, v, jnp.int32(0), bounds))
    moved = bounds.copy()
    moved[1::2] = moved[1::2][::-1] + 3       # every odd lane's bound moves
    second = np.asarray(read(q, k, v, jnp.int32(0), moved))
    assert np.array_equal(first[0::2], second[0::2])
    assert not np.array_equal(first[1::2], second[1::2])


# ---- engines whose decode program takes the kernel -------------------------

# head width 128 (the kernel's predicate), GQA 2:1; everything else tiny
LLAMA = LlamaConfig(vocab_size=128, hidden_size=512, intermediate_size=128,
                    num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=2)
NEMOTRON = dict(vocab_size=256, hidden_size=64, hybrid_override_pattern="M*E*",
                num_attention_heads=4, num_key_value_heads=2, head_dim=HD,
                mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
                ssm_state_size=16, conv_kernel=4, chunk_size=8,
                n_routed_experts=16, num_experts_per_tok=3,
                moe_latent_size=32, moe_intermediate_size=48,
                moe_shared_expert_intermediate_size=96,
                routed_scaling_factor=2.5, layer_norm_epsilon=1e-5)
# float32 logits of O(1) through two layers whose reads differ by
# F32_ATOL-scale rounding
ENGINE_ATOL = 1e-4


def _llama():
    model = LlamaForCausalLM(LLAMA)
    return model, model.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))


def _nemotron_h():
    from apex_tpu.models.nemotron_h import (
        NemotronHConfig,
        NemotronHForCausalLM,
    )

    model = NemotronHForCausalLM(NemotronHConfig(**NEMOTRON,
                                                 experts_held=(4, 8)))
    return model, model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))


@pytest.fixture
def dispatched():
    """``(path, shape)`` of each ``cached_decode_attention`` dispatch."""
    seen = []

    def sink(event):
        if (event["event"] == "kernel_dispatch"
                and event["op"] == "cached_decode_attention"):
            seen.append(event)

    _logging.add_event_sink(sink)
    yield seen
    _logging.remove_event_sink(sink)


def _drive(engine, vocab):
    """Prompts of three lengths (one chunked), greedy decode while the
    lengths drift apart, a slot released and refilled on the way: the token
    streams and every step's logits of the active lanes."""
    rng = np.random.default_rng(0)
    prompts = {0: 5, 1: 17, 2: 40}
    last = np.zeros((3,), np.int32)
    logits = []
    for slot, n in prompts.items():
        row = engine.prefill(slot, rng.integers(0, vocab, n).tolist())
        logits.append(np.asarray(row))
        last[slot] = int(np.argmax(row))
    streams = {slot: [int(last[slot])] for slot in prompts}
    active = np.ones((3,), bool)
    for step in range(14):
        if step == 5:
            engine.release(1)
            active[1] = False
        if step == 8:
            row = engine.prefill(1, rng.integers(0, vocab, 9).tolist())
            logits.append(np.asarray(row))
            last[1], active[1] = int(np.argmax(row)), True
            streams[1].append(int(last[1]))
        out = np.asarray(engine.decode(last, active))
        logits.append(out[active])
        for slot in np.flatnonzero(active):
            last[slot] = int(np.argmax(out[slot]))
            streams[slot].append(int(last[slot]))
    return streams, logits


@pytest.mark.parametrize("build", [_llama, _nemotron_h],
                         ids=["llama", "nemotron_h"])
def test_engine_streams_are_the_reference_reads(build, dispatched,
                                                monkeypatch):
    model, params = build()
    vocab = model.config.vocab_size
    # several blocks a lane at test size: 32 rows of 2 KV heads
    monkeypatch.setattr(cda, "COLUMNS", 64)

    def engine():
        return sv.DecodeEngine(model, params, slots=3, max_len=128,
                               prefill_len=32)

    with monkeypatch.context() as m:
        m.setattr(kvc, "_reads_in_place", lambda cache, q: False)
        want_streams, want_logits = _drive(engine(), vocab)
    assert not dispatched
    eng = engine()
    got_streams, got_logits = _drive(eng, vocab)
    assert eng.decode_compiles() == 1
    assert dispatched and all(
        e["path"] == "pallas" and e["block"] == 32 and e["hd"] == HD
        and e["kv_heads"] == 2 and e["rep"] == 2 and e["max_len"] == 128
        for e in dispatched), dispatched
    assert got_streams == want_streams
    for step, (got, want) in enumerate(zip(got_logits, want_logits)):
        np.testing.assert_allclose(got, want, rtol=0, atol=ENGINE_ATOL,
                                   err_msg=f"step {step}")


@pytest.mark.parametrize("kw", [
    {"quant": sv.QuantConfig(kv=True)},
    {"paged": sv.PagedCacheConfig(block_size=16)}], ids=["int8", "paged"])
def test_int8_rows_and_a_block_table_take_the_reference_read(kw, dispatched):
    model, params = _llama()
    eng = sv.DecodeEngine(model, params, slots=2, max_len=64,
                          prefill_len=16, **kw)
    row = eng.prefill(0, [3, 1, 4, 1, 5])
    eng.decode(np.asarray([int(np.argmax(row)), 0], np.int32),
               np.asarray([True, False]))
    assert dispatched and all(e["path"] == "reference" for e in dispatched)
