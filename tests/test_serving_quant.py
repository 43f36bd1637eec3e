"""Quantized serving (ISSUE 19): int8 weights, int8 KV cache,
quantized tp collectives.

Acceptance is **agreement-tier**, not bit-tier: a quantized engine's
pinned greedy stream must agree with the fp32 engine's at a high rate
with bounded per-position logit error — quantization is a real
rounding step, so the fp bit-exactness ladder does not apply across
the fp/quant boundary.  *Within* a quantized engine every structural
guarantee still holds bit-for-bit and is pinned here: chunk splits are
invisible, paged ≡ dense, speculation ≡ plain decode, preemption
capture → restore ≡ uninterrupted — the same values/extents/op-order
argument as fp32, just over int8 bytes.  The default-off path
(``quant=None``) is byte-for-byte the fp engine: no quant events, no
quant cache types, no QTensor leaves, untouched quant metrics.

Plus: the one-spelling-site int8 primitives against a numpy oracle,
compile-count guards for every program family under quant (dequant
runs INSIDE the existing jitted bodies — no new program family), the
streams-per-GB capacity bar, quant-aware tp param specs, checkpoint
loading with ``quantize=True``, hot-swap requantization, and the
``serving_quant_eval`` → metrics bridge plumbing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import _logging
from apex_tpu import serving as sv
from apex_tpu.amp.quant import INT8_QMAX, dequantize_int8, quantize_int8
from apex_tpu.models import LlamaConfig, LlamaForCausalLM
from apex_tpu.obs import bridge as obs_bridge
from apex_tpu.serving.engine import TPConfig, tp_param_shardings
from apex_tpu.serving.kv_cache import QuantKVCache
from apex_tpu.serving.paged_kv_cache import (PagedCacheConfig,
                                             QuantPagedKVCache,
                                             bytes_per_block)
from apex_tpu.serving.quant import (QTensor, QuantConfig, dequant_params,
                                    evaluate_quant, is_quantized,
                                    kv_bytes_per_token, max_logit_error,
                                    param_bytes, quantize_params,
                                    serving_param_spec, stream_agreement)

# GQA like test_serving_tp.py: kv_heads (2) < heads (4)
CFG = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, max_position_embeddings=256)
MAX = 96
W_KV = QuantConfig(weights=True, kv=True)


@pytest.fixture(scope="module")
def model():
    return LlamaForCausalLM(CFG)


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def _prompt(seed=0, n=12):
    rng = np.random.default_rng(seed)
    return [int(x) for x in rng.integers(1, CFG.vocab_size, n)]


def _greedy(eng, prompt, steps, slot=0):
    """Greedy stream + per-position decode logits off one slot."""
    logits = eng.prefill(slot, list(prompt))
    stream = [int(jnp.argmax(logits))]
    per_pos = []
    toks = np.zeros((eng.slots,), np.int32)
    act = np.zeros((eng.slots,), bool)
    act[slot] = True
    for _ in range(steps):
        toks[slot] = stream[-1]
        lg = np.asarray(eng.decode(toks, act)[slot])
        per_pos.append(lg)
        stream.append(int(lg.argmax()))
    return stream, np.stack(per_pos)


def _teacher_forced(eng, prompt, ref_stream, slot=0):
    """Per-position greedy picks with the REFERENCE stream fed in.

    Free-running streams cascade: one flipped argmax changes every
    subsequent input, so positionwise agreement measures divergence
    length, not quantization quality.  Teacher-forcing pins the inputs
    to the fp32 stream so each position is an independent same-prefix
    comparison — the honest per-token agreement rate."""
    logits = eng.prefill(slot, list(prompt))
    picks = [int(jnp.argmax(logits))]
    per_pos = []
    toks = np.zeros((eng.slots,), np.int32)
    act = np.zeros((eng.slots,), bool)
    act[slot] = True
    for tok in ref_stream[:-1]:
        toks[slot] = tok
        lg = np.asarray(eng.decode(toks, act)[slot])
        per_pos.append(lg)
        picks.append(int(lg.argmax()))
    return picks, np.stack(per_pos)


class _EventTap:
    def __init__(self):
        self.events = []

    def __enter__(self):
        self._sink = lambda e: self.events.append(dict(e))
        _logging.add_event_sink(self._sink)
        return self

    def __exit__(self, *exc):
        _logging.remove_event_sink(self._sink)

    def of(self, kind):
        return [e for e in self.events if e.get("event") == kind]


# ---------------------------------------------------------------------------
# the int8 primitives (one spelling site) vs a numpy oracle
# ---------------------------------------------------------------------------


class TestInt8Primitives:
    def test_matches_numpy_oracle_last_axis(self):
        rng = np.random.default_rng(0)
        x = rng.normal(size=(8, 16)).astype(np.float32)
        q, scale = quantize_int8(jnp.asarray(x), axis=-1)
        amax = np.abs(x).max(axis=-1)
        want_scale = amax / 127.0
        np.testing.assert_allclose(np.asarray(scale), want_scale,
                                   rtol=1e-6)
        want_q = np.clip(np.round(x / want_scale[:, None]), -127, 127)
        assert np.asarray(q).dtype == np.int8
        np.testing.assert_array_equal(np.asarray(q),
                                      want_q.astype(np.int8))

    def test_axis0_scale_per_output_channel(self):
        x = jnp.asarray(np.random.default_rng(1).normal(
            size=(6, 10)).astype(np.float32))
        q, scale = quantize_int8(x, axis=0)
        assert q.shape == (6, 10) and scale.shape == (10,)
        dq = dequantize_int8(q, scale, axis=0)
        assert dq.shape == x.shape and dq.dtype == jnp.float32

    def test_zero_group_takes_scale_one(self):
        """An all-zero group must take scale 1.0 (not 0): unallocated
        quant-cache rows dequantize to exact finite zeros — masked
        attention reads must never meet 0 * inf = NaN."""
        x = jnp.zeros((4, 8), jnp.float32)
        q, scale = quantize_int8(x, axis=-1)
        np.testing.assert_array_equal(np.asarray(scale),
                                      np.ones((4,), np.float32))
        np.testing.assert_array_equal(
            np.asarray(dequantize_int8(q, scale, axis=-1)),
            np.zeros((4, 8), np.float32))

    def test_roundtrip_error_bounded_by_half_scale(self):
        rng = np.random.default_rng(2)
        x = (rng.normal(size=(16, 32)) * 10).astype(np.float32)
        q, scale = quantize_int8(jnp.asarray(x), axis=-1)
        dq = np.asarray(dequantize_int8(q, scale, axis=-1))
        bound = np.asarray(scale)[:, None] * 0.5 * (1 + 1e-5)
        assert np.all(np.abs(x - dq) <= bound)

    def test_amax_element_requantizes_exactly(self):
        """The group amax element maps to exactly ±127, so a payload
        survives dequantize → requantize bit-for-bit — the property
        that makes KV capture → restore reproduce stored int8 bytes."""
        assert INT8_QMAX == 127.0
        rng = np.random.default_rng(3)
        x = rng.normal(size=(8, 16)).astype(np.float32)
        q1, s1 = quantize_int8(jnp.asarray(x), axis=-1)
        dq = dequantize_int8(q1, s1, axis=-1)
        q2, s2 = quantize_int8(dq, axis=-1)
        np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   rtol=1e-6)


# ---------------------------------------------------------------------------
# weight quantization: exactly the projections + lm_head, idempotent
# ---------------------------------------------------------------------------


class TestWeightQuant:
    def test_targets_exactly_projections_and_lm_head(self, params):
        qp = quantize_params(params)
        assert is_quantized(qp) and not is_quantized(params)
        flat = {jax.tree_util.keystr(p): l
                for p, l in jax.tree_util.tree_flatten_with_path(
                    qp, is_leaf=lambda x: isinstance(x, QTensor))[0]}
        quantized = {k for k, v in flat.items()
                     if isinstance(v, QTensor)}
        for mod in ("q_proj", "k_proj", "v_proj", "o_proj",
                    "gate_proj", "up_proj", "down_proj", "lm_head"):
            assert any(mod in k for k in quantized), mod
        # embedding and norm scales stay high-precision
        for k, v in flat.items():
            if "embed" in k or "norm" in k.lower():
                assert not isinstance(v, QTensor), k
        # per-output-channel layout: [in, out] kernels reduce axis 0,
        # the [vocab, h] lm_head reduces axis 1
        for k, v in flat.items():
            if not isinstance(v, QTensor):
                continue
            assert v.q.dtype == jnp.int8 and v.scale.dtype == jnp.float32
            if "lm_head" in k:
                assert v.axis == 1 and v.scale.shape == (v.q.shape[0],)
            else:
                assert v.axis == 0 and v.scale.shape == (v.q.shape[1],)

    def test_idempotent_and_dequant_bounded(self, params):
        qp = quantize_params(params)
        again = quantize_params(qp)
        assert jax.tree.all(jax.tree.map(
            lambda a, b: a is b or bool(jnp.array_equal(a, b)),
            qp, again))
        # dequant restores shape/dtype with per-channel-bounded error
        dq = dequant_params(qp)
        for p, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
            ks = jax.tree_util.keystr(p)
            got = dq
            for part in p:
                got = got[part.key if hasattr(part, "key") else
                          part.name if hasattr(part, "name") else part]
            assert got.shape == leaf.shape and got.dtype == leaf.dtype, ks
        assert param_bytes(qp) < param_bytes(params)


# ---------------------------------------------------------------------------
# default-off identity: quant=None IS the fp engine
# ---------------------------------------------------------------------------


def test_default_off_is_byte_identical_fp_engine(model, params):
    agree0 = obs_bridge.SERVING_QUANT_AGREEMENT.value()
    err0 = obs_bridge.SERVING_QUANT_LOGIT_ERROR.count()
    eng = sv.DecodeEngine(model, params, slots=2, max_len=MAX,
                          prefill_len=16)
    assert eng.quant is None
    assert not is_quantized(eng.params)
    assert not isinstance(eng.cache, (QuantKVCache, QuantPagedKVCache))
    with _EventTap() as tap:
        _greedy(eng, _prompt(), steps=6)
    assert tap.of("serving_quant_enabled") == []
    assert tap.of("serving_quant_eval") == []
    assert obs_bridge.SERVING_QUANT_AGREEMENT.value() == agree0
    assert obs_bridge.SERVING_QUANT_LOGIT_ERROR.count() == err0


def test_config_validation(model, params):
    with pytest.raises(ValueError, match="every lever off"):
        QuantConfig(weights=False, kv=False, allreduce=False)
    with pytest.raises(ValueError, match="tp"):
        sv.DecodeEngine(model, params, slots=1, max_len=32,
                        prefill_len=8,
                        quant=QuantConfig(allreduce=True))
    with pytest.raises(ValueError, match="cache_dtype"):
        sv.DecodeEngine(model, params, slots=1, max_len=32,
                        prefill_len=8, cache_dtype=jnp.bfloat16,
                        quant=QuantConfig(weights=False, kv=True))


# ---------------------------------------------------------------------------
# THE acceptance run: agreement-tier greedy streams, bounded drift,
# unchanged compile discipline
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fp_ref(model, params):
    """One warm fp32 reference engine + its pinned greedy stream,
    shared by every agreement-tier comparison (a fresh DecodeEngine
    recompiles its whole program family — don't pay that per test)."""
    eng = sv.DecodeEngine(model, params, slots=2, max_len=MAX,
                          prefill_len=16)
    s_ref, l_ref = _greedy(eng, _prompt(), steps=24)
    eng.reset()
    return eng, s_ref, l_ref


@pytest.mark.parametrize("quant", [
    QuantConfig(weights=True, kv=False),
    QuantConfig(weights=False, kv=True),
    W_KV,
], ids=["weights", "kv", "weights+kv"])
def test_quant_greedy_agreement_and_compiles(model, params, fp_ref,
                                             quant):
    ref, s_ref, l_ref = fp_ref
    with _EventTap() as tap:
        eng = sv.DecodeEngine(model, params, slots=2, max_len=MAX,
                              prefill_len=16, quant=quant)
    (enabled,) = tap.of("serving_quant_enabled")
    assert enabled["weights"] == quant.weights
    assert enabled["kv"] == quant.kv
    assert eng.quant == quant
    assert is_quantized(eng.params) == quant.weights
    assert isinstance(eng.cache, QuantKVCache) == quant.kv
    s_q, l_q = _teacher_forced(eng, _prompt(), s_ref)
    # the acceptance bars: high greedy agreement, bounded logit drift
    assert stream_agreement(s_ref, s_q) >= 0.9
    assert max_logit_error(l_ref, l_q) < 0.5
    # dequant rides INSIDE the existing program families
    assert eng.decode_compiles() == 1
    assert eng.prefill_compiles() == ref.prefill_compiles()


def test_kv_int8_capacity_bar(model, params, fp_ref):
    """The streams-per-GB claim: fp bytes / quant bytes per cached
    token >= 1.8x (payload 2·hd·4 vs 2·hd + 2·4 per (pos, head))."""
    fp = fp_ref[0]
    q = sv.DecodeEngine(model, params, slots=2, max_len=MAX,
                        prefill_len=16,
                        quant=QuantConfig(weights=False, kv=True))
    assert q.cache.k.dtype == jnp.int8
    assert q.cache.k_scale.dtype == jnp.float32
    ratio = kv_bytes_per_token(fp.cache) / kv_bytes_per_token(q.cache)
    assert ratio >= 1.8
    # hd=16 here: exact ratio is (2*16*4) / (2*16 + 2*4) = 3.2
    assert ratio == pytest.approx(3.2)


# ---------------------------------------------------------------------------
# within-quant structural bit-exactness: chunk splits, preemption,
# prefix caching, speculation, paged/CoW
# ---------------------------------------------------------------------------


def test_chunked_prefill_invisible_under_quant(model, params, same_logits):
    """Chunk boundaries are scheduling, not numerics, under KV-int8
    too: per-(position, head) scales depend only on the row being
    written, never on which chunk wrote it.  The stream is the same
    stream; the logits agree to ``conftest.LOGITS_ATOL`` (a 16-row and a
    64-row chunk are two programs, and the gemm rounds by rows)."""
    prompt = _prompt(seed=3, n=40)
    small = sv.DecodeEngine(model, params, slots=1, max_len=MAX,
                            prefill_len=16, quant=W_KV)
    big = sv.DecodeEngine(model, params, slots=1, max_len=MAX,
                          prefill_len=64, quant=W_KV)
    s_small, l_small = _greedy(small, prompt, steps=8)
    s_big, l_big = _greedy(big, prompt, steps=8)
    assert s_small == s_big
    same_logits(l_small, l_big)


def test_preempt_capture_restore_bit_exact_under_quant(model, params):
    """Lossless preemption composes with KV-int8: capture hands out
    dequantized fp32 rows, restore requantizes in-program, and because
    the group amax requantizes to exactly ±127 the stored int8 payload
    reproduces bit-for-bit.  The regrouped *scale* can move by one ulp
    (``amax/127 * 127 / 127`` is not an fp32 identity), so resumed
    logits carry ~1e-7 float noise — the greedy stream must still be
    identical, and the logits equal to fp tolerance."""
    prompt = _prompt(seed=4)
    ref = sv.DecodeEngine(model, params, slots=2, max_len=MAX,
                          prefill_len=16, quant=W_KV)
    s_ref, l_ref = _greedy(ref, prompt, steps=12)

    eng = sv.DecodeEngine(model, params, slots=2, max_len=MAX,
                          prefill_len=16, quant=W_KV)
    s_pre, _ = _greedy(eng, prompt, steps=6)
    assert s_pre == s_ref[:7]
    k, v, length = eng.capture_slot(0)
    # capture is quantization-oblivious: fp32 host bytes
    assert k.dtype == np.float32 and v.dtype == np.float32
    assert length == len(prompt) + 6
    eng.release(0)
    eng.restore_prefix(1, (k, v), length)
    toks = np.zeros((2,), np.int32)
    act = np.array([False, True])
    stream = list(s_pre)
    per_pos = []
    for _ in range(6):
        toks[1] = stream[-1]
        lg = np.asarray(eng.decode(toks, act)[1])
        per_pos.append(lg)
        stream.append(int(lg.argmax()))
    assert stream == s_ref
    np.testing.assert_allclose(np.stack(per_pos), l_ref[6:],
                               rtol=1e-5, atol=1e-5)


def test_prefix_cache_hit_bit_exact_under_quant(model, params):
    """A prefix-cache hit on a KV-int8 engine restores the dequantized
    span and requantizes to the same stored bytes: warm admission's
    stream is bit-identical to the cold one."""
    eng = sv.DecodeEngine(model, params, slots=2, max_len=MAX,
                          prefill_len=16, quant=W_KV)
    sched = sv.ContinuousBatchingScheduler(
        eng, log_interval=10 ** 9,
        prefix_caching=sv.PrefixCacheConfig())
    prompt = _prompt(seed=5, n=34)
    with _EventTap() as tap:
        sched.submit(sv.Request("cold", prompt, max_new_tokens=6))
        sched.run()
        sched.submit(sv.Request("warm", prompt, max_new_tokens=6))
        sched.run()
    assert len(tap.of("serving_prefix_hit")) == 1
    assert (sched.results["warm"].tokens
            == sched.results["cold"].tokens)
    sched.close()


def test_speculation_exact_under_quant(model, params):
    """verify_draft on a quantized engine is still an exact test
    against the engine's OWN plain-decode stream: a correct draft is
    fully accepted, a wrong token rejected at its position, and the
    emitted tokens match plain decode bit-for-bit."""
    prompt = _prompt(seed=6)
    plain = sv.DecodeEngine(model, params, slots=2, max_len=MAX,
                            prefill_len=16, quant=W_KV)
    s_plain, _ = _greedy(plain, prompt, steps=6)

    eng = sv.DecodeEngine(model, params, slots=2, max_len=MAX,
                          prefill_len=16, quant=W_KV)
    logits = eng.prefill(0, prompt)
    pending = int(jnp.argmax(logits))
    assert pending == s_plain[0]
    # correct draft: the plain continuation — fully accepted
    draft = s_plain[1:4]
    accepted, greedy, _ = eng.verify_draft(0, [pending] + draft)
    assert accepted == len(draft)
    emitted = draft[:accepted] + [int(greedy[accepted])]
    assert emitted == s_plain[1:5]
    # wrong continuation: rejected at its position, bonus row still
    # equals the plain stream's token there
    bad = [s_plain[5], (s_plain[6] + 1) % CFG.vocab_size]
    accepted2, greedy2, _ = eng.verify_draft(
        0, [s_plain[4]] + bad)
    assert accepted2 == 1
    assert int(greedy2[accepted2]) == s_plain[6]
    assert eng.verify_compiles() >= 1
    assert eng.decode_compiles() == 0


def test_paged_quant_identical_to_dense_quant(model, params):
    """Same writes routed through the block pool: the paged KV-int8
    stream is bit-identical to the dense KV-int8 stream (pool + scale
    pool gathers reproduce the dense rows exactly)."""
    dense = sv.DecodeEngine(model, params, slots=2, max_len=MAX,
                            prefill_len=16, quant=W_KV)
    paged = sv.DecodeEngine(model, params, slots=2, max_len=MAX,
                            prefill_len=16, quant=W_KV,
                            paged=PagedCacheConfig(block_size=8))
    assert isinstance(paged.cache, QuantPagedKVCache)
    s_dense, l_dense = _greedy(dense, _prompt(seed=7), steps=10)
    s_paged, l_paged = _greedy(paged, _prompt(seed=7), steps=10)
    assert s_paged == s_dense
    np.testing.assert_array_equal(l_paged, l_dense)
    # scale pools ride the same block accounting: per-block bytes
    # count payload + scales (the scheduler's admission pricing)
    assert bytes_per_block(paged.cache) > bytes_per_block(
        dense_like_block(paged.cache))


def dense_like_block(cache):
    """A payload-only view for the bytes_per_block comparison: the
    quant pool must price strictly MORE than its payload alone."""
    import types

    return types.SimpleNamespace(k=cache.k, v=cache.v, stored=("k", "v"))


def test_paged_cow_fork_isolated_under_quant(model, params):
    """fork_slot + divergent decode under KV-int8: copy-on-write moves
    payload AND scales together (same block ids index both pools), so
    the parent stream is bit-unchanged by the child's writes."""
    prompt = _prompt(seed=8)
    ref = sv.DecodeEngine(model, params, slots=2, max_len=MAX,
                          prefill_len=16, quant=W_KV,
                          paged=PagedCacheConfig(block_size=8))
    s_ref, l_ref = _greedy(ref, prompt, steps=8)

    eng = sv.DecodeEngine(model, params, slots=2, max_len=MAX,
                          prefill_len=16, quant=W_KV,
                          paged=PagedCacheConfig(block_size=8))
    s_pre, _ = _greedy(eng, prompt, steps=4)
    eng.fork_slot(0, 1)
    toks = np.zeros((2,), np.int32)
    act = np.array([True, True])
    stream = list(s_pre)
    per_pos = []
    for i in range(4):
        toks[0] = stream[-1]
        # the fork decodes a DIFFERENT token every step — its CoW
        # copies must never leak into the parent's blocks
        toks[1] = (stream[-1] + 1 + i) % CFG.vocab_size
        lg = np.asarray(eng.decode(toks, act))
        per_pos.append(lg[0])
        stream.append(int(lg[0].argmax()))
    assert stream == s_ref
    np.testing.assert_array_equal(np.stack(per_pos), l_ref[4:])


# ---------------------------------------------------------------------------
# tensor parallel: quant-aware shardings + quantized allreduce
# ---------------------------------------------------------------------------


def test_tp2_quant_stream_matches_single_chip(model, params):
    single = sv.DecodeEngine(model, params, slots=2, max_len=MAX,
                             prefill_len=16, quant=W_KV)
    tp2 = sv.DecodeEngine(model, params, slots=2, max_len=MAX,
                          prefill_len=16, quant=W_KV,
                          tp=TPConfig(size=2))
    s1, l1 = _greedy(single, _prompt(), steps=12)
    s2, l2 = _greedy(tp2, _prompt(), steps=12)
    assert s1 == s2
    np.testing.assert_allclose(l2, l1, rtol=1e-4, atol=1e-4)
    assert tp2.decode_compiles() == 1


def test_tp2_quantized_allreduce_agreement(model, params, fp_ref):
    """The int8 psum pair is the one knowingly lossy-per-step leg:
    agreement-tier against the exact-collective fp32 engine, same
    compile discipline, scoped to the row-linear reduces only."""
    ref, s_ref, l_ref = fp_ref
    eng = sv.DecodeEngine(
        model, params, slots=2, max_len=MAX, prefill_len=16,
        tp=TPConfig(size=2),
        quant=QuantConfig(weights=False, kv=False, allreduce=True))
    s_q, l_q = _teacher_forced(eng, _prompt(), s_ref)
    assert stream_agreement(s_ref, s_q) >= 0.8
    assert max_logit_error(l_ref, l_q) < 1.0
    assert eng.decode_compiles() == 1
    assert eng.prefill_compiles() == ref.prefill_compiles()


def test_quant_param_specs_follow_replaced_kernels(params):
    """A QTensor's .q shards exactly like the kernel it replaced; its
    per-output-channel .scale shards with the OUTPUT dim — split for
    column kernels + lm_head, replicated for row kernels; non-QTensor
    leaves (norm ['scale'] dict keys included) delegate untouched."""
    from jax.sharding import PartitionSpec as P

    from apex_tpu.models.llama import tp_param_spec

    qp = quantize_params(params)
    leaves = jax.tree_util.tree_flatten_with_path(qp)[0]
    seen = {"col_scale": 0, "row_scale": 0, "plain": 0}
    for path, _ in leaves:
        ks = jax.tree_util.keystr(path)
        spec = serving_param_spec(ks, "tp")
        if ks.endswith(".q"):
            assert spec == tp_param_spec(ks[:-2], "tp"), ks
        elif ks.endswith(".scale"):
            if "o_proj" in ks or "down_proj" in ks:
                assert spec == P(), ks
                seen["row_scale"] += 1
            else:
                assert spec == P("tp"), ks
                seen["col_scale"] += 1
        else:
            assert spec == tp_param_spec(ks, "tp"), ks
            seen["plain"] += 1
    assert all(seen.values())


def test_pre_quantized_params_accepted_by_tp_engine(model, params):
    """quantize_params ahead of construction (the load-time path):
    the engine detects the QTensor tree, skips its own requantization,
    and tp_param_shardings lays the quant leaves out mesh-correctly."""
    qp = quantize_params(params)
    eng = sv.DecodeEngine(model, qp, slots=2, max_len=MAX,
                          prefill_len=16, quant=W_KV,
                          tp=TPConfig(size=2))
    shardings = tp_param_shardings(qp, eng.mesh)
    assert (jax.tree.structure(shardings, is_leaf=lambda x: x is None)
            == jax.tree.structure(qp))
    ref = sv.DecodeEngine(model, params, slots=2, max_len=MAX,
                          prefill_len=16, quant=W_KV)
    s_ref, _ = _greedy(ref, _prompt(seed=9), steps=8)
    s_tp, _ = _greedy(eng, _prompt(seed=9), steps=8)
    assert s_tp == s_ref


# ---------------------------------------------------------------------------
# load-time quantization + hot-swap requantization
# ---------------------------------------------------------------------------


def test_load_serving_params_quantize(tmp_path, model, params):
    from apex_tpu.resilience.checkpoint import save_checkpoint

    root = str(tmp_path / "ckpt")
    save_checkpoint(root, 3, {"params": params})
    loaded, step = sv.load_serving_params(
        root, {"params": params}, params_key="params", quantize=True)
    assert step == 3 and is_quantized(loaded)
    eng = sv.DecodeEngine(model, loaded, slots=2, max_len=MAX,
                          prefill_len=16,
                          quant=QuantConfig(weights=True, kv=False))
    ref = sv.DecodeEngine(model, params, slots=2, max_len=MAX,
                          prefill_len=16,
                          quant=QuantConfig(weights=True, kv=False))
    s_loaded, _ = _greedy(eng, _prompt(), steps=8)
    s_boot, _ = _greedy(ref, _prompt(), steps=8)
    # load-time and boot-time quantization are the same function on
    # the same bytes: identical streams
    assert s_loaded == s_boot


def test_swap_params_requantizes(model, params):
    eng = sv.DecodeEngine(model, params, slots=2, max_len=MAX,
                          prefill_len=16, quant=W_KV)
    s_before, _ = _greedy(eng, _prompt(), steps=6)
    eng.reset()
    eng.swap_params(params)          # fp candidate: requantized on swap
    assert is_quantized(eng.params)
    s_after, _ = _greedy(eng, _prompt(), steps=6)
    assert s_after == s_before
    assert eng.decode_compiles() == 1


# ---------------------------------------------------------------------------
# acceptance accounting + the metrics bridge
# ---------------------------------------------------------------------------


def test_evaluate_quant_feeds_bridge_metrics():
    err0 = obs_bridge.SERVING_QUANT_LOGIT_ERROR.count()
    with _EventTap() as tap:
        report = evaluate_quant(
            [1, 2, 3, 4], [1, 2, 9, 4],
            ref_logits=np.zeros((2, 4), np.float32),
            quant_logits=np.full((2, 4), 0.25, np.float32),
            bytes_per_token=160.0, fp_bytes_per_token=512.0)
    assert report["agreement"] == pytest.approx(0.75)
    assert report["tokens"] == 4
    assert report["max_logit_error"] == pytest.approx(0.25)
    assert report["capacity_ratio"] == pytest.approx(3.2)
    (ev,) = tap.of("serving_quant_eval")
    assert ev["agreement"] == pytest.approx(0.75)
    assert obs_bridge.SERVING_QUANT_AGREEMENT.value() == pytest.approx(
        0.75)
    assert obs_bridge.SERVING_QUANT_BYTES_PER_TOKEN.value() == 160.0
    assert obs_bridge.SERVING_QUANT_LOGIT_ERROR.count() == err0 + 1


def test_stream_helpers():
    assert stream_agreement([], []) == 1.0
    assert stream_agreement([1, 2], [1, 2, 3]) == 1.0
    assert stream_agreement([1, 2, 3], [1, 0, 3]) == pytest.approx(2 / 3)
    assert max_logit_error(np.zeros((0, 4)), np.zeros((0, 4))) == 0.0
