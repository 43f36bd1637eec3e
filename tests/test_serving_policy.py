"""Serving control plane (ISSUE 13): lossless priority preemption,
cancellation, deadline shedding, tenant fairness — and chaos.

THE acceptance run: a 2x-overload bursty open-loop workload with mixed
priorities, deadlines, and injected slow decode steps, driven on a
virtual clock.  Every surviving stream's tokens are bit-identical to an
unperturbed isolated run, preempted streams resume losslessly (the
engine-level twin pins exact f32 logits across the preempt/resume
boundary), and the policy run's high-priority p99 TTFT and goodput are
strictly better than the FIFO scheduler on the *same* workload with
the *same* chaos.

Default-off identity: a scheduler without ``policy=`` run over
policy-annotated requests produces the event stream and serving-metric
snapshot of a plain FIFO run, exactly.  No new compiled programs on
the policy path: preempt/resume rides the existing region-read /
restore / alias program families (compile counts asserted).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import _logging, obs
from apex_tpu import serving as sv
from apex_tpu.models import LlamaConfig, LlamaForCausalLM
from apex_tpu.obs import request_trace as rt
from apex_tpu.obs import slo as oslo
from apex_tpu.resilience.fault_injection import (
    CancelStorm,
    SlowDecodeStep,
    StallStream,
)

CFG = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, max_position_embeddings=256)
MAX = 96


@pytest.fixture(scope="module")
def model():
    return LlamaForCausalLM(CFG)


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


@pytest.fixture(scope="module")
def _engine_mod(model, params):
    return sv.DecodeEngine(model, params, slots=2, max_len=MAX,
                           prefill_len=32)


@pytest.fixture
def engine(_engine_mod):
    """Shared 2-slot dense engine, reset per test — fresh engines are
    reserved for tests that assert per-engine compile counts (every
    jit family recompiles per engine, ~seconds each on CPU)."""
    _engine_mod.reset()
    return _engine_mod


@pytest.fixture(scope="module")
def _eng1_mod(model, params):
    return sv.DecodeEngine(model, params, slots=1, max_len=MAX,
                           prefill_len=32)


@pytest.fixture
def eng1(_eng1_mod):
    """Shared single-slot dense engine, reset per test."""
    _eng1_mod.reset()
    return _eng1_mod


def _prompt(seed, n=8):
    return [int(x)
            for x in np.random.default_rng(seed).integers(0, 128, n)]


def _mk_engine(model, params, *, slots=2, paged=False, num_blocks=None):
    return sv.DecodeEngine(
        model, params, slots=slots, max_len=MAX, prefill_len=32,
        paged=(sv.PagedCacheConfig(block_size=16, num_blocks=num_blocks)
               if paged else None))


@pytest.fixture(scope="module")
def isolated_tokens(_eng1_mod):
    """``fn(request) -> tokens``: the request's stream run alone on a
    FIFO scheduler — the unperturbed reference every chaos survivor
    must match bit for bit.  The shared single-slot engine (compiled
    once) + a generation-config memo keep the many reference runs
    cheap."""
    eng = _eng1_mod
    memo = {}

    def run(request):
        key = (tuple(request.prompt), request.max_new_tokens,
               request.eos_id, request.temperature, request.top_k,
               request.seed)
        if key not in memo:
            eng.reset()
            sched = sv.ContinuousBatchingScheduler(eng, max_queue=4)
            sched.submit(sv.Request("ref", request.prompt,
                                    max_new_tokens=request.max_new_tokens,
                                    eos_id=request.eos_id,
                                    temperature=request.temperature,
                                    top_k=request.top_k,
                                    seed=request.seed))
            memo[key] = sched.run()["ref"].tokens
        return memo[key]

    return run


# ---------------------------------------------------------------------------
# policy units
# ---------------------------------------------------------------------------


class TestPolicyUnits:
    def test_policy_validation(self):
        with pytest.raises(ValueError, match="weights must be > 0"):
            sv.SchedulingPolicy(tenant_weights={"a": 0.0})
        with pytest.raises(ValueError, match="default_tenant_weight"):
            sv.SchedulingPolicy(default_tenant_weight=-1.0)
        with pytest.raises(ValueError, match="max_inflight_per_tenant"):
            sv.SchedulingPolicy(max_inflight_per_tenant=0)
        pol = sv.SchedulingPolicy(tenant_weights={"paid": 3.0})
        assert pol.weight_of("paid") == 3.0
        assert pol.weight_of("anyone_else") == 1.0

    def test_wrr_smooth_proportions_and_determinism(self):
        pol = sv.SchedulingPolicy(tenant_weights={"a": 3.0, "b": 1.0})

        def picks(n):
            wrr = sv.WeightedRoundRobin(pol)
            return [wrr.pick(["a", "b"]) for _ in range(n)]

        seq = picks(8)
        assert seq == picks(8)                     # deterministic
        assert seq.count("a") == 6 and seq.count("b") == 2   # 3:1
        # smooth: "b" is interleaved, not parked at the tail
        assert "b" in seq[:4] and "b" in seq[4:]

    def test_wrr_snapshot_restore_and_starvation_credit(self):
        pol = sv.SchedulingPolicy()
        wrr = sv.WeightedRoundRobin(pol)
        assert wrr.pick([]) is None
        snap = wrr.snapshot()
        first = wrr.pick(["a", "b"])
        wrr.restore(snap)
        assert wrr.pick(["a", "b"]) == first       # rollback is exact
        # a tenant kept ineligible accrues credit and wins on re-entry
        for _ in range(3):
            wrr.pick(["a"])
        wrr._credit["b"] = 5.0                     # earned while waiting
        assert wrr.pick(["a", "b"]) == "b"

    def test_request_control_fields_validated_at_submit(self, engine):
        sched = sv.ContinuousBatchingScheduler(engine, max_queue=4)
        with pytest.raises(ValueError, match="deadline_s"):
            sched.submit(sv.Request("d", [1, 2], max_new_tokens=1,
                                    deadline_s=0.0))
        with pytest.raises(ValueError, match="tenant"):
            sched.submit(sv.Request("t", [1, 2], max_new_tokens=1,
                                    tenant=""))


# ---------------------------------------------------------------------------
# lossless capture/restore: exact f32 logits across the boundary
# ---------------------------------------------------------------------------


class TestEngineCapture:
    def test_capture_restore_exact_logits_across_boundary(self, model,
                                                          params):
        """Prefill + 3 decodes, capture, release, restore into a
        DIFFERENT slot, 3 more decodes: every post-boundary f32 logits
        row equals the uninterrupted run bit for bit — the
        lossless-preemption exactness witness."""
        prompt = _prompt(5, 20)
        eng = _mk_engine(model, params, slots=2)

        def drive(interrupt):
            eng.reset()
            logits = eng.prefill(0, prompt)
            toks = [int(np.argmax(np.asarray(logits)))]
            rows = []
            slot = 0
            for i in range(6):
                if interrupt and i == 3:
                    k, v, n = eng.capture_slot(slot)
                    assert n == len(prompt) + len(toks) - 1
                    eng.release(slot)
                    slot = 1
                    eng.restore_prefix(slot, (k, v), n)
                tok = np.zeros((2,), np.int32)
                act = np.zeros((2,), bool)
                tok[slot] = toks[-1]
                act[slot] = True
                lg = np.asarray(eng.decode(tok, act)[slot])
                rows.append(lg)
                toks.append(int(np.argmax(lg)))
            return toks, rows

        ref_toks, ref_rows = drive(interrupt=False)
        got_toks, got_rows = drive(interrupt=True)
        assert got_toks == ref_toks
        for a, b in zip(ref_rows, got_rows):
            assert (a == b).all()          # exact f32, not allclose

    def test_capture_guards_and_compile_bound(self, model, params):
        eng = _mk_engine(model, params, slots=2)
        with pytest.raises(ValueError, match="empty"):
            eng.capture_slot(0)
        assert eng.capture_compiles() == 0     # nothing read yet
        # every capture length decomposes over the bucket table: the
        # read program family stays bounded by len(buckets) plus
        # sub-floor whole-slot extents
        # sub-floor whole slot (3), exact bucket (16), sub-floor tail
        # (20 = 16 + overlap), multi-bucket with tail (50 = 32+16+ovl)
        for n in (3, 16, 20, 50):
            eng.reset()
            eng.prefill(0, _prompt(n, n))
            for _ in range(3):
                eng.decode(np.array([0, 0], np.int32),
                           np.array([True, False]))
            k, v, length = eng.capture_slot(0)
            assert length == n + 3
            assert k.shape[1] == length == v.shape[1]
        bound = len(eng.prefill_buckets) + eng.prefill_buckets[0] - 1
        assert 1 <= eng.capture_compiles() <= bound
        paged = _mk_engine(model, params, paged=True)
        with pytest.raises(ValueError, match="by reference"):
            paged.capture_slot(0)


# ---------------------------------------------------------------------------
# preemption end-to-end
# ---------------------------------------------------------------------------


class TestLosslessPreemption:
    @pytest.mark.parametrize("paged", [False, True],
                             ids=["dense", "paged"])
    def test_preempt_resume_stream_identical(self, model, params, paged,
                                             isolated_tokens):
        """A high-priority arrival evicts the lone low-priority DECODE
        stream mid-flight; both finish with token streams bit-identical
        to isolated runs, the victim's result says so
        (``preempted-resumed``, ``preemptions == 1``), and the paged
        path moves zero K/V bytes (no restore program ever compiles)."""
        eng = _mk_engine(model, params, slots=1, paged=paged)
        sched = sv.ContinuousBatchingScheduler(
            eng, max_queue=8, policy=sv.SchedulingPolicy())
        lo = sv.Request("lo", _prompt(1), max_new_tokens=10, priority=0)
        hi = sv.Request("hi", _prompt(2), max_new_tokens=4, priority=5)
        seen = []
        _logging.add_event_sink(seen.append)
        try:
            sched.submit(lo)
            for _ in range(3):
                sched.step()
            assert sched.phase_of("lo").value == "decode"
            sched.submit(hi)
            results = sched.run()
        finally:
            _logging.remove_event_sink(seen.append)
        assert results["hi"].finish_reason == "length"
        assert results["lo"].finish_reason == "preempted-resumed"
        assert results["lo"].preemptions == 1
        assert results["lo"].tokens == isolated_tokens(lo)
        assert results["hi"].tokens == isolated_tokens(hi)
        kinds = [e["event"] for e in seen]
        assert kinds.count("serving_request_preempted") == 1
        assert kinds.count("serving_request_resumed") == 1
        pre = next(e for e in seen
                   if e["event"] == "serving_request_preempted")
        res = next(e for e in seen
                   if e["event"] == "serving_request_resumed")
        assert pre["rid"] == res["rid"] == "lo"
        assert pre["cached_tokens"] == res["cached_tokens"] > 0
        assert sched.control_stats == {"preempted": 1, "resumed": 1,
                                       "cancelled": 0, "shed": 0}
        # no new compiled programs on the policy path
        assert eng.decode_compiles() == 1
        assert eng.prefill_compiles() <= len(eng.prefill_buckets)
        if paged:
            # zero-copy: capture is block references, resume is table
            # aliasing — neither the read nor the restore family exists
            assert eng.capture_compiles() == 0
            assert eng.restore_compiles() == 0
            assert eng.block_pool.cow_total == 0
            # the suspension hold was dropped: pool fully drained
            assert eng.block_pool.used_blocks == 0
        else:
            assert eng.restore_compiles() <= len(eng.prefill_buckets)

    def test_loadgen_drains_suspended_streams(self, eng1):
        """Review regression: the preemptor can finish while the queue
        is empty — the load generator must keep stepping until the
        suspended victim resumes and finishes, not exit with the
        stream orphaned (no result, close() refusing)."""
        sched = sv.ContinuousBatchingScheduler(
            eng1, max_queue=8, policy=sv.SchedulingPolicy(),
            clock=sv.VirtualClock())
        wl = sv.OpenLoopWorkload(
            requests=(sv.Request("lo", _prompt(1), max_new_tokens=12,
                                 priority=0),
                      sv.Request("hi", _prompt(2), max_new_tokens=2,
                                 priority=5)),
            arrivals=(0.0, 1.0), deadlines=(None, None))
        out = sv.LoadGenerator(sched, wl, step_time_s=0.25).run()
        assert sched.control_stats["preempted"] == 1
        assert sched.suspended_count == 0          # fully drained
        assert out.results["lo"].finish_reason == "preempted-resumed"
        assert out.results["hi"].finish_reason == "length"
        assert out.completed == 2

    def test_paged_no_preempt_for_infeasible_admission(self, model,
                                                       params):
        """Review regression: when the pool cannot cover the
        high-priority admission while the victim lives, the victim
        must NOT be evicted — its suspension hold would keep its own
        blocks unavailable and livelock a tight pool.  The admission
        waits instead; the victim finishes, frees its blocks, and the
        high-priority request serves."""
        eng = _mk_engine(model, params, slots=1, paged=True,
                         num_blocks=4)            # 3 allocatable
        sched = sv.ContinuousBatchingScheduler(
            eng, max_queue=8, policy=sv.SchedulingPolicy(),
            clock=sv.VirtualClock())
        # lo worst-case 17 rows = 2 blocks; hi 31 rows = 2 blocks —
        # infeasible while lo is live, trivially feasible after
        sched.submit(sv.Request("lo", _prompt(1), max_new_tokens=10))
        for _ in range(3):
            sched.step()
        sched.submit(sv.Request("hi", _prompt(2), max_new_tokens=24,
                                priority=5))
        results = sched.run()                     # no SchedulerStalled
        assert sched.control_stats["preempted"] == 0
        assert results["lo"].finish_reason == "length"
        assert results["hi"].finish_reason == "length"

    def test_equal_priority_never_preempts(self, eng1):
        sched = sv.ContinuousBatchingScheduler(
            eng1, max_queue=8, policy=sv.SchedulingPolicy())
        sched.submit(sv.Request("a", _prompt(1), max_new_tokens=6,
                                priority=3))
        for _ in range(3):
            sched.step()
        sched.submit(sv.Request("b", _prompt(2), max_new_tokens=3,
                                priority=3))
        results = sched.run()
        assert sched.control_stats["preempted"] == 0
        # FIFO within the class: "a" ran to completion first
        assert results["a"].finish_reason == "length"

    def test_neighbor_stream_untouched_by_preemption(self, engine,
                                                     isolated_tokens):
        """Slot 0's stream decodes straight through while slot 1's
        neighbor is preempted and resumed — bit-identical to its
        isolated run (preemption must not disturb neighbors)."""
        sched = sv.ContinuousBatchingScheduler(
            engine, max_queue=8, policy=sv.SchedulingPolicy())
        keep = sv.Request("keep", _prompt(11), max_new_tokens=12,
                          priority=1)
        lo = sv.Request("lo", _prompt(12), max_new_tokens=12, priority=0)
        hi = sv.Request("hi", _prompt(13), max_new_tokens=3, priority=5)
        sched.submit(keep)
        sched.submit(lo)
        for _ in range(3):
            sched.step()
        sched.submit(hi)           # evicts "lo" (lowest priority)
        results = sched.run()
        assert sched.control_stats["preempted"] == 1
        assert results["lo"].preemptions == 1
        for req in (keep, lo, hi):
            assert (results[req.rid].tokens
                    == isolated_tokens(req)), req.rid
        assert results["keep"].finish_reason == "length"   # never moved


# ---------------------------------------------------------------------------
# cancellation
# ---------------------------------------------------------------------------


class TestCancel:
    def test_cancel_every_phase_and_unknown(self, eng1):
        sched = sv.ContinuousBatchingScheduler(eng1, max_queue=8)
        a = sv.Request("a", _prompt(1), max_new_tokens=8)
        b = sv.Request("b", _prompt(2), max_new_tokens=4)
        sched.submit(a)
        sched.submit(b)
        sched.step()                       # a active, b queued
        assert sched.cancel("b") is True   # queued cancel
        for _ in range(2):
            sched.step()
        assert sched.cancel("a") is True   # decode cancel, slot freed
        assert eng1.free_slots() == [0]
        results = sched.run()
        assert results["a"].finish_reason == "cancelled"
        assert 0 < len(results["a"].tokens) < 8   # partial output kept
        assert results["b"].finish_reason == "cancelled"
        assert results["b"].tokens == []
        assert np.isnan(results["b"].ttft_s)      # no first token
        assert sched.cancel("a") is False         # already terminal
        with pytest.raises(KeyError, match="unknown rid"):
            sched.cancel("never-submitted")
        assert sched.control_stats["cancelled"] == 2

    def test_cancel_suspended_releases_paged_hold(self, model, params):
        eng = _mk_engine(model, params, slots=1, paged=True)
        sched = sv.ContinuousBatchingScheduler(
            eng, max_queue=8, policy=sv.SchedulingPolicy())
        sched.submit(sv.Request("lo", _prompt(1), max_new_tokens=10))
        for _ in range(3):
            sched.step()
        sched.submit(sv.Request("hi", _prompt(2), max_new_tokens=4,
                                priority=5))
        sched.step()                       # preempts "lo"
        assert sched.suspended_count == 1
        held = eng.block_pool.used_blocks
        assert held > 0
        assert sched.cancel("lo") is True
        results = sched.run()
        assert results["lo"].finish_reason == "cancelled"
        assert results["hi"].finish_reason == "length"
        assert eng.block_pool.used_blocks == 0    # hold released

    def test_cancel_mid_prefill_releases_prefix_pins(self, eng1):
        """Pin-leak regression: a cancelled mid-PREFILL stream was
        pinning the chain it extended — cancel must release every pin
        or those entries can never be evicted."""
        sched = sv.ContinuousBatchingScheduler(
            eng1, max_queue=4,
            prefill_budget=16,
            prefix_caching=sv.PrefixCacheConfig(max_tokens=64))
        long_prompt = _prompt(3, 48)       # 3 budget-16 steps to cache
        sched.submit(sv.Request("long", long_prompt, max_new_tokens=2))
        sched.step()                       # one chunk cached + offered
        assert sched.phase_of("long").value == "prefill"
        pc = sched.prefix_cache
        assert [e for e in pc._entries.values() if e.refs], \
            "test premise: the mid-prefill stream holds pins"
        assert sched.cancel("long") is True
        assert not [e for e in pc._entries.values() if e.refs], \
            "cancel leaked prefix-cache pins"
        sched.run()
        sched.close()

    def test_cancel_neighbor_isolation(self, engine, isolated_tokens):
        sched = sv.ContinuousBatchingScheduler(engine, max_queue=8)
        keep = sv.Request("keep", _prompt(21), max_new_tokens=8)
        gone = sv.Request("gone", _prompt(22), max_new_tokens=8)
        sched.submit(keep)
        sched.submit(gone)
        for _ in range(3):
            sched.step()
        sched.cancel("gone")
        results = sched.run()
        assert (results["keep"].tokens
                == isolated_tokens(keep))


# ---------------------------------------------------------------------------
# deadline shedding
# ---------------------------------------------------------------------------


class TestDeadlineShedding:
    def test_shed_at_admission_and_mid_queue(self, eng1):
        """Both shapes: a request whose deadline passed before it was
        ever considered (admission-time) and one that expires while
        waiting behind a long stream (mid-queue) are shed without
        spending prefill budget; a deadline-free neighbor is not."""
        clk = sv.VirtualClock()
        sched = sv.ContinuousBatchingScheduler(
            eng1, max_queue=8, policy=sv.SchedulingPolicy(), clock=clk)
        seen = []
        _logging.add_event_sink(seen.append)
        try:
            sched.submit(sv.Request("slow", _prompt(1),
                                    max_new_tokens=12))
            sched.step()                   # "slow" owns the only slot
            sched.submit(sv.Request("due", _prompt(2), max_new_tokens=2,
                                    deadline_s=1.0))
            sched.submit(sv.Request("ok", _prompt(3), max_new_tokens=2))
            clk.advance(0.5)
            sched.step()                   # deadline not yet passed
            assert sched.phase_of("due").value == "queued"
            clk.advance(1.0)               # now 1.5s > 1.0s deadline
            results = sched.run()
        finally:
            _logging.remove_event_sink(seen.append)
        assert results["due"].finish_reason == "shed"
        assert results["due"].tokens == []
        assert results["ok"].finish_reason == "length"
        assert results["slow"].finish_reason == "length"
        shed_events = [e for e in seen
                       if e["event"] == "serving_request_shed"]
        assert len(shed_events) == 1
        assert shed_events[0]["rid"] == "due"
        assert shed_events[0]["waited_s"] >= 1.0
        # the shed prompt never reached a prefill chunk
        assert not any(e["event"] == "serving_prefill_chunk"
                       and e["rid"] == "due" for e in seen)
        assert sched.control_stats["shed"] == 1

    def test_loadgen_charges_policy_sheds_to_goodput(self, eng1):
        """A policy-shed request has a result, but goodput counts it
        as a miss — finishing early by giving up is not service."""
        clk = sv.VirtualClock()
        sched = sv.ContinuousBatchingScheduler(
            eng1, max_queue=8, policy=sv.SchedulingPolicy(), clock=clk)
        prompts = [_prompt(i) for i in range(4)]
        wl = sv.make_workload(prompts, sv.uniform_arrivals(4, 100.0),
                              max_new_tokens=8, deadline_s=2.0)
        out = sv.LoadGenerator(sched, wl, step_time_s=0.5).run()
        reasons = {r.rid: r.finish_reason for r in out.results.values()}
        assert "shed" in set(reasons.values())
        served = [rid for rid, why in reasons.items()
                  if why in sv.SERVED_REASONS]
        assert out.completed == len(served) < 4
        for rid, why in reasons.items():
            if why == "shed":
                assert out.met_deadline[rid] is False
        assert out.goodput < 1.0

    def test_shedding_off_keeps_expired_requests(self, eng1):
        clk = sv.VirtualClock()
        sched = sv.ContinuousBatchingScheduler(
            eng1, max_queue=8, clock=clk,
            policy=sv.SchedulingPolicy(deadline_shedding=False))
        sched.submit(sv.Request("x", _prompt(1), max_new_tokens=2,
                                deadline_s=0.5))
        clk.advance(2.0)
        results = sched.run()
        assert results["x"].finish_reason == "length"   # served late
        assert sched.control_stats["shed"] == 0


# ---------------------------------------------------------------------------
# tenant fairness
# ---------------------------------------------------------------------------


class TestTenantFairness:
    def test_inflight_cap_blocks_a_flood(self, engine):
        """Tenant A floods the queue first; with a cap of 1, A never
        holds both slots and B's later arrivals are served alongside —
        admission order interleaves instead of draining A first."""
        sched = sv.ContinuousBatchingScheduler(
            engine, max_queue=16,
            policy=sv.SchedulingPolicy(max_inflight_per_tenant=1))
        for i in range(3):
            sched.submit(sv.Request(f"a{i}", _prompt(i),
                                    max_new_tokens=4, tenant="A"))
        for i in range(2):
            sched.submit(sv.Request(f"b{i}", _prompt(10 + i),
                                    max_new_tokens=4, tenant="B"))
        admitted = []
        seen = []
        _logging.add_event_sink(seen.append)
        try:
            while sched.queue_depth or sched.active_count:
                sched.step()
                counts = {}
                for rid in sched.active_rids:
                    tenant = rid[0].upper()
                    counts[tenant] = counts.get(tenant, 0) + 1
                assert counts.get("A", 0) <= 1     # the cap held
                assert counts.get("B", 0) <= 1
        finally:
            _logging.remove_event_sink(seen.append)
        admitted = [e["rid"] for e in seen
                    if e["event"] == "serving_request_admitted"]
        # B was admitted while A still had queued requests
        assert admitted.index("b0") < admitted.index("a2")

    def test_wrr_interleaves_admissions_by_weight(self, eng1):
        """slots=1, everything queued up front: admission order IS the
        WRR order — weight 2:1 serves A twice per B, interleaved."""
        sched = sv.ContinuousBatchingScheduler(
            eng1, max_queue=16,
            policy=sv.SchedulingPolicy(tenant_weights={"A": 2.0,
                                                       "B": 1.0}))
        for i in range(4):
            sched.submit(sv.Request(f"a{i}", _prompt(i),
                                    max_new_tokens=2, tenant="A"))
        for i in range(2):
            sched.submit(sv.Request(f"b{i}", _prompt(10 + i),
                                    max_new_tokens=2, tenant="B"))
        seen = []
        _logging.add_event_sink(seen.append)
        try:
            sched.run()
        finally:
            _logging.remove_event_sink(seen.append)
        admitted = [e["rid"] for e in seen
                    if e["event"] == "serving_request_admitted"]
        assert admitted == ["a0", "b0", "a1", "a2", "b1", "a3"]

    def test_tenant_inflight_gauge(self, engine):
        from apex_tpu.obs.bridge import SERVING_TENANT_INFLIGHT

        sched = sv.ContinuousBatchingScheduler(
            engine, max_queue=8, policy=sv.SchedulingPolicy())
        sched.submit(sv.Request("a0", _prompt(1), max_new_tokens=6,
                                tenant="A"))
        sched.submit(sv.Request("b0", _prompt(2), max_new_tokens=6,
                                tenant="B"))
        sched.step()
        assert SERVING_TENANT_INFLIGHT.value(tenant="A") == 1
        assert SERVING_TENANT_INFLIGHT.value(tenant="B") == 1
        sched.run()
        assert SERVING_TENANT_INFLIGHT.value(tenant="A") == 0
        assert SERVING_TENANT_INFLIGHT.value(tenant="B") == 0


# ---------------------------------------------------------------------------
# satellites: O(1) submit guard, run() stall bound, close() lifecycle
# ---------------------------------------------------------------------------


class TestSatellites:
    def test_duplicate_rid_semantics_preserved(self, eng1):
        sched = sv.ContinuousBatchingScheduler(eng1, max_queue=8)
        sched.submit(sv.Request("r", _prompt(1), max_new_tokens=2))
        with pytest.raises(ValueError, match="in flight"):
            sched.submit(sv.Request("r", _prompt(2), max_new_tokens=2))
        sched.run()
        with pytest.raises(ValueError, match="finished"):
            sched.submit(sv.Request("r", _prompt(2), max_new_tokens=2))
        sched.pop_result("r")              # claiming frees the rid
        sched.submit(sv.Request("r", _prompt(2), max_new_tokens=2))
        sched.run()
        assert set(sched.pop_results()) == {"r"}
        sched.submit(sv.Request("r", _prompt(3), max_new_tokens=2))
        sched.run()

    def test_run_raises_scheduler_stalled(self, eng1):
        sched = sv.ContinuousBatchingScheduler(eng1, max_queue=8)
        sched.submit(sv.Request("r", _prompt(1), max_new_tokens=2))
        # an engine bug that never finishes a stream: a no-op step
        sched.step = lambda: []
        with pytest.raises(sv.SchedulerStalled) as exc:
            sched.run()
        msg = str(exc.value)
        assert "1 queued" in msg and "prefill backlog" in msg
        # explicit max_steps is a progress bound too
        with pytest.raises(sv.SchedulerStalled):
            sched.run(max_steps=3)

    def test_derived_bound_is_generous_for_healthy_drains(self, eng1):
        sched = sv.ContinuousBatchingScheduler(eng1, max_queue=8)
        for i in range(3):
            sched.submit(sv.Request(f"r{i}", _prompt(i),
                                    max_new_tokens=4))
        bound = sched._derived_step_bound()
        results = sched.run()
        assert len(results) == 3
        assert sched.steps_run < bound / 2     # nowhere near the bound

    def test_close_twice_and_close_with_work(self, eng1):
        sched = sv.ContinuousBatchingScheduler(
            eng1, max_queue=4,
            prefix_caching=sv.PrefixCacheConfig(max_tokens=64))
        sched.submit(sv.Request("r", _prompt(1), max_new_tokens=8))
        with pytest.raises(RuntimeError, match="queued"):
            sched.close()                  # queued work refuses
        sched.step()
        with pytest.raises(RuntimeError, match="active"):
            sched.close()                  # active work refuses
        sched.run()
        sched.close()
        sched.close()                      # idempotent once drained
        # suspended work refuses too
        sched2 = sv.ContinuousBatchingScheduler(
            eng1, max_queue=8, policy=sv.SchedulingPolicy(),
            prefix_caching=sv.PrefixCacheConfig(max_tokens=64))
        sched2.submit(sv.Request("lo", _prompt(1), max_new_tokens=10))
        for _ in range(3):
            sched2.step()
        sched2.submit(sv.Request("hi", _prompt(2), max_new_tokens=4,
                                 priority=5))
        sched2.step()
        assert sched2.suspended_count == 1
        with pytest.raises(RuntimeError, match="suspended"):
            sched2.close()
        sched2.run()
        sched2.close()


# ---------------------------------------------------------------------------
# chaos fault units
# ---------------------------------------------------------------------------


class TestChaosFaults:
    def test_slow_decode_step_inflates_virtual_clock(self):
        clk = sv.VirtualClock()
        fault = SlowDecodeStep([1, 3], 0.5, clock=clk)
        for step in range(5):
            fault(step)
        assert clk() == 1.0                # exactly two inflations
        with pytest.raises(ValueError, match="extra_s"):
            SlowDecodeStep([0], 0.0, clock=clk)
        with pytest.raises(ValueError, match="advanceable"):
            SlowDecodeStep([0], 0.5, clock=lambda: 0.0)

    def test_stall_stream_cancels_after_n_tokens(self, engine,
                                                 isolated_tokens):
        sched = sv.ContinuousBatchingScheduler(engine, max_queue=8,
                                               clock=sv.VirtualClock())
        keep = sv.Request("keep", _prompt(1), max_new_tokens=8)
        wl = sv.OpenLoopWorkload(
            requests=(keep,
                      sv.Request("stall", _prompt(2), max_new_tokens=8)),
            arrivals=(0.0, 0.0), deadlines=(None, None))
        fault = StallStream(["stall"], after_tokens=3)
        out = sv.LoadGenerator(sched, wl, step_time_s=0.25,
                               step_hook=fault).run()
        assert fault.stalled == ["stall"]
        res = out.results["stall"]
        assert res.finish_reason == "cancelled"
        assert 3 <= len(res.tokens) < 8
        assert (out.results["keep"].tokens
                == isolated_tokens(keep))

    def test_cancel_storm_deterministic_and_isolated(self, engine,
                                                     isolated_tokens):
        def run_storm():
            engine.reset()
            sched = sv.ContinuousBatchingScheduler(
                engine, max_queue=16, clock=sv.VirtualClock())
            prompts = [_prompt(i) for i in range(6)]
            wl = sv.make_workload(prompts, (0.0,) * 6,
                                  max_new_tokens=6, rid_prefix="s")
            storm = CancelStorm([2], count=2, seed=3)
            out = sv.LoadGenerator(sched, wl, step_time_s=0.25,
                                   step_hook=storm).run()
            return storm.cancelled, out

        hit1, out1 = run_storm()
        hit2, out2 = run_storm()
        assert hit1 == hit2 and len(hit1) == 2     # seed-deterministic
        for req in out1.results:
            assert out1.results[req].tokens == out2.results[req].tokens
        survivors = [r for r in out1.results.values()
                     if r.finish_reason in sv.SERVED_REASONS]
        assert survivors
        wl_by_rid = {f"s{i}": i for i in range(6)}
        for res in survivors:
            ref = isolated_tokens(
                sv.Request(res.rid, _prompt(wl_by_rid[res.rid]),
                           max_new_tokens=6))
            assert res.tokens == ref


# ---------------------------------------------------------------------------
# default-off identity: no policy == the FIFO scheduler, byte for byte
# ---------------------------------------------------------------------------


def _serving_metric_state():
    snap = obs.snapshot()
    return {name: entry for name, entry in snap.items()
            if name.startswith("apex_serving_")
            or name == "apex_events_total"}


class TestDefaultOffIdentity:
    def test_policy_annotations_inert_without_policy(self, engine):
        """The SAME workload, once with control-plane annotations
        (priorities, deadlines, tenants) and once with plain requests,
        through policy-less schedulers: event streams (kind, rid,
        sorted payload keys) and serving-metric snapshots are EXACTLY
        equal — the annotations are inert, and the refactored
        admission path is byte-for-byte the FIFO scheduler."""
        def one_run(annotated):
            clk = sv.VirtualClock()
            engine.reset()
            sched = sv.ContinuousBatchingScheduler(engine, max_queue=8,
                                                   clock=clk)
            prompts = [_prompt(i) for i in range(5)]
            wl = sv.make_workload(
                prompts, sv.burst_arrivals(5, burst=2, period_s=1.0),
                max_new_tokens=3,
                deadline_s=0.75 if annotated else None,
                priorities=[5, 0] if annotated else None,
                tenants=["paid", "free"] if annotated else None)
            seen = []
            _logging.add_event_sink(seen.append)
            obs.metrics.reset()
            try:
                out = sv.LoadGenerator(sched, wl,
                                       step_time_s=0.25).run()
            finally:
                _logging.remove_event_sink(seen.append)
            stream = [(e["event"], e.get("rid"), tuple(sorted(e)))
                      for e in seen]
            tokens = {rid: r.tokens for rid, r in out.results.items()}
            return stream, _serving_metric_state(), tokens

        s_plain, m_plain, t_plain = one_run(annotated=False)
        s_annot, m_annot, t_annot = one_run(annotated=True)
        assert s_annot == s_plain
        assert t_annot == t_plain
        # the deadline-carrying run publishes goodput (a loadgen
        # feature that predates this PR) — everything else identical
        m_annot.pop("apex_serving_goodput_ratio", None)
        m_plain.pop("apex_serving_goodput_ratio", None)
        assert m_annot == m_plain
        # and no control-plane event kind ever fired
        control = {"serving_request_preempted", "serving_request_resumed",
                   "serving_request_cancelled", "serving_request_shed"}
        assert not control & {k for k, _, _ in s_annot}


# ---------------------------------------------------------------------------
# THE acceptance run: 2x-overload chaos, policy vs FIFO
# ---------------------------------------------------------------------------


class TestChaosAcceptance:
    N = 10
    #: burst 1 (cx0..cx4) is all low priority; burst 2 carries the
    #: high-priority arrivals (cx5, cx7) — they land while both slots
    #: hold low-priority DECODE streams, forcing preempt-to-admit
    PRIORITIES = (0, 0, 0, 0, 0, 5, 0, 5, 0, 0)
    TENANTS = ("batch",) * 5 + ("paid", "batch", "paid", "batch",
                                "batch")
    HI = (5, 7)

    def _workload(self):
        prompts = [_prompt(100 + i) for i in range(self.N)]
        # deadline 5.0 (4.0 before ISSUE 36): a stream's last token is
        # delivered a step after the step that computed it, so every
        # request takes one virtual step more and the preempted victims,
        # which came back inside 4.0 with steps to spare, would now be
        # shed while suspended instead of resuming
        return sv.make_workload(
            prompts, sv.burst_arrivals(self.N, burst=5, period_s=2.0),
            max_new_tokens=6, deadline_s=5.0,
            priorities=self.PRIORITIES, tenants=self.TENANTS,
            rid_prefix="cx")

    def _drive(self, model, params, policy):
        clk = sv.VirtualClock()
        eng = _mk_engine(model, params, slots=2)
        sched = sv.ContinuousBatchingScheduler(
            eng, max_queue=16, policy=policy, clock=clk)
        rec = rt.RequestTraceRecorder(clock=clk).install()
        chaos = SlowDecodeStep([3, 9], 1.0, clock=clk)
        try:
            out = sv.LoadGenerator(sched, self._workload(),
                                   step_time_s=0.25,
                                   step_hook=chaos).run()
        finally:
            rec.uninstall()
        return sched, eng, out, rec

    @pytest.fixture(scope="class")
    def runs(self, model, params):
        fifo = self._drive(model, params, policy=None)
        pol = self._drive(model, params,
                          policy=sv.SchedulingPolicy(
                              tenant_weights={"paid": 3.0}))
        return fifo, pol

    def test_chaos_exercised_the_control_plane(self, runs):
        (fifo_sched, _, _, _), (sched, _, out, _) = runs
        stats = sched.control_stats
        assert stats["preempted"] >= 2, stats
        assert stats["resumed"] == stats["preempted"]   # all came back
        assert stats["shed"] >= 1, stats
        # the FIFO side of the comparison ran no control plane at all
        assert fifo_sched.control_stats == {
            "preempted": 0, "resumed": 0, "cancelled": 0, "shed": 0}

    def test_survivors_bit_identical_to_unperturbed_runs(
            self, runs, isolated_tokens):
        """Every stream that survived the chaos run — including every
        preempted-and-resumed one — is token-identical to its
        unperturbed isolated run: neither the slow steps, nor the
        shedding around it, nor a lossless preemption moved one bit."""
        (_, _, fifo_out, _), (_, _, pol_out, _) = runs
        wl = self._workload()
        by_rid = {r.rid: r for r in wl.requests}
        checked = resumed = 0
        for out in (fifo_out, pol_out):
            for rid, res in out.results.items():
                if res.finish_reason not in sv.SERVED_REASONS:
                    continue
                assert res.tokens == isolated_tokens(by_rid[rid]), rid
                checked += 1
                resumed += res.finish_reason == "preempted-resumed"
        assert checked >= self.N            # FIFO serves all 10
        assert resumed >= 1                 # incl. a preempted stream

    def test_policy_beats_fifo_on_hp_p99_ttft_and_goodput(self, runs):
        """The headline: on the same 2x-overload chaos workload, the
        policy's high-priority p99 TTFT and overall goodput are
        STRICTLY better than FIFO's (the PR-12 SLO-report semantics:
        goodput over offered, deadlines from arrival)."""
        (_, _, fifo_out, fifo_rec), (_, _, pol_out, pol_rec) = runs
        hi_rids = {f"cx{i}" for i in self.HI}

        def report(out, rec):
            return oslo.build_report(
                rec.records(), offered=out.offered,
                deadlines=out.deadlines, arrivals=out.arrivals,
                duration_s=out.duration_s)

        def hp_p99(rec):
            samples = [r.ttft_s for r in rec.records()
                       if r.rid in hi_rids and r.complete]
            assert len(samples) == len(hi_rids)   # every hp served
            return oslo.percentile(samples, 0.99)

        fifo_report = report(fifo_out, fifo_rec)
        pol_report = report(pol_out, pol_rec)
        assert hp_p99(pol_rec) < hp_p99(fifo_rec)
        assert pol_report.goodput > fifo_report.goodput
        assert pol_out.goodput > fifo_out.goodput
        # recorder-side annotations agree with the scheduler
        pre = [r for r in pol_rec.records() if r.preemptions]
        assert pre and all(p["t_resumed"] is not None
                           for r in pre for p in r.preempts
                           if r.finish_reason == "preempted-resumed")

    def test_no_new_compiled_programs_on_the_policy_path(self, runs):
        (_, fifo_eng, _, _), (_, pol_eng, _, _) = runs
        for eng in (fifo_eng, pol_eng):
            assert eng.decode_compiles() == 1
            assert eng.prefill_compiles() <= len(eng.prefill_buckets)
        # preempt/resume reuses the existing read/restore families
        bound = len(pol_eng.prefill_buckets) + \
            pol_eng.prefill_buckets[0] - 1
        assert pol_eng.capture_compiles() <= bound
        assert pol_eng.restore_compiles() <= len(pol_eng.prefill_buckets)
        # FIFO never paid either family
        assert fifo_eng.capture_compiles() == 0
        assert fifo_eng.restore_compiles() == 0
