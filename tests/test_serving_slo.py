"""Request-level serving observability (ISSUE 12): lifecycle traces,
the deterministic open-loop load generator, and SLO reports.

THE acceptance run: a drained open-loop workload (bursty arrivals,
chunked prompts, prefix caching AND speculation enabled) whose
:class:`RequestTraceRecorder` output is *exactly reconciled* against
the scheduler's results and the raw event stream — every request one
complete span tree, phase durations summing to the total within the
recorder's stated rounding, prefix-hit/spec annotations matching the
events one for one.  Plus: the default-off identity (no recorder ⇒ no
new events, metric stream unchanged — snapshot-equal on a virtual
clock), deterministic virtual-clock timing (exact TTFT/TPOT arithmetic,
no sleeps), bit-reproducible workloads by seed, QueueFull shedding
charged against goodput, SLO percentile/crosscheck units, and the
instrumented-vs-bare scheduler step overhead bound (≤ 1.10x with a
recorder installed).
"""

import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import _logging, obs
from apex_tpu import serving as sv
from apex_tpu.models import LlamaConfig, LlamaForCausalLM
from apex_tpu.obs import request_trace as rt
from apex_tpu.obs import slo as oslo
from apex_tpu.obs.request_trace import PHASE_SUM_TOLERANCE_S

CFG = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, max_position_embeddings=96)
MAX = 96
PREFILL = 16


@pytest.fixture(scope="module")
def model():
    return LlamaForCausalLM(CFG)


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


@pytest.fixture(scope="module")
def engine(model, params):
    return sv.DecodeEngine(model, params, slots=4, max_len=MAX,
                           prefill_len=PREFILL)


@pytest.fixture()
def capture_events():
    """Append every emitted event dict to a list for the duration."""
    seen = []
    _logging.add_event_sink(seen.append)
    yield seen
    _logging.remove_event_sink(seen.append)


def _sched(engine, clock, **kw):
    return sv.ContinuousBatchingScheduler(engine, log_interval=10 ** 9,
                                          clock=clock, **kw)


# ---------------------------------------------------------------------------
# loadgen units: arrival processes, prompt mixes, workload validation
# ---------------------------------------------------------------------------

class TestLoadgenUnits:
    def test_uniform_arrivals(self):
        assert sv.uniform_arrivals(4, 2.0) == (0.0, 0.5, 1.0, 1.5)
        with pytest.raises(ValueError):
            sv.uniform_arrivals(0, 1.0)
        with pytest.raises(ValueError):
            sv.uniform_arrivals(4, 0.0)

    def test_poisson_arrivals_seeded(self):
        a = sv.poisson_arrivals(16, 5.0, seed=3)
        b = sv.poisson_arrivals(16, 5.0, seed=3)
        c = sv.poisson_arrivals(16, 5.0, seed=4)
        assert a == b                      # bit-identical by seed
        assert a != c
        assert a[0] == 0.0
        assert all(y >= x for x, y in zip(a, a[1:]))

    def test_burst_arrivals_trains(self):
        a = sv.burst_arrivals(6, burst=2, period_s=1.0)
        assert a == (0.0, 0.0, 1.0, 1.0, 2.0, 2.0)
        spaced = sv.burst_arrivals(4, burst=2, period_s=1.0,
                                   spacing_s=0.25)
        assert spaced == (0.0, 0.25, 1.0, 1.25)
        with pytest.raises(ValueError):       # burst outlasts period
            sv.burst_arrivals(4, burst=3, period_s=1.0, spacing_s=0.5)

    def test_prompt_mixes_seeded_and_shaped(self):
        sp = sv.shared_prefix_prompts(4, shared_len=8, suffix_len=3,
                                      vocab=128, seed=1)
        assert all(p[:8] == sp[0][:8] for p in sp)
        assert len({tuple(p) for p in sp}) == 4       # unique suffixes
        assert sp == sv.shared_prefix_prompts(4, shared_len=8,
                                              suffix_len=3, vocab=128,
                                              seed=1)
        zo = sv.zero_overlap_prompts(3, length=6, vocab=128, seed=2)
        assert all(len(p) == 6 for p in zo)
        ml = sv.mixed_length_prompts(8, prefill_len=64, vocab=128)
        assert [len(p) for p in ml] == [
            max(1, int(64 * f)) for f in sv.loadgen.LENGTH_SKEW_FRACTIONS]

    def test_workload_validation(self):
        reqs = (sv.Request("a", [1], 2), sv.Request("b", [1], 2))
        with pytest.raises(ValueError, match="mismatch"):
            sv.OpenLoopWorkload(reqs, (0.0,), (None, None))
        with pytest.raises(ValueError, match="non-decreasing"):
            sv.OpenLoopWorkload(reqs, (1.0, 0.5), (None, None))
        with pytest.raises(ValueError, match="< 0"):
            sv.OpenLoopWorkload(reqs, (-1.0, 0.5), (None, None))
        with pytest.raises(ValueError, match="positive"):
            sv.OpenLoopWorkload(reqs, (0.0, 1.0), (0.0, None))
        dup = (sv.Request("a", [1], 2), sv.Request("a", [1], 2))
        with pytest.raises(ValueError, match="duplicate"):
            sv.OpenLoopWorkload(dup, (0.0, 1.0), (None, None))
        with pytest.raises(ValueError, match="prompts vs"):
            sv.make_workload([[1], [2]], (0.0,), max_new_tokens=1)

    def test_fingerprint_covers_schedule_and_streams(self):
        wl = sv.make_workload([[1, 2], [3, 4]], (0.0, 1.0),
                              max_new_tokens=4, deadline_s=2.0)
        same = sv.make_workload([[1, 2], [3, 4]], (0.0, 1.0),
                                max_new_tokens=4, deadline_s=2.0)
        assert wl.schedule_fingerprint() == same.schedule_fingerprint()
        for other in (
                sv.make_workload([[1, 2], [3, 5]], (0.0, 1.0),
                                 max_new_tokens=4, deadline_s=2.0),
                sv.make_workload([[1, 2], [3, 4]], (0.0, 1.5),
                                 max_new_tokens=4, deadline_s=2.0),
                sv.make_workload([[1, 2], [3, 4]], (0.0, 1.0),
                                 max_new_tokens=5, deadline_s=2.0)):
            assert wl.schedule_fingerprint() != other.schedule_fingerprint()
        assert wl.offered_rps == 1.0

    def test_generator_guards(self, engine):
        wl = sv.make_workload([[1, 2, 3]], (0.0,), max_new_tokens=2)
        sched = _sched(engine, time.monotonic)
        with pytest.raises(ValueError, match="advanceable"):
            sv.LoadGenerator(sched, wl, step_time_s=0.25)
        with pytest.raises(ValueError, match="step_time_s"):
            sv.LoadGenerator(_sched(engine, sv.VirtualClock()), wl,
                             step_time_s=0.0)
        # a virtual clock that never advances + a pending future
        # arrival must fail loudly instead of spinning forever
        future = sv.make_workload([[1, 2], [3, 4]], (0.0, 10.0),
                                  max_new_tokens=1)
        gen = sv.LoadGenerator(_sched(engine, sv.VirtualClock()), future)
        with pytest.raises(RuntimeError, match="did not advance"):
            gen.run()

    def test_virtual_clock(self):
        clk = sv.VirtualClock(1.0)
        assert clk() == 1.0
        assert clk.advance(0.25) == 1.25
        with pytest.raises(ValueError):
            clk.advance(-0.1)


# ---------------------------------------------------------------------------
# deterministic virtual-clock timing
# ---------------------------------------------------------------------------

class TestVirtualClockTiming:
    def test_exact_latency_arithmetic(self, engine):
        """On a shared VirtualClock every latency is an exact multiple
        of the virtual step: a one-chunk prompt admits, prefills,
        samples its first token and delivers it inside step 1 (TTFT
        exactly 0.0) while the same step's decode runs; each later step
        delivers the token the step before it computed (ISSUE 36) — 3
        tokens finish two steps later (total exactly 0.5, TPOT exactly
        0.25)."""
        clk = sv.VirtualClock()
        sched = _sched(engine, clk)
        rec = rt.RequestTraceRecorder(clock=clk).install()
        try:
            wl = sv.make_workload([[5, 6, 7, 8]], (0.0,),
                                  max_new_tokens=3, deadline_s=10.0)
            out = sv.LoadGenerator(sched, wl, step_time_s=0.25).run()
        finally:
            rec.uninstall()
        res = out.results["lg0"]
        assert res.ttft_s == 0.0
        assert res.total_s == 0.5
        (record,) = rec.records()
        assert record.complete
        assert record.queue_wait_s == 0.0
        assert record.prefill_s == 0.0
        assert record.decode_s == 0.5
        assert record.total_s == 0.5
        assert record.tpot_s == 0.25
        # the recorder's view and the scheduler's event measurements
        # agree exactly — one shared clock, one timeline
        assert record.scheduler_ttft_s == res.ttft_s
        assert record.scheduler_queue_wait_s == 0.0
        assert out.goodput == 1.0 and out.duration_s == 0.75

    def test_chunked_prompt_ttft_spans_steps(self, engine):
        """A prompt needing two budgeted chunks takes two steps to
        first token: TTFT is exactly one virtual step."""
        clk = sv.VirtualClock()
        sched = _sched(engine, clk, prefill_budget=4)
        wl = sv.make_workload([[1] * 8], (0.0,), max_new_tokens=1)
        out = sv.LoadGenerator(sched, wl, step_time_s=0.25).run()
        assert out.results["lg0"].ttft_s == 0.25

    def test_token_streams_reproducible_by_seed(self, engine):
        """Same seed ⇒ same workload ⇒ same token streams, run to run
        (fresh scheduler each time, arrival timing irrelevant)."""
        def one_run(step_time):
            clk = sv.VirtualClock()
            sched = _sched(engine, clk)
            prompts = sv.zero_overlap_prompts(6, length=7, vocab=128,
                                              seed=11)
            wl = sv.make_workload(
                prompts, sv.poisson_arrivals(6, 4.0, seed=11),
                max_new_tokens=4, temperature=0.8, top_k=8, seed=11)
            out = sv.LoadGenerator(sched, wl, step_time_s=step_time).run()
            return (wl.schedule_fingerprint(),
                    {r: res.tokens for r, res in out.results.items()})

        fp_a, tokens_a = one_run(0.25)
        fp_b, tokens_b = one_run(0.25)
        assert fp_a == fp_b
        assert tokens_a == tokens_b
        # arrival *timing* is scheduling, not numerics: a different
        # virtual step cost reorders nothing in any stream
        _, tokens_c = one_run(0.125)
        assert tokens_c == tokens_a


# ---------------------------------------------------------------------------
# THE acceptance run: recorder output exactly reconciled
# ---------------------------------------------------------------------------

class TestReconciliation:
    @pytest.fixture(scope="class")
    def drained(self, model, params):
        """A drained bursty open-loop run with prefix caching AND
        speculation on, chunked prompts, and a queueing second burst —
        returns (scheduler, loadgen result, recorder, raw events).
        Class-scoped: ONE run (and one engine's worth of compiles)
        feeds every reconciliation assertion below, all of which only
        read it."""
        events = []
        _logging.add_event_sink(events.append)
        eng = sv.DecodeEngine(model, params, slots=4, max_len=MAX,
                              prefill_len=PREFILL)
        clk = sv.VirtualClock()
        sched = _sched(
            eng, clk,
            speculation=sv.SpeculationConfig(max_draft=2),
            prefix_caching=sv.PrefixCacheConfig(max_tokens=1 << 14))
        # 8 requests sharing a 32-token prefix (2 cache blocks), unique
        # 4-token tails; prompts chunk (36 > prefill_len=16); two
        # bursts of 4 so the second burst queues behind busy slots
        prompts = sv.shared_prefix_prompts(8, shared_len=32,
                                           suffix_len=4, vocab=128,
                                           seed=5)
        wl = sv.make_workload(
            prompts, sv.burst_arrivals(8, burst=4, period_s=0.5),
            max_new_tokens=6, deadline_s=64.0, seed=5)
        rec = rt.RequestTraceRecorder(clock=clk).install()
        try:
            out = sv.LoadGenerator(sched, wl, step_time_s=0.25).run()
        finally:
            rec.uninstall()
            _logging.remove_event_sink(events.append)
        yield sched, out, rec, events
        sched.close()

    def test_every_request_one_complete_record(self, drained):
        sched, out, rec, _ = drained
        records = rec.records()
        assert out.completed == out.offered == 8
        assert {r.rid for r in records} == set(out.results)
        assert len(records) == 8 and not rec.open_records()
        for record in records:
            assert record.complete
            res = out.results[record.rid]
            assert record.new_tokens == len(res.tokens)
            assert record.prompt_tokens == 36
            assert record.finish_reason == res.finish_reason
            assert record.slot is not None

    def test_phase_durations_sum_to_total(self, drained):
        _, out, rec, _ = drained
        for record in rec.records():
            total = (record.queue_wait_s + record.prefill_s
                     + record.decode_s)
            assert abs(total - record.total_s) <= PHASE_SUM_TOLERANCE_S
            # recorder timeline == scheduler timeline (shared clock)
            res = out.results[record.rid]
            assert record.ttft_s == pytest.approx(res.ttft_s, abs=1e-6)
            assert record.total_s == pytest.approx(res.total_s, abs=1e-6)
        # the second burst queued behind busy slots: somebody waited
        assert any(r.queue_wait_s > 0 for r in rec.records())

    def test_chunks_cover_the_uncached_prompt(self, drained):
        _, _, rec, _ = drained
        for record in rec.records():
            saved = (record.prefix or {}).get("saved_tokens") or 0
            assert (sum(c["chunk_tokens"] for c in record.chunks)
                    + saved == record.prompt_tokens)
            offs = [c["offset_tokens"] for c in record.chunks]
            assert offs == sorted(offs)
            if record.chunks:
                assert record.chunks[0]["offset_tokens"] == saved

    def test_prefix_annotations_match_event_stream(self, drained):
        _, _, rec, events = drained
        hits = {e["rid"]: e for e in events
                if e["event"] == "serving_prefix_hit"}
        misses = {e["rid"] for e in events
                  if e["event"] == "serving_prefix_miss"}
        assert hits and misses            # cold first burst, warm later
        for record in rec.records():
            if record.rid in hits:
                assert record.prefix["hit"] is True
                assert (record.prefix["saved_tokens"]
                        == hits[record.rid]["saved_tokens"])
            elif record.rid in misses:
                assert record.prefix == {"hit": False}

    def test_spec_annotations_match_event_stream(self, drained):
        sched, _, rec, events = drained
        per_rid = {}
        for e in events:
            if e["event"] == "serving_spec_verify":
                st = per_rid.setdefault(e["rid"], {"dispatches": 0,
                                                   "drafted": 0,
                                                   "accepted": 0,
                                                   "emitted": 0})
                st["dispatches"] += 1
                for f in ("drafted", "accepted", "emitted"):
                    st[f] += e[f]
        for record in rec.records():
            got = {k: record.spec.get(k, 0)
                   for k in ("dispatches", "drafted", "accepted",
                             "emitted")}
            want = per_rid.get(record.rid, {"dispatches": 0,
                                            "drafted": 0, "accepted": 0,
                                            "emitted": 0})
            assert got == want
        # and the totals reconcile against the scheduler's own books
        stats = sched.spec_stats
        records = rec.records()
        for key in ("dispatches", "drafted", "accepted", "emitted"):
            assert sum(r.spec.get(key, 0) for r in records) == stats[key]

    def test_chrome_trace_one_track_per_request(self, drained, tmp_path):
        _, _, rec, _ = drained
        payload = rec.export(str(tmp_path / "req.trace.json"))
        loaded = json.loads((tmp_path / "req.trace.json").read_text())
        assert loaded == payload
        events = loaded["traceEvents"]
        names = {e["args"]["name"] for e in events
                 if e.get("ph") == "M" and e["name"] == "thread_name"}
        assert names == {r.rid for r in rec.records()}
        by_tid = {}
        for e in events:
            if e.get("ph") == "X":
                by_tid.setdefault(e["tid"], []).append(e)
        assert len(by_tid) == 8           # one track per request
        for tid, slices in by_tid.items():
            by_name = {e["name"]: e for e in slices}
            req = by_name["request"]
            # a complete span tree: every phase/chunk slice contained
            # within its request slice on the same track
            for e in slices:
                assert e["ts"] >= req["ts"] - 1e-6
                assert (e["ts"] + e["dur"]
                        <= req["ts"] + req["dur"] + 1e-6)
            assert {"queued", "prefill", "decode"} <= set(by_name)

    def test_jsonl_export_round_trips(self, drained, tmp_path):
        _, _, rec, _ = drained
        path = tmp_path / "req.jsonl"
        n = rec.export_jsonl(str(path))
        rows = [json.loads(line) for line in
                path.read_text().splitlines()]
        assert n == len(rows) == 8
        assert ({r["rid"] for r in rows}
                == {r.rid for r in rec.records()})
        for row, record in zip(rows, rec.records()):
            assert row["total_s"] == record.total_s

    def test_slo_report_over_the_run(self, drained):
        _, out, rec, _ = drained
        report = oslo.build_report(rec.records(), offered=out.offered,
                                   deadlines=out.deadlines,
                                   arrivals=out.arrivals,
                                   duration_s=out.duration_s)
        assert report.completed == 8 and report.incomplete == 0
        assert report.goodput == out.goodput == 1.0
        ttft = sorted(r.ttft_s for r in rec.records())
        assert report.ttft["p50"] == ttft[math.ceil(0.5 * 8) - 1]
        assert report.ttft["p99"] == ttft[-1]
        d = report.to_dict()
        assert d["goodput"] == 1.0
        assert d["ttft_s"]["n"] == 8


# ---------------------------------------------------------------------------
# default-off identity + overhead bound
# ---------------------------------------------------------------------------

def _serving_metric_state():
    """The serving-relevant slice of the default registry snapshot."""
    snap = obs.snapshot()
    return {name: entry for name, entry in snap.items()
            if name.startswith("apex_serving_")
            or name == "apex_events_total"}


class TestDefaultOffIdentity:
    def test_no_recorder_no_new_events_same_metrics(self, engine):
        """Recorder on vs off: the event stream (kinds + rids, in
        order) and the metric stream are IDENTICAL — the recorder is a
        pure consumer.  Virtual clock ⇒ even histogram sums match
        exactly."""
        def one_run(install_recorder):
            clk = sv.VirtualClock()
            sched = _sched(engine, clk)
            prompts = sv.zero_overlap_prompts(5, length=6, vocab=128,
                                              seed=9)
            wl = sv.make_workload(
                prompts, sv.burst_arrivals(5, burst=2, period_s=1.0),
                max_new_tokens=3, seed=9)
            seen = []
            _logging.add_event_sink(seen.append)
            rec = (rt.RequestTraceRecorder(clock=clk).install()
                   if install_recorder else None)
            obs.metrics.reset()
            try:
                sv.LoadGenerator(sched, wl, step_time_s=0.25).run()
            finally:
                if rec is not None:
                    rec.uninstall()
                _logging.remove_event_sink(seen.append)
            stream = [(e["event"], e.get("rid")) for e in seen]
            return stream, _serving_metric_state()

        stream_off, metrics_off = one_run(False)
        stream_on, metrics_on = one_run(True)
        assert stream_on == stream_off     # no new events, none missing
        assert metrics_on == metrics_off   # metric stream unchanged

    def test_queue_wait_histogram_fed(self, engine):
        before = obs.bridge.SERVING_QUEUE_WAIT.count()
        clk = sv.VirtualClock()
        sched = _sched(engine, clk)
        wl = sv.make_workload([[1, 2, 3]], (0.0,), max_new_tokens=1)
        sv.LoadGenerator(sched, wl, step_time_s=0.25).run()
        assert obs.bridge.SERVING_QUEUE_WAIT.count() == before + 1

    def test_goodput_gauge_only_with_deadlines(self, engine):
        obs.bridge.SERVING_GOODPUT.set(-1.0)       # sentinel
        clk = sv.VirtualClock()
        wl = sv.make_workload([[1, 2, 3]], (0.0,), max_new_tokens=1)
        sv.LoadGenerator(_sched(engine, clk), wl,
                         step_time_s=0.25).run()
        assert obs.bridge.SERVING_GOODPUT.value() == -1.0   # untouched
        clk = sv.VirtualClock()
        wl = sv.make_workload([[1, 2, 3]], (0.0,), max_new_tokens=1,
                              deadline_s=10.0)
        out = sv.LoadGenerator(_sched(engine, clk), wl,
                               step_time_s=0.25).run()
        assert out.goodput == 1.0
        assert obs.bridge.SERVING_GOODPUT.value() == 1.0


class TestDeadlineFromArrival:
    def test_submit_lag_never_extends_a_deadline(self, engine):
        """A request due MID-step is submitted at the next boundary —
        the submit lag must come out of its deadline budget, not
        silently extend it.  Arrival at t=0.1, submitted at t=0.25,
        finished at t=0.75 (its third token is delivered by the third
        step): submit-relative elapsed is 0.5 (under a 0.6 deadline)
        but arrival-relative is 0.65 — a miss."""
        clk = sv.VirtualClock()
        sched = _sched(engine, clk)
        rec = rt.RequestTraceRecorder(clock=clk).install()
        try:
            wl = sv.make_workload([[1, 2, 3]], (0.1,),
                                  max_new_tokens=3, deadline_s=0.6)
            out = sv.LoadGenerator(sched, wl, step_time_s=0.25).run()
        finally:
            rec.uninstall()
        res = out.results["lg0"]
        assert out.arrivals["lg0"] == 0.1
        assert res.total_s == 0.5            # submit-relative: "meets"
        assert out.met_deadline["lg0"] is False
        assert out.goodput == 0.0
        # the report agrees when given the arrivals, and documents the
        # submission-relative fallback when not
        report = oslo.build_report(rec.records(), offered=1,
                                   deadlines=out.deadlines,
                                   arrivals=out.arrivals)
        assert report.goodput == 0.0 and report.deadline_misses == 1
        fallback = oslo.build_report(rec.records(), offered=1,
                                     deadlines=out.deadlines)
        assert fallback.goodput == 1.0


class TestShedding:
    def test_queue_full_sheds_and_charges_goodput(self, model, params):
        """Open-loop: a simultaneous burst past queue + slot capacity
        sheds the overflow (never retried) and goodput counts the shed
        arrivals against the offered total."""
        eng = sv.DecodeEngine(model, params, slots=1, max_len=MAX,
                              prefill_len=PREFILL)
        clk = sv.VirtualClock()
        sched = _sched(eng, clk, max_queue=2)
        prompts = sv.zero_overlap_prompts(5, length=4, vocab=128,
                                          seed=4)
        wl = sv.make_workload(prompts, (0.0,) * 5, max_new_tokens=2,
                              deadline_s=100.0)
        out = sv.LoadGenerator(sched, wl, step_time_s=0.25).run()
        # all 5 arrive before the first step boundary, so only the
        # 2-deep bounded queue accepts — the other 3 shed immediately
        assert len(out.rejected) == 3
        assert out.completed == 2
        assert out.goodput == 2 / 5
        assert [r for r in out.met_deadline.values()].count(True) == 2
        report = oslo.build_report(
            [], offered=out.offered, deadlines=out.deadlines)
        assert report.goodput == 0.0      # no records at all -> 0 met


class TestOverheadBound:
    def test_recorder_overhead_within_1_10x(self, engine):
        """The acceptance bound: a drained event-rich workload with a
        recorder installed costs <= 1.10x the bare drain (the recorder
        is dict bookkeeping per event against a decode dispatch per
        step).  Best-of-3 interleaved attempts absorb scheduler noise."""
        prompts = sv.zero_overlap_prompts(24, length=5, vocab=128,
                                          seed=13)

        def drain(with_recorder):
            sched = sv.ContinuousBatchingScheduler(engine,
                                                   log_interval=10 ** 9)
            wl = sv.make_workload(prompts, (0.0,) * len(prompts),
                                  max_new_tokens=2, seed=13)
            rec = (rt.RequestTraceRecorder().install()
                   if with_recorder else None)
            try:
                t0 = time.perf_counter()
                sv.LoadGenerator(sched, wl).run()
                return time.perf_counter() - t0
            finally:
                if rec is not None:
                    rec.uninstall()

        drain(True)                        # warm compiles outside timing
        # one retry: the bound is a tight 1.10x on a wall-clock drain,
        # and a loaded CI host can hand either side one unlucky run —
        # best-of-3 per side per attempt absorbs most of it
        for attempt in range(2):
            bare = min(drain(False) for _ in range(3))
            instrumented = min(drain(True) for _ in range(3))
            if instrumented <= 1.10 * bare:
                break
        assert instrumented <= 1.10 * bare, (
            f"recorder-instrumented drain {instrumented:.4f}s vs bare "
            f"{bare:.4f}s = {instrumented / bare:.3f}x > 1.10x")


# ---------------------------------------------------------------------------
# recorder units
# ---------------------------------------------------------------------------

class TestRecorderUnits:
    def test_bounded_and_counts_drops(self):
        rec = rt.RequestTraceRecorder(max_requests=2)
        rec.install()
        try:
            for i in range(4):
                # queued AND admitted both hit the create path — a
                # refused request must count as ONE drop, not one per
                # lifecycle event that retried the create
                _logging.emit_event("serving_request_queued",
                                    rid=f"r{i}", prompt_tokens=1)
                _logging.emit_event("serving_request_admitted",
                                    rid=f"r{i}", slot=0)
        finally:
            rec.uninstall()
        assert len(rec.open_records()) == 2
        assert rec.dropped == 2
        trace = rec.to_chrome_trace()
        assert trace["otherData"]["dropped_requests"] == 2
        assert trace["otherData"]["open_requests"] == 2

    def test_stray_events_do_not_fabricate_records(self):
        rec = rt.RequestTraceRecorder()
        rec.install()
        try:
            _logging.emit_event("serving_request_finished", rid="ghost",
                                new_tokens=3)
            _logging.emit_event("serving_prefill_chunk", rid="ghost",
                                bucket=16, chunk_tokens=16)
            _logging.emit_event("serving_step", step=1)   # no rid
            _logging.emit_event("checkpoint_saved", step=1)
        finally:
            rec.uninstall()
        assert not rec.records() and not rec.open_records()

    def test_context_manager_and_validation(self):
        with pytest.raises(ValueError):
            rt.RequestTraceRecorder(max_requests=0)
        with rt.recording_requests() as rec:
            assert rec.installed()
            _logging.emit_event("serving_request_queued", rid="x",
                                prompt_tokens=2)
        assert not rec.installed()
        assert len(rec.open_records()) == 1

    def test_install_idempotent(self):
        rec = rt.RequestTraceRecorder()
        rec.install()
        rec.install()
        try:
            _logging.emit_event("serving_request_queued", rid="once",
                                prompt_tokens=1)
        finally:
            rec.uninstall()
        assert len(rec.open_records()) == 1


# ---------------------------------------------------------------------------
# SLO units: percentiles, report shape, crosscheck
# ---------------------------------------------------------------------------

class TestSLOUnits:
    def test_percentile_nearest_rank(self):
        xs = [10.0, 20.0, 30.0, 40.0]
        assert oslo.percentile(xs, 0.0) == 10.0
        assert oslo.percentile(xs, 0.25) == 10.0
        assert oslo.percentile(xs, 0.5) == 20.0
        assert oslo.percentile(xs, 0.51) == 30.0
        assert oslo.percentile(xs, 0.99) == 40.0
        assert oslo.percentile(xs, 1.0) == 40.0
        assert oslo.percentile([7.0], 0.99) == 7.0
        assert math.isnan(oslo.percentile([], 0.5))
        for bad in (-0.1, 1.1, float("nan"), float("inf")):
            with pytest.raises(ValueError):
                oslo.percentile([1.0], bad)

    def test_summarize_empty(self):
        s = oslo.summarize([])
        assert s["n"] == 0
        assert all(math.isnan(s[k]) for k in ("mean", "p50", "p99"))

    def test_build_report_guards(self):
        with pytest.raises(ValueError, match="undercount"):
            oslo.build_report(
                [rt.RequestRecord(rid="a", t_queued=0.0, t_admitted=0.0,
                                  t_first=0.0, t_finished=1.0)],
                offered=0)
        with pytest.raises(ValueError, match="unknown crosscheck"):
            oslo.build_report([], histograms={"bogus": None})

    def test_goodput_none_without_deadlines(self):
        rec = rt.RequestRecord(rid="a", new_tokens=2, t_queued=0.0,
                               t_admitted=0.0, t_first=0.5,
                               t_finished=1.0)
        report = oslo.build_report([rec], deadlines={"a": None})
        assert report.goodput is None
        report = oslo.build_report([rec], deadlines={"a": 0.75})
        assert report.goodput == 0.0 and report.deadline_misses == 1
        report = oslo.build_report([rec], deadlines={"a": 2.0})
        assert report.goodput == 1.0

    def test_crosscheck_agreement(self):
        h = obs.Histogram("apex_unit_xc_seconds",
                          buckets=(0.1, 1.0, 10.0))
        samples = [0.05, 0.5, 0.5, 5.0]
        for v in samples:
            h.observe(v)
        out = oslo.crosscheck_quantiles(samples, h)
        assert out["aligned"]
        for q in ("p50", "p95", "p99"):
            assert out["quantiles"][q]["agree"], (q, out)
        # overflow clamp counts as agreement for an overflow sample
        h2 = obs.Histogram("apex_unit_xc2_seconds", buckets=(1.0,))
        h2.observe(5.0)
        out2 = oslo.crosscheck_quantiles([5.0], h2)
        assert out2["quantiles"]["p99"]["estimate"] == 1.0
        assert out2["quantiles"]["p99"]["agree"]
        # misaligned counts are reported, not hidden
        h.observe(0.5)
        assert not oslo.crosscheck_quantiles(samples, h)["aligned"]

    def test_report_dict_deterministic(self):
        recs = [rt.RequestRecord(rid=f"r{i}", new_tokens=3,
                                 t_queued=0.0, t_admitted=0.25 * i,
                                 t_first=0.25 * i + 0.25,
                                 t_finished=0.25 * i + 0.75)
                for i in range(4)]
        a = oslo.build_report(recs, duration_s=2.0).to_dict()
        b = oslo.build_report(recs, duration_s=2.0).to_dict()
        assert a == b
        assert a["tpot_s"]["p50"] == 0.25
        assert a["throughput_rps"] == 2.0
        assert a["output_tokens"] == 12
