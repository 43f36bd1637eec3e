"""Training-supervisor subsystem tests (ISSUE 2 tentpole).

Every host-loop hardening path runs deterministically on CPU: the step
watchdog (synchronous deadline + monitor thread + heartbeat file),
classified transient retry with deterministic jitter, the validating
data-pipeline guard with its bounded skip budget, the supervisor-domain
fault injectors, and the escalation policy — ending with THE acceptance
run: flaky iterator + corrupt batch + injected slow step under a
deadline → retries, skips within budget, watchdog fires, emergency
checkpoint written and validated, restart resumes bit-identically.  No
real sleep here exceeds ~1 s.
"""

import json
import logging
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import resilience as rz
from apex_tpu._logging import _RANK_INFO_WARNED, _debug_once, emit_event
from apex_tpu.optimizers import FusedAdam
from apex_tpu.transformer.pipeline_parallel._timers import Timers


class FakeClock:
    """Injectable monotonic clock — deadline logic without real waits."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


@pytest.fixture
def events():
    """Capture structured apex_tpu.events as parsed dicts.

    Returns ``get(kind=None)`` — all events, or just one kind.
    """
    records = []
    handler = logging.Handler()
    handler.emit = lambda r: records.append(r.getMessage())
    logger = logging.getLogger("apex_tpu.events")
    logger.addHandler(handler)
    logger.setLevel(logging.INFO)

    def get(kind=None):
        parsed = [json.loads(r) for r in records]
        return parsed if kind is None else [e for e in parsed
                                            if e["event"] == kind]

    yield get
    logger.removeHandler(handler)


def _tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(x, y)


# --------------------------------------------------------------------------
# _logging satellites: monotonic duration_s + debug-once rank-info failures
# --------------------------------------------------------------------------

class TestLoggingSatellites:
    def test_emit_event_t0_adds_monotonic_duration(self):
        t0 = time.monotonic()
        ev = emit_event("unit_timing_event", t0=t0, detail=1)
        assert ev["duration_s"] >= 0.0
        assert ev["detail"] == 1

    def test_emit_event_without_t0_has_no_duration(self):
        assert "duration_s" not in emit_event("unit_plain_event")

    def test_rank_info_failures_log_once_at_debug(self):
        records = []
        handler = logging.Handler()
        handler.emit = lambda r: records.append(r)
        logger = logging.getLogger("apex_tpu._logging")
        logger.addHandler(handler)
        logger.setLevel(logging.DEBUG)
        try:
            _RANK_INFO_WARNED.discard("unit_test_key")
            _debug_once("unit_test_key", "unit thing", ValueError("boom"))
            _debug_once("unit_test_key", "unit thing", ValueError("boom"))
        finally:
            logger.removeHandler(handler)
        assert len(records) == 1
        assert records[0].levelno == logging.DEBUG
        assert "boom" in records[0].getMessage()


# --------------------------------------------------------------------------
# retry: classification, deterministic jitter, events
# --------------------------------------------------------------------------

class TestRetry:
    def test_transient_retries_then_recovers(self, events):
        calls, slept = [], []

        def fn():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("blip")
            return "ok"

        policy = rz.RetryPolicy(max_attempts=4, base_delay_s=0.25)
        assert rz.retry_transient(fn, policy=policy, what="op",
                                  sleep=slept.append) == "ok"
        assert len(calls) == 3
        assert slept == [policy.delay_s("op", 1), policy.delay_s("op", 2)]
        assert slept[1] > slept[0]  # exponential backoff
        assert len(events("retry_attempt")) == 2
        [rec] = events("retry_recovered")
        assert rec["attempts"] == 3 and rec["duration_s"] >= 0.0

    def test_non_transient_propagates_first_attempt(self, events):
        calls = []

        def fn():
            calls.append(1)
            raise ValueError("deterministic bug")

        with pytest.raises(ValueError):
            rz.retry_transient(fn, sleep=lambda s: None)
        assert len(calls) == 1
        assert events() == []

    def test_stop_iteration_propagates_untouched(self):
        it = iter([])
        with pytest.raises(StopIteration):
            rz.retry_transient(lambda: next(it), sleep=lambda s: None)

    def test_exhaustion_raises_retry_exhausted(self, events):
        def fn():
            raise ConnectionError("down")

        policy = rz.RetryPolicy(max_attempts=3, base_delay_s=0.0)
        with pytest.raises(rz.RetryExhausted) as ei:
            rz.retry_transient(fn, policy=policy, what="op",
                               sleep=lambda s: None)
        assert ei.value.attempts == 3
        assert isinstance(ei.value.last, ConnectionError)
        assert isinstance(ei.value.__cause__, ConnectionError)
        [ex] = events("retry_exhausted")
        assert ex["attempts"] == 3 and "down" in ex["error"]

    def test_marker_classification_catches_status_anchored_errors(self):
        calls = []

        def fn():
            calls.append(1)
            if len(calls) < 2:
                raise RuntimeError("UNAVAILABLE: connection reset")
            return 1

        assert rz.retry_transient(fn, sleep=lambda s: None) == 1
        assert len(calls) == 2
        # lowercase words in deterministic failure text do NOT match
        with pytest.raises(RuntimeError, match="internal"):
            rz.retry_transient(
                lambda: (_ for _ in ()).throw(
                    RuntimeError("lowering failed: internal op")),
                sleep=lambda s: None)

    def test_jitter_is_deterministic_and_seed_decorrelated(self):
        p = rz.RetryPolicy(seed=0)
        assert p.delay_s("save", 1) == p.delay_s("save", 1)
        assert p.delay_s("save", 1) != p.delay_s("fetch", 1)
        assert rz.RetryPolicy(seed=1).delay_s("save", 1) != \
            p.delay_s("save", 1)
        # delays are bounded by max_delay_s even with jitter
        cap = rz.RetryPolicy(base_delay_s=1.0, max_delay_s=1.5, jitter=10.0)
        assert cap.delay_s("x", 5) <= 1.5

    def test_degenerate_policies_rejected(self):
        with pytest.raises(ValueError):
            rz.RetryPolicy(max_attempts=0)
        with pytest.raises(ValueError):
            rz.RetryPolicy(backoff=0.5)
        with pytest.raises(ValueError):
            rz.RetryPolicy(jitter=-1.0)

    def test_transient_error_marker_class_is_retried(self):
        calls = []

        def fn():
            calls.append(1)
            if len(calls) < 2:
                raise rz.TransientError("caller-classified")
            return "ok"

        assert rz.retry_transient(fn, sleep=lambda s: None) == "ok"


# --------------------------------------------------------------------------
# timers snapshot (watchdog diagnostics source)
# --------------------------------------------------------------------------

class TestTimersSnapshot:
    def test_snapshot_is_non_destructive_and_includes_inflight(self):
        timers = Timers()
        timers("fwd").start()
        time.sleep(0.02)
        snap = timers.snapshot()
        assert snap["fwd"]["running"] is True
        assert snap["fwd"]["total_s"] > 0.0
        # unlike elapsed(), nothing was stopped or reset
        assert timers("fwd").running is True
        timers("fwd").stop()
        total = timers.snapshot()["fwd"]["total_s"]
        assert timers.snapshot()["fwd"]["total_s"] == total  # idempotent

    def test_snapshot_mid_start_does_not_pair_stale_t0(self, monkeypatch):
        """A snapshot landing inside start() — the widest monitor-thread
        race window — must never combine running=True with the PREVIOUS
        region's _t0 (which would inflate total_s by the whole idle gap
        between regions)."""
        from apex_tpu.transformer.pipeline_parallel import _timers as T

        timers = Timers()
        t = timers("fwd")
        t.start()
        t.stop()  # region 1 done; its end stamp lingers in _t0
        fake_now = time.perf_counter() + 100.0  # pretend a 100 s idle gap
        state = {"snap": None}

        def counter():
            if state["snap"] is None:
                # emulate the monitor sampling at the exact instant
                # start() reads the clock (recurses into this counter,
                # guarded by the snap-is-set flag)
                state["snap"] = {}
                state["snap"] = timers.snapshot()["fwd"]
            return fake_now

        monkeypatch.setattr(T.time, "perf_counter", counter)
        t.start()
        assert state["snap"]["total_s"] < 1.0  # region 1 only, not the gap


# --------------------------------------------------------------------------
# step watchdog + heartbeat
# --------------------------------------------------------------------------

class TestWatchdog:
    def test_fast_step_passes(self):
        wd = rz.StepWatchdog(1.0, clock=FakeClock())
        wd.arm(0)
        wd.disarm()  # no raise

    def test_slow_step_raises_with_diagnostics(self, events):
        clock = FakeClock()
        wd = rz.StepWatchdog(1.0, clock=clock)
        wd.beat(4)
        clock.advance(0.5)
        wd.arm(5)
        clock.advance(2.5)
        with pytest.raises(rz.StepDeadlineExceeded) as ei:
            wd.disarm()
        e = ei.value
        assert e.step == 5 and e.elapsed_s == pytest.approx(2.5)
        assert e.diagnostics["heartbeat_age_s"] == pytest.approx(3.0)
        assert isinstance(e.diagnostics["live_arrays"], int)
        [stall] = events("watchdog_stall")
        assert stall["step"] == 5

    def test_timers_snapshot_rides_the_stall_dump(self):
        clock = FakeClock()
        timers = Timers()
        timers("fwd").start()
        wd = rz.StepWatchdog(1.0, timers=timers, clock=clock)
        wd.arm(0)
        clock.advance(5.0)
        with pytest.raises(rz.StepDeadlineExceeded) as ei:
            wd.disarm()
        assert ei.value.diagnostics["timers"]["fwd"]["running"] is True
        timers("fwd").stop()

    def test_monitor_thread_reports_mid_stall(self, events, tmp_path):
        """A hung step leaves evidence BEFORE it ends: the monitor dumps
        the stall event and marks the heartbeat while still armed."""
        hb = str(tmp_path / "heartbeat.json")
        wd = rz.StepWatchdog(0.05, heartbeat_path=hb, poll_interval_s=0.01)
        with wd:
            wd.arm(7)
            deadline = time.monotonic() + 2.0
            while not events("watchdog_stall") \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            assert len(events("watchdog_stall")) == 1
            assert rz.read_heartbeat(hb)["stalled"] is True
            with pytest.raises(rz.StepDeadlineExceeded):
                wd.disarm()
        # one report per armed step: disarm did not re-emit
        assert len(events("watchdog_stall")) == 1

    def test_step_context_does_not_double_fire_on_body_error(self):
        clock = FakeClock()
        wd = rz.StepWatchdog(0.1, clock=clock)
        with pytest.raises(ValueError, match="body bug"):
            with wd.step(0):
                clock.advance(99.0)  # deadline long blown...
                raise ValueError("body bug")  # ...but the body's error wins
        wd.arm(1)  # armed state was cleaned up
        wd.disarm()

    def test_disarm_without_arm_is_a_usage_error(self):
        with pytest.raises(RuntimeError, match="without a matching arm"):
            rz.StepWatchdog(1.0).disarm()

    def test_degenerate_deadline_rejected(self):
        with pytest.raises(ValueError):
            rz.StepWatchdog(0.0)

    def test_heartbeat_roundtrip_and_atomicity(self, tmp_path):
        hb = str(tmp_path / "hb.json")
        payload = rz.write_heartbeat(hb, 42, ckpt_path="/ckpts/step_42")
        got = rz.read_heartbeat(hb)
        assert got["step"] == 42
        assert got["ckpt_path"] == "/ckpts/step_42"
        assert got["pid"] == os.getpid()
        assert got["monotonic"] == payload["monotonic"]
        # no temp litter: the write is temp + atomic rename
        assert os.listdir(tmp_path) == ["hb.json"]

    def test_concurrent_heartbeat_writers_never_tear_the_file(self, tmp_path):
        """The monitor thread (stall marker) and the main thread (beat)
        share a pid and can write simultaneously — every read must still
        parse (per-thread temp names keep os.replace atomic)."""
        hb = str(tmp_path / "hb.json")
        rz.write_heartbeat(hb, 0)
        stop = threading.Event()
        errors = []

        def hammer(tid):
            i = 0
            try:
                while not stop.is_set():
                    rz.write_heartbeat(hb, i, ckpt_path=f"/ckpts/{tid}/{i}")
                    i += 1
            except Exception as e:  # surfaced below, not swallowed
                errors.append(e)

        threads = [threading.Thread(target=hammer, args=(k,))
                   for k in range(2)]
        for th in threads:
            th.start()
        deadline = time.monotonic() + 0.3
        try:
            while time.monotonic() < deadline:
                got = rz.read_heartbeat(hb)  # JSONDecodeError == torn write
                assert got["pid"] == os.getpid()
        finally:
            stop.set()
            for th in threads:
                th.join()
        assert not errors

    def test_beat_failure_never_kills_the_run(self, tmp_path):
        wd = rz.StepWatchdog(
            1.0, heartbeat_path=str(tmp_path / "no_such_dir" / "hb.json"))
        wd.beat(0)  # logged, not raised

    def test_beat_keeps_newest_ckpt_path_between_saves(self, tmp_path):
        # with checkpoint_every > 1 most beats carry no ckpt_path — the
        # heartbeat's resume pointer must survive them, not be nulled
        hb = str(tmp_path / "hb.json")
        wd = rz.StepWatchdog(1.0, heartbeat_path=hb)
        wd.beat(99, ckpt_path="/ckpts/step_99")
        wd.beat(100)
        got = rz.read_heartbeat(hb)
        assert got["step"] == 100
        assert got["ckpt_path"] == "/ckpts/step_99"
        wd.beat(199, ckpt_path="/ckpts/step_199")
        assert rz.read_heartbeat(hb)["ckpt_path"] == "/ckpts/step_199"

    def test_heartbeat_carries_rank_info_when_model_parallel(
            self, tmp_path, devices):
        """ISSUE 3 satellite: with model parallelism initialized, the
        heartbeat names WHICH slice member wrote it (rank descriptor +
        machine-readable mesh shape); without it, neither key appears."""
        from apex_tpu.transformer import parallel_state

        hb = str(tmp_path / "hb.json")
        rz.write_heartbeat(hb, 1)
        got = rz.read_heartbeat(hb)
        assert "rank_info" not in got and "mesh" not in got

        parallel_state.initialize_model_parallel(2, devices=devices[:8])
        try:
            rz.write_heartbeat(hb, 2)
        finally:
            parallel_state.destroy_model_parallel()
        got = rz.read_heartbeat(hb)
        assert got["mesh"] == {"dp": 4, "pp": 1, "tp": 2}
        assert "dp=4" in got["rank_info"] and "tp=2" in got["rank_info"]


# --------------------------------------------------------------------------
# data-pipeline guard
# --------------------------------------------------------------------------

def _clean_batch(i=0):
    return {"x": np.full((2, 3), float(i), np.float32),
            "y": np.arange(2, dtype=np.int32)}


class TestDataGuard:
    def test_clean_batches_pass_untouched(self):
        batches = [_clean_batch(i) for i in range(3)]
        g = rz.GuardedIterator(iter(batches),
                               spec=rz.spec_of(_clean_batch()))
        out = list(g)
        assert len(out) == 3 and g.skipped == 0 and g.delivered == 3
        assert out[1] is batches[1]

    @pytest.mark.parametrize("mutate,reason_word", [
        (lambda b: {**b, "x": np.full((2, 3), np.nan, np.float32)},
         "non-finite"),
        (lambda b: {**b, "x": b["x"][1:]}, "shape"),
        (lambda b: {**b, "x": b["x"].astype(np.float64)}, "dtype"),
    ])
    def test_corrupt_batch_skipped_with_reason(self, events, mutate,
                                               reason_word):
        bad = mutate(_clean_batch())
        g = rz.GuardedIterator(iter([_clean_batch(0), bad, _clean_batch(2)]),
                               spec=rz.spec_of(_clean_batch()))
        out = list(g)
        assert len(out) == 2 and g.skipped == 1
        [skip] = events("batch_skipped")
        assert reason_word in skip["reasons"][0]
        assert "'x'" in skip["reasons"][0]  # the leaf is named

    def test_structure_mismatch_skipped(self):
        g = rz.GuardedIterator(iter([{"z": np.zeros((2, 3), np.float32)}]),
                               spec=rz.spec_of(_clean_batch()),
                               skip_budget=1)
        with pytest.raises(StopIteration):
            next(g)
        assert g.skipped == 1

    def test_skip_budget_exceeded_raises(self):
        bads = [{**_clean_batch(), "x": np.full((2, 3), np.nan, np.float32)}
                for _ in range(3)]
        g = rz.GuardedIterator(iter(bads), spec=rz.spec_of(_clean_batch()),
                               skip_budget=1)
        with pytest.raises(rz.SkipBudgetExceeded) as ei:
            next(g)
        assert ei.value.skipped == 2 and ei.value.budget == 1

    def test_stall_timeout_raises(self, events):
        clock = FakeClock()

        def slow_source():
            clock.advance(5.0)  # the fetch itself "takes" 5 s
            yield _clean_batch()

        g = rz.GuardedIterator(slow_source(), stall_timeout_s=1.0,
                               clock=clock)
        with pytest.raises(rz.DataStallError):
            next(g)
        [ev] = events("data_stall")
        assert ev["fetch_s"] == pytest.approx(5.0)

    def test_stalled_batch_is_redelivered_not_lost(self):
        """The stall raise happens AFTER the producer delivered — the
        late batch must come back on the next call, or a chronically
        slow producer silently loses data with no budget accounting."""
        clock = FakeClock()

        def source():
            for i in range(3):
                clock.advance(5.0 if i == 1 else 0.0)
                yield _clean_batch(i)

        g = rz.GuardedIterator(source(), stall_timeout_s=1.0, clock=clock)
        _tree_equal(next(g), _clean_batch(0))
        with pytest.raises(rz.DataStallError):
            next(g)
        _tree_equal(next(g), _clean_batch(1))  # the late batch, redelivered
        _tree_equal(next(g), _clean_batch(2))
        assert g.delivered == 3 and g.skipped == 0

    def test_spec_locks_to_first_batch_when_omitted(self):
        g = rz.GuardedIterator(iter([_clean_batch(0), _clean_batch(1),
                                     {**_clean_batch(),
                                      "x": np.zeros((9, 9), np.float32)}]))
        assert next(g) is not None
        assert next(g) is not None
        with pytest.raises(StopIteration):  # third batch violates the spec
            next(g)
        assert g.skipped == 1

    def test_check_finite_false_admits_nan(self):
        bad = {**_clean_batch(), "x": np.full((2, 3), np.nan, np.float32)}
        g = rz.GuardedIterator(iter([bad]), spec=rz.spec_of(_clean_batch()),
                               check_finite=False)
        assert np.isnan(next(g)["x"]).all()

    def test_degenerate_arguments_rejected(self):
        with pytest.raises(ValueError):
            rz.GuardedIterator(iter([]), skip_budget=-1)
        with pytest.raises(ValueError):
            rz.GuardedIterator(iter([]), stall_timeout_s=0.0)


# --------------------------------------------------------------------------
# supervisor-domain fault injection
# --------------------------------------------------------------------------

class TestSupervisorFaults:
    def test_slow_step_stalls_only_configured_steps(self):
        slept = []
        slow = rz.SlowStep((3,), 0.7, sleep=slept.append)
        for i in range(5):
            slow(i)
        assert slept == [0.7]

    def test_flaky_iterator_fails_n_then_succeeds_without_consuming(self):
        fl = rz.FlakyIterator(iter([10, 11, 12]), fail_at=(1,), failures=2,
                              exc_type=ConnectionError)
        got, failures = [], 0
        while True:
            try:
                got.append(next(fl))
            except ConnectionError:
                failures += 1
            except StopIteration:
                break
        assert got == [10, 11, 12]  # nothing lost, nothing reordered
        assert failures == 2

    def test_corrupt_batch_inserts_copy_preserving_clean_stream(self):
        clean = [{"x": np.full((3, 2), float(i), np.float32)}
                 for i in range(4)]
        cb = rz.CorruptBatch(iter(clean), at=(2,), mode="nan", seed=5)
        out = list(cb)
        assert len(out) == 5  # one inserted corrupt copy
        assert np.isnan(out[2]["x"]).any()  # the insert, at clean index 2
        # the clean stream is intact and untouched
        for got, want in zip([out[0], out[1], out[3], out[4]], clean):
            np.testing.assert_array_equal(got["x"], np.asarray(want["x"]))

    def test_corrupt_batch_modes_are_guard_detectable(self):
        spec = rz.spec_of({"x": np.zeros((3, 2), np.float32)})
        for mode in ("nan", "shape", "dtype"):
            cb = rz.CorruptBatch(
                iter([{"x": np.zeros((3, 2), np.float32)}]), at=(0,),
                mode=mode)
            corrupted = next(cb)
            assert rz.validate_batch(corrupted, spec), mode

    def test_corrupt_batch_rejects_unknown_mode(self):
        with pytest.raises(ValueError, match="mode"):
            rz.CorruptBatch(iter([]), mode="gamma-ray")

    def test_corrupt_batch_raises_when_nothing_to_corrupt(self):
        # nan mode needs a floating leaf; an int-only batch is a plan
        # mismatch, not a silent clean-copy insert that desyncs the stream
        cb = rz.CorruptBatch(
            iter([{"y": np.zeros((2,), np.int32)}]), at=(0,), mode="nan")
        with pytest.raises(ValueError, match="no floating-point"):
            next(cb)


# --------------------------------------------------------------------------
# checkpoint-manager retry wiring
# --------------------------------------------------------------------------

class TestCheckpointManagerRetry:
    def test_save_retries_transient_io(self, tmp_path, monkeypatch, events):
        from apex_tpu.resilience import checkpoint as ckpt

        real = ckpt.save_checkpoint
        calls = []

        def flaky(*a, **kw):
            calls.append(1)
            if len(calls) < 3:
                raise OSError("disk blip")
            return real(*a, **kw)

        monkeypatch.setattr(ckpt, "save_checkpoint", flaky)
        mgr = rz.CheckpointManager(
            str(tmp_path), retry=rz.RetryPolicy(base_delay_s=0.001))
        path = mgr.save(0, {"a": jnp.ones((2,))})
        rz.validate_checkpoint(path)
        assert len(calls) == 3
        assert len(events("retry_attempt")) == 2

    def test_restore_does_not_retry_checkpoint_errors(self, tmp_path,
                                                      monkeypatch):
        from apex_tpu.resilience import checkpoint as ckpt

        calls = []
        real = ckpt.restore_checkpoint

        def counting(*a, **kw):
            calls.append(1)
            return real(*a, **kw)

        monkeypatch.setattr(ckpt, "restore_checkpoint", counting)
        mgr = rz.CheckpointManager(
            str(tmp_path), retry=rz.RetryPolicy(base_delay_s=0.001))
        with pytest.raises(rz.CheckpointError):  # deterministic: no retry
            mgr.restore(like={"a": jnp.ones((2,))})
        assert len(calls) == 1

    def test_no_policy_means_no_wrapping(self, tmp_path):
        mgr = rz.CheckpointManager(str(tmp_path))
        mgr.save(0, {"a": jnp.ones((2,))})
        restored, step = mgr.restore(like={"a": jnp.zeros((2,))})
        assert step == 0

    def test_restore_retries_transient_read_blip_from_newest(
            self, tmp_path, monkeypatch, events):
        # an OSError mid-read of a perfectly good newest checkpoint must
        # engage the retry, not be wrapped into CheckpointError and make
        # the fallback walk silently resume an OLDER step
        from apex_tpu.resilience import checkpoint as ckpt

        mgr = rz.CheckpointManager(
            str(tmp_path), retry=rz.RetryPolicy(base_delay_s=0.001))
        mgr.save(0, {"a": jnp.zeros((2,))})
        mgr.save(1, {"a": jnp.ones((2,))})

        real = ckpt._read_record
        calls = []

        def blips_once(*a, **kw):
            calls.append(1)
            if len(calls) == 1:
                raise OSError("Connection reset by peer")
            return real(*a, **kw)

        monkeypatch.setattr(ckpt, "_read_record", blips_once)
        restored, step = mgr.restore(like={"a": jnp.zeros((2,))})
        assert step == 1  # newest, not the pre-blip fallback
        assert len(events("retry_attempt")) == 1
        assert events("checkpoint_rejected") == []

    def test_unreadable_newest_manifest_still_falls_back(self, tmp_path):
        # a deterministic OSError on the manifest PROBE (not mid-payload)
        # rejects the candidate: the walk must reach the older valid step
        mgr = rz.CheckpointManager(str(tmp_path))
        mgr.save(0, {"a": jnp.zeros((2,))})
        p1 = mgr.save(1, {"a": jnp.ones((2,))})
        manifest = os.path.join(p1, "manifest.json")
        os.remove(manifest)
        os.mkdir(manifest)  # open() -> IsADirectoryError, not FileNotFound
        restored, step = mgr.restore(like={"a": jnp.zeros((2,))})
        assert step == 0

    def test_marker_text_inside_checkpoint_error_is_not_transient(self):
        from apex_tpu.resilience.retry import is_transient

        e = rz.CheckpointError(
            "no valid checkpoint under '/ckpts'; rejected: "
            '["OSError: [Errno 104] Connection reset by peer"]')
        assert not is_transient(e, rz.RetryPolicy())


# --------------------------------------------------------------------------
# escalation policy
# --------------------------------------------------------------------------

def _fast_config(**kw):
    kw.setdefault("step_deadline_s", 30.0)
    kw.setdefault("poll_interval_s", 5.0)
    kw.setdefault("retry", rz.RetryPolicy(max_attempts=3, base_delay_s=0.0))
    return rz.SupervisorConfig(**kw)


class TestEscalation:
    def test_failures_below_threshold_do_not_abort(self):
        sup = rz.TrainingSupervisor(
            None, _fast_config(max_consecutive_failures=3))
        sup.record_failure(0, {}, OSError("x"))
        sup.record_failure(1, {}, OSError("x"))
        assert sup.consecutive_failures == 2
        sup.record_success()
        assert sup.consecutive_failures == 0

    def test_threshold_escalates_with_validated_checkpoint(self, tmp_path,
                                                           events):
        mgr = rz.CheckpointManager(str(tmp_path))
        sup = rz.TrainingSupervisor(
            mgr, _fast_config(max_consecutive_failures=1))
        state = {"w": jnp.arange(4.0)}
        with pytest.raises(rz.TrainingAborted) as ei:
            sup.record_failure(9, state, rz.StepDeadlineExceeded(9, 1.0, 2.0))
        ab = ei.value
        assert ab.step == 9 and ab.checkpoint_path is not None
        rz.validate_checkpoint(ab.checkpoint_path)
        restored, step = mgr.restore(like={"w": jnp.zeros(4)})
        assert step == 9
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.arange(4.0))
        [abort] = events("supervisor_abort")
        assert abort["checkpoint"] == ab.checkpoint_path
        assert abort["checkpoint_error"] is None

    def test_abort_survives_unwritable_checkpoint(self, events):
        mgr = rz.CheckpointManager("/proc/definitely/not/writable")
        sup = rz.TrainingSupervisor(
            mgr, _fast_config(max_consecutive_failures=1), sleep=lambda s: None)
        with pytest.raises(rz.TrainingAborted) as ei:
            sup.record_failure(3, {"w": jnp.ones(2)}, OSError("x"))
        assert ei.value.checkpoint_path is None
        [abort] = events("supervisor_abort")
        assert abort["checkpoint_error"] is not None

    def test_degenerate_config_rejected(self):
        with pytest.raises(ValueError):
            rz.SupervisorConfig(max_consecutive_failures=0)
        with pytest.raises(ValueError):
            rz.SupervisorConfig(checkpoint_every=0)
        with pytest.raises(ValueError):
            rz.SupervisorConfig(step_deadline_s=-1.0)


class TestSupervisedRun:
    def test_empty_iterator_completes_nothing(self):
        sup = rz.TrainingSupervisor(None, _fast_config())
        state, last = sup.run(lambda s, b, i: s, {"x": 0}, iter([]),
                              num_steps=5)
        assert last == -1 and state == {"x": 0}

    def test_flaky_fetch_is_recovered_without_failure_accounting(self):
        sup = rz.TrainingSupervisor(None, _fast_config(), sleep=lambda s: None)
        src = rz.FlakyIterator(iter(range(4)), fail_at=(1,), failures=2)
        seen = []
        state, last = sup.run(lambda s, b, i: seen.append((i, b)) or s,
                              None, src, num_steps=4)
        assert seen == [(0, 0), (1, 1), (2, 2), (3, 3)]
        assert last == 3 and sup.consecutive_failures == 0

    def test_unrelated_step_errors_propagate_unabsorbed(self):
        sup = rz.TrainingSupervisor(None, _fast_config())

        def bad_step(state, batch, step):
            raise ZeroDivisionError("model bug, not infrastructure")

        with pytest.raises(ZeroDivisionError):
            sup.run(bad_step, None, iter(range(3)), num_steps=3)

    def test_checkpoint_save_exhaustion_counts_as_failure(self, tmp_path,
                                                          monkeypatch):
        from apex_tpu.resilience import checkpoint as ckpt

        monkeypatch.setattr(
            ckpt, "save_checkpoint",
            lambda *a, **kw: (_ for _ in ()).throw(OSError("disk gone")))
        mgr = rz.CheckpointManager(str(tmp_path))
        sup = rz.TrainingSupervisor(
            mgr, _fast_config(max_consecutive_failures=1),
            sleep=lambda s: None)
        with pytest.raises(rz.TrainingAborted) as ei:
            sup.run(lambda s, b, i: s, {"x": jnp.ones(2)}, iter(range(3)),
                    num_steps=3)
        # the emergency checkpoint cannot be written either — abort still
        # happens, carrying no checkpoint path
        assert ei.value.checkpoint_path is None

    def test_fetch_failure_escalation_checkpoints_completed_step(
            self, tmp_path):
        """When a STEP's fetch fails, the state still predates that step
        — the emergency checkpoint must carry the completed step's label,
        or the documented resume (restored_step + 1) silently skips the
        step that never ran."""
        class OneGoodThenBroken:
            def __init__(self):
                self.n = 0

            def __iter__(self):
                return self

            def __next__(self):
                self.n += 1
                if self.n == 1:
                    return 1.0
                raise OSError("producer gone")

        mgr = rz.CheckpointManager(str(tmp_path))
        sup = rz.TrainingSupervisor(
            mgr, _fast_config(max_consecutive_failures=1, checkpoint_every=5),
            sleep=lambda s: None)
        with pytest.raises(rz.TrainingAborted) as ei:
            sup.run(lambda s, b, i: {"w": s["w"] + b}, {"w": jnp.zeros(2)},
                    OneGoodThenBroken(), num_steps=5)
        assert ei.value.step == 1  # the step whose fetch failed...
        restored, got = mgr.restore(like={"w": jnp.zeros(2)})
        assert got == 0  # ...but the checkpoint is the state AFTER step 0
        np.testing.assert_array_equal(np.asarray(restored["w"]), np.ones(2))
        # the resume recipe (got + 1) therefore re-attempts step 1

    def test_fetch_failure_before_any_step_checkpoints_initial_state(
            self, tmp_path):
        class Broken:
            def __iter__(self):
                return self

            def __next__(self):
                raise OSError("producer gone")

        mgr = rz.CheckpointManager(str(tmp_path))
        sup = rz.TrainingSupervisor(
            mgr, _fast_config(max_consecutive_failures=1, checkpoint_every=5),
            sleep=lambda s: None)
        with pytest.raises(rz.TrainingAborted):
            sup.run(lambda s, b, i: s, {"w": jnp.full(2, 7.0)}, Broken(),
                    num_steps=5)
        restored, got = mgr.restore(like={"w": jnp.zeros(2)})
        assert got == -1  # pre-first-step sentinel: resume starts at 0
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.full(2, 7.0))

    def test_manager_retry_policy_is_not_nested(self, tmp_path, monkeypatch):
        # the documented recipe sets retry on BOTH the manager and the
        # supervisor config; the supervisor must defer to the manager's
        # loop, not multiply attempts to max_attempts**2 per save
        from apex_tpu.resilience import checkpoint as ckpt

        calls = []

        def failing_save(*a, **kw):
            calls.append(1)
            raise OSError("disk gone")

        monkeypatch.setattr(ckpt, "save_checkpoint", failing_save)
        mgr = rz.CheckpointManager(
            str(tmp_path),
            retry=rz.RetryPolicy(max_attempts=2, base_delay_s=0.0))
        sup = rz.TrainingSupervisor(
            mgr, _fast_config(max_consecutive_failures=1),
            sleep=lambda s: None)
        with pytest.raises(rz.TrainingAborted):
            sup.run(lambda s, b, i: s, {"x": jnp.ones(2)}, iter(range(3)),
                    num_steps=3)
        # 2 attempts for the periodic save + 2 for the emergency save —
        # the supervisor's own 3-attempt policy never wrapped either
        assert len(calls) == 4


# --------------------------------------------------------------------------
# THE acceptance run (ISSUE 2): flaky fetch + corrupt batch + slow step
# under a deadline -> retry, skip, watchdog, emergency checkpoint,
# bit-identical resume.  JAX_PLATFORMS=cpu; no sleep longer than ~1 s.
# --------------------------------------------------------------------------

N_STEPS = 10
FLAKY_AT = 2      # fetch index that fails transiently (twice)
CORRUPT_AT = 4    # clean index that gets a corrupted copy inserted
SLOW_AT = 6       # step that stalls past the deadline
DEADLINE_S = 0.2
SLOW_S = 0.6


def _build_update():
    params = {"w": jnp.full((6, 6), 0.3, jnp.float32),
              "b": jnp.zeros((6,), jnp.float32)}
    opt = FusedAdam(lr=5e-2)

    def loss_fn(p, batch):
        pred = jnp.tanh(batch @ p["w"]) + p["b"]
        return jnp.mean((pred - 1.0) ** 2)

    @jax.jit
    def update(state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state["params"], batch)
        new_p, new_o = opt.step(grads, state["params"], state["opt"])
        return {"params": new_p, "opt": new_o}, loss

    state = {"params": params, "opt": opt.init(params)}
    # pre-warm the compile OUTSIDE any watchdog window: compilation cost
    # is not step time, and the acceptance deadline is 200 ms
    update(state, jnp.zeros((4, 6), jnp.float32))
    return state, update


def _batches():
    key = jax.random.PRNGKey(0)
    return [jax.random.normal(jax.random.fold_in(key, i), (4, 6))
            for i in range(N_STEPS)]


def _make_step_fn(update, losses, slow=None):
    def step_fn(state, batch, step):
        if slow is not None:
            slow(step)
        new_state, loss = update(state, batch)
        losses[step] = float(loss)
        return new_state

    return step_fn


def test_acceptance_faulted_run_degrades_then_resumes_bit_identically(
        tmp_path, events):
    batches = _batches()

    # ---- reference: uninterrupted supervised run
    ref_losses = {}
    ref_state, update = _build_update()
    ref_mgr = rz.CheckpointManager(str(tmp_path / "ref"), keep=N_STEPS)
    ref_sup = rz.TrainingSupervisor(ref_mgr, _fast_config())
    ref_final, ref_last = ref_sup.run(
        _make_step_fn(update, ref_losses), ref_state, iter(batches),
        num_steps=N_STEPS)
    assert ref_last == N_STEPS - 1
    assert sorted(ref_losses) == list(range(N_STEPS))

    # ---- victim: flaky fetch + corrupt batch + slow step under deadline
    run_losses = {}
    init_state, update_b = _build_update()
    hb_path = str(tmp_path / "heartbeat.json")
    stream = rz.GuardedIterator(
        rz.CorruptBatch(
            rz.FlakyIterator(iter(batches), fail_at=(FLAKY_AT,), failures=2),
            at=(CORRUPT_AT,), mode="nan", seed=7),
        spec=rz.spec_of(batches[0]), skip_budget=2)
    cfg = rz.SupervisorConfig(
        step_deadline_s=DEADLINE_S, poll_interval_s=0.02,
        max_consecutive_failures=1, checkpoint_every=1,
        heartbeat_path=hb_path,
        retry=rz.RetryPolicy(max_attempts=4, base_delay_s=0.001,
                             max_delay_s=0.01))
    mgr = rz.CheckpointManager(str(tmp_path / "victim"), keep=3)
    sup = rz.TrainingSupervisor(mgr, cfg)
    with pytest.raises(rz.TrainingAborted) as ei:
        sup.run(_make_step_fn(update_b, run_losses, slow=rz.SlowStep(
            (SLOW_AT,), SLOW_S)), init_state, stream, num_steps=N_STEPS)
    aborted = ei.value

    # every recovery path fired, each exactly as planned:
    assert len(events("retry_attempt")) == 2          # flaky fetch, twice
    assert len(events("retry_recovered")) == 1
    assert stream.skipped == 1                        # corrupt copy dropped
    assert len(events("batch_skipped")) == 1
    assert len(events("watchdog_stall")) == 1         # the slow step
    assert len(events("supervisor_abort")) == 1
    # the slow step COMPLETED (late): its loss was computed and recorded
    assert sorted(run_losses) == list(range(SLOW_AT + 1))

    # graceful degradation: validated emergency checkpoint at the abort
    # step, recorded in the heartbeat for the external orchestrator
    assert aborted.step == SLOW_AT
    assert aborted.checkpoint_path is not None
    rz.validate_checkpoint(aborted.checkpoint_path)
    hb = rz.read_heartbeat(hb_path)
    assert hb["step"] == SLOW_AT
    assert hb["ckpt_path"] == aborted.checkpoint_path

    # ---- restart: resume from the emergency checkpoint, finish clean
    resume_template, update_c = _build_update()
    resumed, resume_step = mgr.restore(like=resume_template)
    assert resume_step == SLOW_AT
    sup2 = rz.TrainingSupervisor(mgr, _fast_config())
    final, last = sup2.run(
        _make_step_fn(update_c, run_losses), resumed,
        iter(batches[SLOW_AT + 1:]), num_steps=N_STEPS,
        start_step=SLOW_AT + 1)
    assert last == N_STEPS - 1

    # bit-identical to the uninterrupted reference: every recorded loss
    # and every leaf of the final state
    assert sorted(run_losses) == list(range(N_STEPS))
    for i in range(N_STEPS):
        assert run_losses[i] == ref_losses[i], (
            f"loss diverged at step {i}: {run_losses[i]} != {ref_losses[i]}")
    _tree_equal(final, ref_final)


# --------------------------------------------------------------------------
# asynchronous checkpoint pipeline under the supervisor (ISSUE 8):
# snapshot-only blocking, backpressure, failed-write ladder, emergency/
# shutdown joins, consistency veto — and THE acceptance run: an async-
# interrupted run resumes bit-identically through the existing harness
# --------------------------------------------------------------------------


def _accum_step(state, batch, step):
    return {"w": state["w"] + batch, "n": state["n"] + 1}


def _accum_state():
    return {"w": jnp.zeros((4, 4), jnp.float32), "n": jnp.int32(0)}


def _accum_batches(n):
    return [jnp.full((4, 4), float(i + 1), jnp.float32) for i in range(n)]


def _step_dirs(root):
    return sorted(d for d in os.listdir(root) if d.startswith("step_"))


def _dir_bytes(path):
    return {name: open(os.path.join(path, name), "rb").read()
            for name in sorted(os.listdir(path))}


class TestAsyncSupervisor:
    def test_async_run_matches_sync_run_byte_for_byte(self, tmp_path):
        """async_save=True must change WHEN the write happens, not one
        byte of what lands on disk — every periodic step dir compares
        equal to the sync run's, and the final states match."""
        roots = {"sync": str(tmp_path / "sync"),
                 "async": str(tmp_path / "async")}
        finals = {}
        for mode, root in roots.items():
            sup = rz.TrainingSupervisor(
                rz.CheckpointManager(root, keep=10),
                _fast_config(checkpoint_every=2,
                             async_save=(mode == "async")))
            finals[mode], last = sup.run(
                _accum_step, _accum_state(), _accum_batches(6), num_steps=6)
            assert last == 5
        _tree_equal(finals["sync"], finals["async"])
        assert _step_dirs(roots["sync"]) == _step_dirs(roots["async"])
        for d in _step_dirs(roots["sync"]):
            assert _dir_bytes(os.path.join(roots["sync"], d)) == \
                _dir_bytes(os.path.join(roots["async"], d)), d

    def test_heartbeat_pointer_advances_only_on_committed_dirs(
            self, tmp_path):
        hb = str(tmp_path / "hb.json")
        root = str(tmp_path / "ckpts")
        sup = rz.TrainingSupervisor(
            rz.CheckpointManager(root, keep=10),
            _fast_config(checkpoint_every=1, async_save=True,
                         heartbeat_path=hb))
        sup.run(_accum_step, _accum_state(), _accum_batches(4), num_steps=4)
        beat = rz.read_heartbeat(hb)
        # the final drain published the LAST committed step's path
        assert beat["ckpt_path"] is not None
        assert beat["ckpt_path"].endswith("step_0000000003")
        rz.validate_checkpoint(beat["ckpt_path"])

    def test_failed_background_write_joins_failure_ladder(
            self, tmp_path, events):
        """A background write that exhausts its transient retries
        surfaces at the next step boundary as one supervisor failure —
        the same accounting a failed synchronous save gets."""
        sup = rz.TrainingSupervisor(
            rz.CheckpointManager(str(tmp_path)),
            _fast_config(checkpoint_every=1, async_save=True,
                         max_consecutive_failures=50))
        # every write attempt dies on a transient error (hook runs per
        # record inside the write machinery, under config.retry)
        def bad_io(progress):
            raise OSError("injected transient write failure")

        sup._async.progress_hook = bad_io
        state, last = sup.run(_accum_step, _accum_state(),
                              _accum_batches(3), num_steps=3)
        assert last == 2  # the run survived: writes failed, steps didn't
        fails = events("supervisor_failure")
        assert fails and all(f["failure"] == "RetryExhausted"
                             for f in fails)
        assert not _step_dirs(str(tmp_path))

    def test_escalation_joins_inflight_write_then_checkpoints(
            self, tmp_path):
        """Emergency checkpointing must join the in-flight background
        write first (single-writer root) — both the periodic dir and the
        emergency dir end up committed and valid."""
        sup = rz.TrainingSupervisor(
            rz.CheckpointManager(str(tmp_path), keep=10),
            _fast_config(checkpoint_every=1, async_save=True,
                         max_consecutive_failures=1))
        sup._async.progress_hook = lambda p: time.sleep(0.2)  # slow writer
        fut = sup._async.save(0, {"w": jnp.arange(4.0)})
        assert not fut.done()
        with pytest.raises(rz.TrainingAborted) as ei:
            sup.record_failure(1, {"w": jnp.ones(4)},
                               rz.StepDeadlineExceeded(1, 1.0, 2.0))
        # the join happened before the emergency save: the periodic
        # write committed (not swept/aborted), the emergency dir too
        assert fut.done() and fut.error is None
        assert _step_dirs(str(tmp_path)) == ["step_0000000000",
                                             "step_0000000001"]
        rz.validate_checkpoint(ei.value.checkpoint_path)

    def test_consistency_failure_vetoes_inflight_commit(
            self, tmp_path, events):
        """ISSUE 8: a failed consistency pass must ALSO veto the write
        already in the air — an untrusted lineage never becomes
        latest_valid_step, not even through a commit scheduled before
        the pass ran."""
        class FlakyConsistency:
            calls = 0

            def check(self, state, step):
                type(self).calls += 1
                if type(self).calls == 1:
                    raise rz.ReplicaDesyncError(step, [])
                return state

        sup = rz.TrainingSupervisor(
            rz.CheckpointManager(str(tmp_path), keep=10),
            _fast_config(checkpoint_every=1, async_save=True,
                         consistency_check_interval=2,
                         max_consecutive_failures=50),
            consistency=FlakyConsistency())
        sup._async.progress_hook = lambda p: time.sleep(0.25)  # in flight
        state, last = sup.run(_accum_step, _accum_state(),
                              _accum_batches(6), num_steps=6)
        assert last == 5
        dirs = _step_dirs(str(tmp_path))
        # step 0's write was in flight when the step-1 pass failed: the
        # veto killed it.  Steps 1 and 2 never scheduled (untrusted);
        # the step-3 pass re-proved the state clean, so 3.. committed.
        assert "step_0000000000" not in dirs
        assert "step_0000000001" not in dirs
        assert "step_0000000002" not in dirs
        assert {"step_0000000003", "step_0000000004",
                "step_0000000005"} <= set(dirs)
        assert events("checkpoint_commit_vetoed")
        assert rz.latest_valid_step(str(tmp_path)) == 5

    def test_acceptance_async_interrupted_run_resumes_bit_identically(
            self, tmp_path):
        """THE ISSUE-8 acceptance run: preempt an async_save run mid-
        flight, restart from latest_valid_step through the normal
        restore path, finish — the final state is bit-identical to an
        uninterrupted SYNC run, and every surviving step dir is byte-
        identical to the sync run's."""
        n = 8
        sync_root = str(tmp_path / "sync")
        sup = rz.TrainingSupervisor(
            rz.CheckpointManager(sync_root, keep=20),
            _fast_config(checkpoint_every=1))
        ref_final, _ = sup.run(_accum_step, _accum_state(),
                               _accum_batches(n), num_steps=n)

        async_root = str(tmp_path / "async")
        mgr = rz.CheckpointManager(async_root, keep=20)
        injector = rz.FaultInjector(rz.FaultPlan(preempt_steps=(5,)))

        def preempting_step(state, batch, step):
            injector.check_preemption(step)
            return _accum_step(state, batch, step)

        sup1 = rz.TrainingSupervisor(
            mgr, _fast_config(checkpoint_every=1, async_save=True))
        with pytest.raises(rz.SimulatedPreemption):
            sup1.run(preempting_step, _accum_state(), _accum_batches(n),
                     num_steps=n)
        # restart: newest VALID checkpoint (an in-flight write at the
        # kill either committed whole or is invisible), resume async
        resume_state, last = mgr.restore(like=_accum_state())
        assert last == rz.latest_valid_step(async_root) == 4
        sup2 = rz.TrainingSupervisor(
            mgr, _fast_config(checkpoint_every=1, async_save=True))
        final, done = sup2.run(_accum_step, resume_state,
                               _accum_batches(n)[last + 1:],
                               num_steps=n, start_step=last + 1)
        assert done == n - 1
        _tree_equal(final, ref_final)
        for d in _step_dirs(async_root):
            assert _dir_bytes(os.path.join(async_root, d)) == \
                _dir_bytes(os.path.join(sync_root, d)), d

    def test_resume_pointer_advances_under_sustained_backpressure(
            self, tmp_path):
        """Write duration persistently longer than the save interval:
        every success's future is consumed by the next save's
        backpressure join (poll never sees it), and the heartbeat's
        resume pointer must STILL advance mid-run — the lossless
        last_committed record, not future harvesting, feeds the beat."""
        hb = str(tmp_path / "hb.json")
        root = str(tmp_path / "ckpts")
        sup = rz.TrainingSupervisor(
            rz.CheckpointManager(root, keep=20),
            _fast_config(checkpoint_every=1, async_save=True,
                         heartbeat_path=hb))
        sup._async.progress_hook = lambda p: time.sleep(0.1)  # slow write
        seen = {}

        def step_fn(state, batch, step):
            if step == 3:  # mid-run, while write(2) is still in the air
                seen["beat"] = rz.read_heartbeat(hb)
            return _accum_step(state, batch, step)

        sup.run(step_fn, _accum_state(), _accum_batches(4), num_steps=4)
        assert seen["beat"]["ckpt_path"] is not None, (
            "resume pointer never advanced while writes overlapped saves")
        rz.validate_checkpoint(seen["beat"]["ckpt_path"])
        assert len(_step_dirs(root)) == 4  # every periodic save committed
        final = rz.read_heartbeat(hb)
        assert final["ckpt_path"].endswith("step_0000000003")

    def test_shutdown_drain_never_regresses_emergency_pointer(
            self, tmp_path):
        """After escalate() publishes the emergency checkpoint, the
        shutdown drain must not overwrite the heartbeat's resume
        pointer with an OLDER async commit."""
        hb = str(tmp_path / "hb.json")
        sup = rz.TrainingSupervisor(
            rz.CheckpointManager(str(tmp_path / "c"), keep=20),
            _fast_config(checkpoint_every=1, async_save=True,
                         max_consecutive_failures=1,
                         heartbeat_path=hb))
        # an async commit for step 0, then escalation at step 6
        sup._async.save(0, {"w": jnp.arange(4.0)}).result()
        with pytest.raises(rz.TrainingAborted) as ei:
            sup.record_failure(6, {"w": jnp.ones(4)},
                               rz.StepDeadlineExceeded(6, 1.0, 2.0))
        assert ei.value.checkpoint_path.endswith("step_0000000006")
        # the drain run()'s finally performs: must be a no-op here
        sup._async.wait(timeout=5.0)
        sup._beat_if_newer(6)
        beat = rz.read_heartbeat(hb)
        assert beat["ckpt_path"] == ei.value.checkpoint_path
