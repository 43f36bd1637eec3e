"""Observability subsystem tests (ISSUE 6 tentpole).

Registry correctness (labels, bucket edges, concurrent updates from
threads), Prometheus exposition golden text, JSON export, span
nesting/ordering in exported Chrome trace JSON, the emit_event sink
registry (byte-identical default output), the event → metric bridge —
and THE acceptance runs: a fault-injected supervisor run and a
continuous-batching serving drain, each producing a Prometheus snapshot
whose counters exactly match the injected fault / request counts plus a
loadable Chrome trace, ending with the no-exporter overhead budget.
"""

import collections
import json
import logging
import math
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import _logging
from apex_tpu import resilience as rz
from apex_tpu.obs import bridge, metrics, trace
from apex_tpu.obs.metrics import (
    LATENCY_BUCKETS_S,
    MetricsRegistry,
    REGISTRY,
)
from apex_tpu.transformer.pipeline_parallel._timers import Timers


@pytest.fixture
def reg():
    """A private registry — unit tests never touch the process default."""
    return MetricsRegistry()


def _reject_constant(name):
    raise AssertionError(f"non-strict JSON constant {name!r} in export")


@pytest.fixture
def events():
    """Capture structured events BOTH ways the new fan-out offers: the
    parsed log lines (proving the default sink) and a direct sink."""
    sunk = []
    _logging.add_event_sink(sunk.append)
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

    get.sunk = sunk
    yield get
    logger.removeHandler(handler)
    _logging.remove_event_sink(sunk.append)


# --------------------------------------------------------------------------
# registry correctness
# --------------------------------------------------------------------------

class TestCounter:
    def test_inc_and_value(self, reg):
        c = reg.counter("apex_t_total", "h")
        assert c.value() == 0.0
        c.inc()
        c.inc(2.5)
        assert c.value() == 3.5

    def test_negative_increment_rejected(self, reg):
        c = reg.counter("apex_t_total")
        with pytest.raises(ValueError, match=">= 0"):
            c.inc(-1)
        # NaN slips past a naive `< 0` check, and +Inf past a naive
        # `>= 0` one — either would poison the running total for the
        # life of the process
        with pytest.raises(ValueError, match="finite"):
            c.inc(float("nan"))
        with pytest.raises(ValueError, match="finite"):
            c.inc(float("inf"))
        assert c.value() == 0.0

    def test_labeled_series_are_independent(self, reg):
        c = reg.counter("apex_t_total", "h", ("kind", "site"))
        c.inc(kind="a", site="x")
        c.inc(3, kind="b", site="x")
        assert c.value(kind="a", site="x") == 1.0
        assert c.value(kind="b", site="x") == 3.0
        assert c.value(kind="a", site="y") == 0.0
        assert c.series_count() == 2

    def test_wrong_labels_rejected(self, reg):
        c = reg.counter("apex_t_total", "h", ("kind",))
        with pytest.raises(ValueError, match="labelnames"):
            c.inc()  # missing label
        with pytest.raises(ValueError, match="labelnames"):
            c.inc(kind="a", extra="b")

    def test_name_conventions_enforced_at_registration(self, reg):
        for bad in ("step_total", "apex_BadCase", "apex-dash", "apex_"):
            # "apex_" alone fails [a-z0-9_]+ needing >= 1 char after apex_
            if bad == "apex_":
                continue
            with pytest.raises(ValueError, match="must match"):
                reg.counter(bad)
        with pytest.raises(ValueError, match="invalid label name"):
            reg.counter("apex_ok_total", "h", ("BadLabel",))

    def test_reregistration_same_signature_returns_same_object(self, reg):
        a = reg.counter("apex_t_total", "h", ("k",))
        b = reg.counter("apex_t_total", "other help", ("k",))
        assert a is b

    def test_conflicting_reregistration_raises(self, reg):
        reg.counter("apex_t_total", "h", ("k",))
        with pytest.raises(ValueError, match="conflicting"):
            reg.counter("apex_t_total", "h", ("other",))
        with pytest.raises(ValueError, match="conflicting"):
            reg.gauge("apex_t_total")


class TestGauge:
    def test_set_inc_dec(self, reg):
        g = reg.gauge("apex_t_depth")
        g.set(5)
        g.inc()
        g.dec(2.5)
        assert g.value() == 3.5

    def test_set_function_evaluates_at_read_time(self, reg):
        g = reg.gauge("apex_t_age")
        box = {"v": 1.0}
        g.set_function(lambda: box["v"])
        assert g.value() == 1.0
        box["v"] = 42.0
        assert g.value() == 42.0
        snap = reg.snapshot()["apex_t_age"]["series"]
        assert snap == [{"labels": {}, "value": 42.0}]
        g.set_function(None)
        assert g.value() == 0.0  # unbound: back to pushed value

    def test_function_failure_exports_nan_not_crash(self, reg, tmp_path):
        g = reg.gauge("apex_t_age")
        g.set_function(lambda: 1 / 0)
        [serie] = reg.snapshot()["apex_t_age"]["series"]
        assert serie["value"] != serie["value"]  # NaN
        assert "NaN" in reg.prometheus_text()
        # the JSON export must stay STRICT-parser valid: NaN -> null
        path = str(tmp_path / "m.json")
        reg.write_json(path)
        with open(path) as f:
            loaded = json.load(f, parse_constant=_reject_constant)
        [serie] = loaded["metrics"]["apex_t_age"]["series"]
        assert serie["value"] is None


class TestHistogram:
    def test_default_buckets_are_fixed_and_log_spaced(self):
        assert len(LATENCY_BUCKETS_S) == 25
        assert LATENCY_BUCKETS_S[0] == pytest.approx(1e-4)
        assert LATENCY_BUCKETS_S[-1] == pytest.approx(1e2)
        ratios = [b / a for a, b in zip(LATENCY_BUCKETS_S,
                                        LATENCY_BUCKETS_S[1:])]
        for r in ratios:  # 4 per decade
            assert r == pytest.approx(10 ** 0.25, rel=1e-6)

    def test_bucket_edges_are_upper_inclusive(self, reg):
        h = reg.histogram("apex_t_lat_seconds", "h", buckets=(1.0, 10.0))
        h.observe(1.0)    # exactly on an edge -> that bucket (le)
        h.observe(0.5)
        h.observe(10.0)
        h.observe(11.0)   # past the last edge -> +Inf
        assert h.cumulative_counts() == (2, 3, 4)
        assert h.count() == 4
        assert h.sum() == pytest.approx(22.5)

    def test_non_finite_observations_rejected(self, reg):
        h = reg.histogram("apex_t_lat_seconds", buckets=(1.0,))
        for bad in (float("nan"), float("inf"), -float("inf")):
            with pytest.raises(ValueError, match="non-finite"):
                h.observe(bad)
        assert h.count() == 0

    def test_le_label_is_reserved_for_histograms(self, reg):
        with pytest.raises(ValueError, match="reserved"):
            reg.histogram("apex_t_lat_seconds", labelnames=("le",))
        reg.counter("apex_t_total", "le is fine elsewhere", ("le",))

    def test_degenerate_buckets_rejected(self, reg):
        with pytest.raises(ValueError, match="at least one"):
            reg.histogram("apex_t_lat_seconds", buckets=())
        with pytest.raises(ValueError, match="strictly"):
            reg.histogram("apex_t_lat_seconds", buckets=(1.0, 1.0))

    def test_conflicting_buckets_on_reregistration(self, reg):
        reg.histogram("apex_t_lat_seconds", buckets=(1.0,))
        with pytest.raises(ValueError, match="conflicting"):
            reg.histogram("apex_t_lat_seconds", buckets=(2.0,))

    def test_labeled_histogram_series(self, reg):
        h = reg.histogram("apex_t_lat_seconds", "h", ("op",),
                          buckets=(1.0,))
        h.observe(0.5, op="save")
        h.observe(2.0, op="save")
        h.observe(0.1, op="restore")
        assert h.count(op="save") == 2
        assert h.count(op="restore") == 1
        assert h.cumulative_counts(op="save") == (1, 2)


class TestHistogramQuantile:
    """ISSUE-12 satellite: bucket-interpolated ``Histogram.quantile``
    (exact at bucket edges, documented one-bucket error bound, the same
    NaN/Inf guard family as ``observe``)."""

    def test_exact_at_bucket_edges(self, reg):
        h = reg.histogram("apex_t_q_seconds", buckets=(1.0, 2.0, 4.0))
        for v in (1.0, 1.0, 2.0, 2.0):
            h.observe(v)
        # rank coincides with a cumulative count -> exactly the edge
        assert h.quantile(0.5) == 1.0
        assert h.quantile(1.0) == 2.0

    def test_interpolates_within_bucket(self, reg):
        h = reg.histogram("apex_t_q_seconds", buckets=(1.0, 2.0))
        for _ in range(4):
            h.observe(1.5)                # all in (1.0, 2.0]
        # rank q*4=2 of 4 -> halfway through the bucket's count
        assert h.quantile(0.5) == pytest.approx(1.5)
        assert h.quantile(0.25) == pytest.approx(1.25)
        # error bound: any estimate stays inside the populated bucket
        for q in (0.01, 0.5, 0.99):
            assert 1.0 <= h.quantile(q) <= 2.0

    def test_first_bucket_lower_edge_is_zero(self, reg):
        h = reg.histogram("apex_t_q_seconds", buckets=(1.0, 2.0))
        h.observe(0.5)
        h.observe(0.5)
        assert h.quantile(0.5) == pytest.approx(0.5)   # 0 + 1.0 * 1/2
        assert h.quantile(0.0) == 0.0

    def test_overflow_bucket_clamps_to_last_edge(self, reg):
        h = reg.histogram("apex_t_q_seconds", buckets=(1.0, 2.0))
        h.observe(100.0)
        assert h.quantile(0.99) == 2.0
        assert h.quantile(0.0) == 2.0     # only populated "bucket"

    def test_single_bucket_histogram(self, reg):
        h = reg.histogram("apex_t_q_seconds", buckets=(1.0,))
        h.observe(0.25)
        h.observe(0.75)
        assert h.quantile(0.5) == pytest.approx(0.5)
        assert h.quantile(1.0) == 1.0
        assert h.quantile(0.0) == 0.0

    def test_empty_histogram_is_nan(self, reg):
        h = reg.histogram("apex_t_q_seconds", buckets=(1.0,))
        assert math.isnan(h.quantile(0.5))

    def test_q_guards_match_observe_family(self, reg):
        h = reg.histogram("apex_t_q_seconds", buckets=(1.0,))
        h.observe(0.5)
        for bad in (-0.01, 1.01, float("nan"), float("inf"),
                    -float("inf")):
            with pytest.raises(ValueError, match="quantile"):
                h.quantile(bad)

    def test_labeled_series_quantiles_independent(self, reg):
        h = reg.histogram("apex_t_q_seconds", "h", ("op",),
                          buckets=(1.0, 2.0, 4.0))
        h.observe(0.5, op="a")
        h.observe(3.0, op="b")
        assert h.quantile(0.5, op="a") <= 1.0
        assert h.quantile(0.5, op="b") > 2.0
        assert math.isnan(h.quantile(0.5, op="c")
                          ) if h.count(op="c") == 0 else True

    def test_monotone_in_q(self, reg):
        h = reg.histogram("apex_t_q_seconds",
                          buckets=tuple(float(b) for b in
                                        (1, 2, 4, 8, 16)))
        rng = np.random.default_rng(0)
        for v in rng.uniform(0.1, 20.0, 200):
            h.observe(float(v))
        qs = [h.quantile(q) for q in np.linspace(0, 1, 21)]
        assert qs == sorted(qs)


class TestConcurrency:
    N_THREADS, N_OPS = 8, 5_000

    def test_concurrent_updates_are_exact(self, reg):
        c = reg.counter("apex_t_total", "h", ("t",))
        h = reg.histogram("apex_t_lat_seconds", "h", buckets=(0.5,))
        g = reg.gauge("apex_t_depth")

        def worker(tid):
            for i in range(self.N_OPS):
                c.inc(t=str(tid % 2))
                h.observe(0.25 if i % 2 else 0.75)
                g.inc()

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(self.N_THREADS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = self.N_THREADS * self.N_OPS
        assert c.value(t="0") + c.value(t="1") == total
        assert h.count() == total
        assert h.cumulative_counts() == (total // 2, total)
        assert g.value() == total

    def test_exposition_during_concurrent_writes(self, reg):
        c = reg.counter("apex_t_total")
        stop = threading.Event()

        def writer():
            while not stop.is_set():
                c.inc()

        t = threading.Thread(target=writer)
        t.start()
        try:
            for _ in range(50):
                text = reg.prometheus_text()
                assert "apex_t_total" in text
        finally:
            stop.set()
            t.join()


class TestExposition:
    def test_prometheus_golden_text(self, reg):
        c = reg.counter("apex_g_total", "help text", ("kind",))
        c.inc(kind="a")
        c.inc(2, kind="b")
        g = reg.gauge("apex_g_depth", "queue depth")
        g.set(3)
        h = reg.histogram("apex_g_lat_seconds", "latency",
                          buckets=(1.0, 10.0))
        h.observe(0.5)
        h.observe(5.0)
        h.observe(50.0)
        assert reg.prometheus_text() == (
            '# HELP apex_g_depth queue depth\n'
            '# TYPE apex_g_depth gauge\n'
            'apex_g_depth 3\n'
            '# HELP apex_g_lat_seconds latency\n'
            '# TYPE apex_g_lat_seconds histogram\n'
            'apex_g_lat_seconds_bucket{le="1"} 1\n'
            'apex_g_lat_seconds_bucket{le="10"} 2\n'
            'apex_g_lat_seconds_bucket{le="+Inf"} 3\n'
            'apex_g_lat_seconds_sum 55.5\n'
            'apex_g_lat_seconds_count 3\n'
            '# HELP apex_g_total help text\n'
            '# TYPE apex_g_total counter\n'
            'apex_g_total{kind="a"} 1\n'
            'apex_g_total{kind="b"} 2\n')

    def test_label_values_are_escaped(self, reg):
        c = reg.counter("apex_g_total", "", ("what",))
        c.inc(what='a"b\\c\nd')
        assert r'what="a\"b\\c\nd"' in reg.prometheus_text()

    def test_json_export_is_atomic_and_loadable(self, reg, tmp_path):
        c = reg.counter("apex_g_total")
        c.inc(7)
        path = str(tmp_path / "metrics.json")
        reg.write_json(path)
        with open(path) as f:
            payload = json.load(f)
        assert payload["metrics"]["apex_g_total"]["series"] == [
            {"labels": {}, "value": 7.0}]
        assert payload["time"] > 0
        # no temp litter left behind
        assert [p.name for p in tmp_path.iterdir()] == ["metrics.json"]

    def test_reset_zeroes_series_keeps_registrations(self, reg):
        c = reg.counter("apex_g_total", "h", ("k",))
        c.inc(k="a")
        g = reg.gauge("apex_g_depth")
        g.set_function(lambda: 9.0)
        reg.reset()
        assert c.value(k="a") == 0.0
        assert reg.counter("apex_g_total", "h", ("k",)) is c
        # bound functions describe live state: they survive reset
        assert g.value() == 9.0


# --------------------------------------------------------------------------
# spans -> Chrome trace JSON
# --------------------------------------------------------------------------

class TestSpans:
    def test_no_recorder_is_a_noop(self):
        assert trace.uninstall_recorder() is None or True  # park any
        with trace.span("free") as s:
            assert s is None
            assert trace.current_span() is None

    def test_nesting_parentage_and_containment(self):
        with trace.recording() as rec:
            with trace.span("outer", step=3) as outer:
                assert trace.current_span() is outer
                with trace.span("inner_a") as inner:
                    assert inner.parent_id == outer.span_id
                with trace.span("inner_b"):
                    pass
            assert trace.current_span() is None
        payload = rec.to_chrome_trace()
        # schema: loads as JSON, every event is a complete "X" event
        loaded = json.loads(json.dumps(payload))
        evs = loaded["traceEvents"]
        assert [e["name"] for e in evs] == ["outer", "inner_a", "inner_b"]
        for e in evs:
            assert e["ph"] == "X"
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
            assert e["dur"] >= 0.0
        o, a, b = evs
        assert "parent_id" not in o["args"] and o["args"]["step"] == 3
        assert a["args"]["parent_id"] == o["args"]["span_id"]
        assert b["args"]["parent_id"] == o["args"]["span_id"]
        # proper nesting: children inside the parent window, in order
        assert o["ts"] <= a["ts"] and a["ts"] + a["dur"] <= o["ts"] + o["dur"]
        assert a["ts"] + a["dur"] <= b["ts"]
        assert b["ts"] + b["dur"] <= o["ts"] + o["dur"]

    def test_span_survives_exceptions_and_still_records(self):
        with trace.recording() as rec:
            with pytest.raises(RuntimeError):
                with trace.span("doomed"):
                    raise RuntimeError("body failed")
            assert trace.current_span() is None
        assert [e["name"] for e in rec.to_chrome_trace()["traceEvents"]] \
            == ["doomed"]

    def test_attributes_and_events(self):
        with trace.recording() as rec:
            with trace.span("op", a=1) as s:
                s.set_attribute("b", "two")
                s.add_event("milestone", detail=7)
        [ev] = rec.to_chrome_trace()["traceEvents"]
        assert ev["args"]["a"] == 1 and ev["args"]["b"] == "two"
        [stamped] = ev["args"]["events"]
        assert stamped["name"] == "milestone" and stamped["detail"] == 7
        assert ev["ts"] <= stamped["ts_us"] <= ev["ts"] + ev["dur"]

    def test_threads_get_independent_span_stacks(self):
        seen = {}

        def worker():
            with trace.span("thread_side") as s:
                seen["parent"] = s.parent_id

        with trace.recording() as rec:
            with trace.span("main_side"):
                t = threading.Thread(target=worker)
                t.start()
                t.join()
        assert seen["parent"] is None  # no cross-thread parentage
        tids = {e["tid"] for e in rec.to_chrome_trace()["traceEvents"]}
        assert len(tids) == 2

    def test_export_writes_loadable_file(self, tmp_path):
        with trace.recording() as rec:
            with trace.span("op"):
                pass
        path = str(tmp_path / "trace.json")
        rec.export(path)
        with open(path) as f:
            loaded = json.load(f)
        assert loaded["traceEvents"][0]["name"] == "op"
        assert loaded["displayTimeUnit"] == "ms"

    def test_export_stays_strict_json_under_nan_attributes(self, tmp_path):
        with trace.recording() as rec:
            # NaN at top level, nested in a tuple (json serializes
            # tuples natively — the finite-walk must reach inside), and
            # a non-JSON object (degrades via default=str)
            with trace.span("diverged", loss=float("nan"),
                            grads=(float("nan"), 1.0),
                            arr=np.ones(2)) as s:
                s.add_event("blowup", delta=float("inf"))
        path = str(tmp_path / "trace.json")
        rec.export(path)
        with open(path) as f:
            loaded = json.load(f, parse_constant=_reject_constant)
        [ev] = loaded["traceEvents"]
        assert ev["args"]["loss"] is None
        assert ev["args"]["grads"] == [None, 1.0]
        assert isinstance(ev["args"]["arr"], str)
        assert ev["args"]["events"][0]["delta"] is None

    def test_recorder_caps_events_and_reports_drops(self):
        rec = trace.TraceRecorder(max_events=2)
        prev = trace.uninstall_recorder()
        trace.install_recorder(rec)
        try:
            for i in range(5):
                with trace.span("s", i=i):
                    pass
        finally:
            trace.uninstall_recorder()
            if prev is not None:
                trace.install_recorder(prev)
        assert len(rec) == 2 and rec.dropped == 3
        payload = rec.to_chrome_trace()
        # the run's BEGINNING is kept, and truncation is never silent
        assert [e["args"]["i"] for e in payload["traceEvents"]] == [0, 1]
        assert payload["otherData"] == {"dropped_events": 3,
                                        "max_events": 2}
        with pytest.raises(ValueError):
            trace.TraceRecorder(max_events=0)

    def test_recording_restores_previous_recorder(self):
        outer = trace.install_recorder()
        try:
            with trace.recording() as inner:
                with trace.span("in_window"):
                    pass
            with trace.span("after_window"):
                pass
            assert [e["name"] for e in
                    inner.to_chrome_trace()["traceEvents"]] == ["in_window"]
            assert [e["name"] for e in
                    outer.to_chrome_trace()["traceEvents"]] \
                == ["after_window"]
        finally:
            trace.uninstall_recorder()

    def test_jax_profiler_hooks_are_idempotent(self, tmp_path):
        logdir = str(tmp_path / "prof")
        # ONE profiler session covers the whole contract: the on_stall
        # adapter starts it, re-entry is refused while active, stop is
        # idempotent (start/stop cycles cost seconds on this backend)
        hook = trace.profile_on_stall(logdir)
        hook({"step": 3})
        if not trace._PROFILER_ACTIVE:
            pytest.skip("jax profiler unavailable on this backend")
        try:
            assert trace.start_jax_profiler(logdir) is False  # already on
            hook({"step": 4})  # second stall: no double start, no raise
        finally:
            assert trace.stop_jax_profiler() is True
        assert trace.stop_jax_profiler() is False  # already off


# --------------------------------------------------------------------------
# emit_event sink registry + the event -> metric bridge
# --------------------------------------------------------------------------

class TestSinkRegistry:
    def test_default_output_is_byte_identical_json(self, events):
        returned = _logging.emit_event("obs_test_probe", step=3,
                                       note="hello")
        [line] = [e for e in events()
                  if e["event"] == "obs_test_probe"]
        # the logged line parses back to exactly the returned event, and
        # the raw message is exactly the canonical dumps — the pre-PR
        # format, byte for byte
        assert line == json.loads(
            json.dumps(returned, sort_keys=True, default=str))

    def test_raw_line_matches_canonical_dumps(self):
        records = []
        handler = logging.Handler()
        handler.emit = lambda r: records.append(r.getMessage())
        logger = logging.getLogger("apex_tpu.events")
        logger.addHandler(handler)
        logger.setLevel(logging.INFO)
        try:
            returned = _logging.emit_event("obs_test_probe", a=1)
        finally:
            logger.removeHandler(handler)
        assert records == [
            json.dumps(returned, sort_keys=True, default=str)]

    def test_custom_sink_receives_every_event(self, events):
        _logging.emit_event("obs_test_probe", n=1)
        _logging.emit_event("obs_test_probe", n=2)
        mine = [e for e in events.sunk if e["event"] == "obs_test_probe"]
        assert [e["n"] for e in mine] == [1, 2]

    def test_add_is_idempotent_and_remove_unsubscribes(self):
        seen = []
        before = len(_logging.event_sinks())
        _logging.add_event_sink(seen.append)
        _logging.add_event_sink(seen.append)
        assert len(_logging.event_sinks()) == before + 1
        _logging.emit_event("obs_test_probe")
        _logging.remove_event_sink(seen.append)
        _logging.remove_event_sink(seen.append)  # no-op, no raise
        _logging.emit_event("obs_test_probe")
        assert len(seen) == 1

    def test_raising_sink_never_breaks_the_emitter(self, events):
        def bad_sink(event):
            raise RuntimeError("sink bug")

        _logging.add_event_sink(bad_sink)
        try:
            out = _logging.emit_event("obs_test_probe", n=3)
        finally:
            _logging.remove_event_sink(bad_sink)
        assert out["n"] == 3
        # the default log sink still ran
        assert [e["n"] for e in events("obs_test_probe")] == [3]

    def test_rank_info_warned_set_is_capped(self):
        saved = set(_logging._RANK_INFO_WARNED)
        _logging._RANK_INFO_WARNED.clear()
        try:
            for i in range(3 * _logging._MAX_WARNED_KEYS):
                _logging._debug_once(f"obs_cap_probe_{i}", "probe",
                                     ValueError("x"))
            assert len(_logging._RANK_INFO_WARNED) \
                == _logging._MAX_WARNED_KEYS
        finally:
            _logging._RANK_INFO_WARNED.clear()
            _logging._RANK_INFO_WARNED.update(saved)


class TestBridge:
    def test_bridge_is_installed_by_default(self):
        assert bridge.installed()

    def test_every_event_kind_is_counted(self):
        REGISTRY.reset()
        _logging.emit_event("obs_test_probe")
        _logging.emit_event("obs_test_probe")
        _logging.emit_event("obs_other_probe")
        assert bridge.EVENTS_TOTAL.value(event="obs_test_probe") == 2
        assert bridge.EVENTS_TOTAL.value(event="obs_other_probe") == 1

    def test_payload_handlers_map_measurements(self):
        REGISTRY.reset()
        _logging.emit_event("retry_attempt", what="data_fetch")
        _logging.emit_event("retry_exhausted", what="ckpt_save")
        _logging.emit_event("batch_skipped", reasons=["nan"])
        _logging.emit_event("replica_desync", leaf="w")
        _logging.emit_event("fault_injected", fault="slow_step")
        _logging.emit_event("serving_first_token", rid="r", ttft_s=0.02)
        _logging.emit_event("serving_request_finished", rid="r",
                            tokens_per_s=123.0, per_token_ms=2.0)
        assert bridge.RETRY_ATTEMPTS.value(what="data_fetch") == 1
        assert bridge.RETRY_EXHAUSTED.value(what="ckpt_save") == 1
        assert bridge.BATCHES_SKIPPED.value() == 1
        assert bridge.REPLICA_DESYNC.value() == 1
        assert bridge.FAULTS_INJECTED.value(fault="slow_step") == 1
        assert bridge.SERVING_TTFT.count() == 1
        assert bridge.SERVING_TTFT.sum() == pytest.approx(0.02)
        assert bridge.SERVING_PER_TOKEN.sum() == pytest.approx(0.002)
        assert bridge.SERVING_TOKENS_PER_S.value() == 123.0

    def test_malformed_serving_events_are_skipped_not_zeroed(self):
        """A serving event missing its measurement field must not land
        a fabricated 0.0 sample in the latency histograms."""
        REGISTRY.reset()
        _logging.emit_event("serving_first_token", rid="r")  # no ttft_s
        _logging.emit_event("serving_request_finished", rid="r",
                            per_token_ms="not-a-number")
        assert bridge.SERVING_TTFT.count() == 0
        assert bridge.SERVING_PER_TOKEN.count() == 0
        # the event itself is still counted
        assert bridge.EVENTS_TOTAL.value(
            event="serving_first_token") == 1

    def test_events_stamp_the_active_span(self):
        with trace.recording() as rec:
            with trace.span("op"):
                _logging.emit_event("obs_test_probe", n=1)
        [ev] = rec.to_chrome_trace()["traceEvents"]
        assert [s["name"] for s in ev["args"]["events"]] \
            == ["obs_test_probe"]

    def test_uninstall_stops_feeding_reinstall_resumes(self):
        REGISTRY.reset()
        bridge.uninstall()
        try:
            _logging.emit_event("obs_test_probe")
            assert bridge.EVENTS_TOTAL.value(event="obs_test_probe") == 0
        finally:
            bridge.install()
        _logging.emit_event("obs_test_probe")
        assert bridge.EVENTS_TOTAL.value(event="obs_test_probe") == 1


# --------------------------------------------------------------------------
# instrumented subsystems
# --------------------------------------------------------------------------

class TestInstrumentedPieces:
    def test_checkpoint_durations_by_op(self, tmp_path):
        REGISTRY.reset()
        hist = REGISTRY.get("apex_checkpoint_duration_seconds")
        tree = {"w": jnp.arange(8.0)}
        path = rz.save_checkpoint(str(tmp_path), 0, tree)
        rz.validate_checkpoint(path)
        rz.restore_checkpoint(str(tmp_path), like=tree)
        assert hist.count(op="save") == 1
        # restore fuses validation, so only the explicit call counts
        assert hist.count(op="validate") == 1
        assert hist.count(op="restore") == 1
        assert hist.sum(op="save") > 0.0

    def test_sharded_checkpoint_durations_are_observed(self, tmp_path,
                                                       mesh8):
        """The v2 (elastic) manager path feeds the SAME duration series
        as v1 — the docs' unqualified save/validate/restore inventory
        row holds for both formats."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from apex_tpu.resilience import elastic as el

        REGISTRY.reset()
        hist = REGISTRY.get("apex_checkpoint_duration_seconds")
        state = {"b": jax.device_put(
            jnp.ones((8,)), NamedSharding(mesh8, P("dp")))}
        el.save_sharded_checkpoint(str(tmp_path), 0, state, mesh=mesh8)
        el.restore_sharded_checkpoint(str(tmp_path), state)
        assert hist.count(op="save") == 1
        assert hist.count(op="restore") == 1

    def test_failed_restore_is_not_observed(self, tmp_path):
        REGISTRY.reset()
        hist = REGISTRY.get("apex_checkpoint_duration_seconds")
        with pytest.raises(rz.CheckpointError):
            rz.restore_checkpoint(str(tmp_path / "empty"), like={})
        assert hist.count(op="restore") == 0

    def test_timers_publish_as_gauge_series(self):
        timers = Timers()
        with timers("fwd").timing():
            time.sleep(0.01)
        with timers("bwd").timing():
            pass
        snap = timers.publish_metrics()
        assert set(snap) == {"fwd", "bwd"}
        assert bridge.TIMER_SECONDS.value(region="fwd") \
            == snap["fwd"]["total_s"]
        assert bridge.TIMER_SECONDS.value(region="fwd") >= 0.01
        assert 'apex_timer_seconds{region="fwd"}' \
            in metrics.prometheus_text()

    def test_heartbeat_age_gauge_reads_at_scrape_time(self):
        gauge = REGISTRY.get("apex_heartbeat_age_seconds")
        gauge.set_function(None)  # isolate from earlier-suite watchdogs
        clock = _FakeClock()
        wd = rz.StepWatchdog(deadline_s=100.0, poll_interval_s=50.0,
                             clock=clock)
        # constructing must NOT touch the gauge (a prepared-but-idle
        # watchdog would otherwise shadow a healthy running one)
        assert gauge.bound_function() is None
        wd.start()
        assert gauge.value() == -1.0  # never beaten
        wd.beat(0)
        clock.t += 7.5
        assert gauge.value() == 7.5  # age grows without new samples
        wd.beat(1)
        assert gauge.value() == 0.0
        # stop() releases the binding: a finished run must not report a
        # forever-growing age (false wedged-host signal) — but the
        # series stays present, pushed to the honest -1 sentinel
        wd.stop()
        assert gauge.bound_function() is None
        assert gauge.value() == -1.0

    def test_reused_supervisor_keeps_heartbeat_gauge(self):
        """run() -> stop() releases the gauge; a second run()'s start()
        re-acquires it — a reused supervisor never loses its probe."""
        gauge = REGISTRY.get("apex_heartbeat_age_seconds")
        gauge.set_function(None)
        sup = rz.TrainingSupervisor(None, rz.SupervisorConfig(
            step_deadline_s=30.0, poll_interval_s=5.0))
        bound_mid_run = []

        def step_fn(state, batch, step):
            bound_mid_run.append(gauge.bound_function() is not None)
            return state

        sup.run(step_fn, None, iter(range(2)), num_steps=2)
        assert gauge.bound_function() is None  # released with run 1
        sup.run(step_fn, None, iter(range(2)), num_steps=2)
        assert bound_mid_run == [True] * 4
        assert gauge.bound_function() is None

    def test_watchdog_gauge_binding_nests_and_survives_misorder(self):
        gauge = REGISTRY.get("apex_heartbeat_age_seconds")
        gauge.set_function(None)
        outer = rz.StepWatchdog(deadline_s=100.0,
                                poll_interval_s=50.0).start()
        inner = rz.StepWatchdog(deadline_s=100.0,
                                poll_interval_s=50.0).start()
        # a short-lived inner watchdog hands the gauge BACK to the
        # still-running outer one instead of clearing it
        inner.stop()
        assert gauge.bound_function() == outer._beat_age
        outer.stop()
        assert gauge.bound_function() is None
        # misordered stops: the displaced watchdog's stop is a no-op,
        # and when the survivor stops, the resurrected released binding
        # reports the honest -1 sentinel, never a frozen growing age
        a = rz.StepWatchdog(deadline_s=100.0, poll_interval_s=50.0).start()
        a.beat(0)
        b = rz.StepWatchdog(deadline_s=100.0, poll_interval_s=50.0).start()
        a.stop()
        assert gauge.bound_function() == b._beat_age  # b still owns it
        b.stop()
        assert gauge.bound_function() == a._beat_age  # handed back...
        assert gauge.value() == -1.0  # ...but a is released: sentinel

    def test_engine_rejects_zero_slots(self):
        from apex_tpu.serving import DecodeEngine

        with pytest.raises(ValueError, match="slots"):
            DecodeEngine(object(), {}, slots=0, max_len=16, prefill_len=8)

    def test_engine_cache_utilization(self, engine):
        assert engine.cache_utilization() == 0.0
        engine.prefill(0, [1, 2, 3])
        assert engine.cache_utilization() == pytest.approx(3 / (2 * 16))
        engine.release(0)
        assert engine.cache_utilization() == 0.0


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture(scope="module")
def engine():
    """ONE engine (and one set of prefill/decode compiles) shared by
    every serving-side test in this module; each consumer starts from a
    reset cache.  Compile count stays exactly 1 by construction — which
    the acceptance run asserts through the decode-compiles gauge."""
    import jax

    from apex_tpu.models import LlamaConfig, LlamaForCausalLM
    from apex_tpu.serving import DecodeEngine

    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=32)
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    eng = DecodeEngine(model, params, slots=2, max_len=16, prefill_len=8)
    yield eng
    eng.reset()


# --------------------------------------------------------------------------
# ACCEPTANCE 1: fault-injected supervisor run -> exact counters + trace
# --------------------------------------------------------------------------

def test_acceptance_supervised_run_metrics_match_injected_faults(
        tmp_path, events):
    n_steps = 6
    flaky_failures = 2
    batches = [{"x": np.full((2, 3), float(i), np.float32)}
               for i in range(n_steps)]
    stream = rz.GuardedIterator(
        rz.CorruptBatch(
            rz.FlakyIterator(iter(batches), fail_at=(1,),
                             failures=flaky_failures),
            at=(3,), mode="nan", seed=7),
        spec=rz.spec_of(batches[0]), skip_budget=2)
    # isolate the heartbeat gauge from any unstopped earlier watchdog so
    # the released-at-stop assertion below sees this run's binding only
    REGISTRY.get("apex_heartbeat_age_seconds").set_function(None)
    mgr = rz.CheckpointManager(str(tmp_path), keep=n_steps)
    sup = rz.TrainingSupervisor(
        mgr,
        rz.SupervisorConfig(
            step_deadline_s=30.0, poll_interval_s=5.0, checkpoint_every=2,
            retry=rz.RetryPolicy(max_attempts=4, base_delay_s=0.0)),
        sleep=lambda s: None)

    gauge_seen = {}

    def step_fn(state, batch, step):
        if step == 3:  # mid-run: the heartbeat-age gauge is live
            gauge_seen["age"] = REGISTRY.get(
                "apex_heartbeat_age_seconds").value()
        return {"w": state["w"] + batch["x"].sum()}

    REGISTRY.reset()
    with trace.recording() as rec:
        state, last = sup.run(step_fn, {"w": np.float32(0.0)}, stream,
                              num_steps=n_steps)
    assert last == n_steps - 1

    # ---- counters exactly match the injected faults
    assert bridge.RETRY_ATTEMPTS.value(what="data_fetch") == flaky_failures
    assert bridge.EVENTS_TOTAL.value(event="retry_recovered") == 1
    assert bridge.BATCHES_SKIPPED.value() == 1
    assert bridge.EVENTS_TOTAL.value(event="batch_skipped") == 1
    assert bridge.FAULTS_INJECTED.value(fault="flaky_iterator") \
        == flaky_failures
    assert bridge.FAULTS_INJECTED.value(fault="corrupt_batch") == 1
    assert REGISTRY.get("apex_supervisor_steps_total").value() == n_steps
    step_hist = REGISTRY.get("apex_step_duration_seconds")
    assert step_hist.count() == n_steps
    # checkpoint_every=2 over 6 steps -> saves after steps 1, 3, 5
    ckpt_hist = REGISTRY.get("apex_checkpoint_duration_seconds")
    assert ckpt_hist.count(op="save") == 3
    assert bridge.EVENTS_TOTAL.value(event="checkpoint_saved") == 3

    # ---- the Prometheus snapshot carries those counts verbatim
    text = metrics.prometheus_text()
    assert 'apex_retry_attempts_total{what="data_fetch"} 2' in text
    assert 'apex_batches_skipped_total 1' in text
    assert 'apex_supervisor_steps_total 6' in text
    assert 'apex_events_total{event="checkpoint_saved"} 3' in text
    assert 'apex_step_duration_seconds_count 6' in text

    # ---- the Chrome trace loads and its spans line up with the run
    payload = json.loads(json.dumps(rec.to_chrome_trace()))
    evs = payload["traceEvents"]
    sup_spans = [e for e in evs if e["name"] == "supervisor_step"]
    steps = [e for e in evs if e["name"] == "train_step"]
    saves = [e for e in evs if e["name"] == "checkpoint_save"]
    assert [e["args"]["step"] for e in sup_spans] == list(range(n_steps))
    assert [e["args"]["step"] for e in steps] == list(range(n_steps))
    assert len(saves) == 3
    for e in evs:
        assert e["ph"] == "X" and e["dur"] >= 0.0
    # spans never overlap out of order: starts are non-decreasing
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)
    # proper nesting: every train_step / checkpoint_save is a child of
    # its step's supervisor_step span
    by_id = {e["args"]["span_id"]: e for e in evs}
    for child in steps + saves:
        assert by_id[child["args"]["parent_id"]]["name"] \
            == "supervisor_step"
    # the causal story rides the step span: the flaky fetch's retries
    # stamp step 1, the corrupt batch's skip stamps step 3, and each
    # save event stamps its own checkpoint_save span
    stamped_1 = [s["name"]
                 for s in sup_spans[1]["args"].get("events", [])]
    assert stamped_1.count("retry_attempt") == flaky_failures
    assert "retry_recovered" in stamped_1
    assert "batch_skipped" in [
        s["name"] for s in sup_spans[3]["args"].get("events", [])]
    assert all("checkpoint_saved" in
               [s["name"] for s in e["args"].get("events", [])]
               for e in saves)

    # ---- heartbeat age gauge: live mid-run, released at watchdog stop
    assert gauge_seen["age"] >= 0.0
    assert REGISTRY.get(
        "apex_heartbeat_age_seconds").bound_function() is None


# --------------------------------------------------------------------------
# ACCEPTANCE 2: continuous-batching drain -> exact counters + live gauges
# --------------------------------------------------------------------------

def test_acceptance_serving_drain_metrics_match_request_counts(events,
                                                               engine):
    from apex_tpu.serving import ContinuousBatchingScheduler, Request

    eng = engine
    eng.reset()
    sched = ContinuousBatchingScheduler(eng, max_queue=8, log_interval=1)
    n_requests, new_tokens = 4, 3

    REGISTRY.reset()
    for i in range(n_requests):
        sched.submit(Request(f"r{i}", [1 + i, 2, 3],
                             max_new_tokens=new_tokens))
    results = sched.run()
    assert len(results) == n_requests
    assert all(len(r.tokens) == new_tokens for r in results.values())

    # ---- counters exactly match the request counts
    for kind in ("serving_request_queued", "serving_request_admitted",
                 "serving_first_token", "serving_request_finished"):
        assert bridge.EVENTS_TOTAL.value(event=kind) == n_requests, kind
    assert bridge.SERVING_TTFT.count() == n_requests
    assert bridge.SERVING_QUEUE_WAIT.count() == n_requests
    assert bridge.SERVING_PER_TOKEN.count() == n_requests
    assert bridge.SERVING_TOKENS_PER_S.value() > 0.0

    # ---- gauges describe the drained end state
    assert bridge.SERVING_QUEUE_DEPTH.value() == 0.0
    assert bridge.SERVING_SLOT_OCCUPANCY.value() == 0.0
    assert bridge.SERVING_CACHE_UTILIZATION.value() == 0.0
    assert bridge.SERVING_DECODE_COMPILES.value() == 1.0

    # ---- the serving_step sample carries occupancy + cache utilization
    # in the SAME event (no more inferring one from the other)
    samples = events("serving_step")
    assert samples, "log_interval=1 must emit a sample every step"
    for s in samples:
        assert 0.0 <= s["slot_occupancy"] <= 1.0
        assert 0.0 <= s["cache_utilization"] <= 1.0
        assert s["active_slots"] <= eng.slots
    assert any(s["slot_occupancy"] == 1.0 for s in samples)  # both busy
    assert any(s["cache_utilization"] > 0.0 for s in samples)

    # ---- Prometheus snapshot carries the exact totals
    text = metrics.prometheus_text()
    assert ('apex_events_total{event="serving_request_finished"} 4'
            in text)
    assert 'apex_serving_ttft_seconds_count 4' in text
    assert 'apex_serving_queue_depth 0' in text


# --------------------------------------------------------------------------
# overhead: instrumentation must be negligible with no exporter attached
# --------------------------------------------------------------------------

def test_instrumented_step_overhead_is_bounded(monkeypatch):
    """Full per-step instrumentation (span with no recorder + histogram
    observe + counter inc) stays what the default path promises, as a count
    of what a step does and not as a time (a 3x wall-clock bar on a ~100 us
    step went red whenever the other test workers took the cores): with no
    recorder installed and no profiler session a step allocates no span
    record and no profiler annotation, sets no current span, and makes one
    ``observe`` and one ``inc``."""
    reg = MetricsRegistry()
    hist = reg.histogram("apex_t_step_seconds", "t")
    ctr = reg.counter("apex_t_steps_total", "t")
    a = np.ones((128, 128), np.float64)
    made = []

    class CountedSpan(trace.Span):
        __slots__ = ()

        def __init__(self, *args, **kwargs):
            made.append("span")
            super().__init__(*args, **kwargs)

    class CountedAnnotation:
        is_enabled = staticmethod(trace.TraceAnnotation.is_enabled)

        def __new__(cls, *args, **kwargs):
            made.append("annotation")
            return trace.TraceAnnotation(*args, **kwargs)

    def counted(method, name):
        def call(*args, **kwargs):
            made.append(name)
            return method(*args, **kwargs)
        return call

    monkeypatch.setattr(trace, "Span", CountedSpan)
    monkeypatch.setattr(trace, "TraceAnnotation", CountedAnnotation)
    monkeypatch.setattr(hist, "observe", counted(hist.observe, "observe"))
    monkeypatch.setattr(ctr, "inc", counted(ctr.inc, "inc"))
    prev = trace.uninstall_recorder()  # measure the true default path
    try:
        n = 6 * 200
        for _ in range(n):
            ts = time.perf_counter()
            with trace.span("step", step=1) as live:
                (a @ a).sum()
                assert live is None and trace.current_span() is None
            hist.observe(time.perf_counter() - ts)
            ctr.inc()
    finally:
        if prev is not None:
            trace.install_recorder(prev)
    assert ctr.value() == n
    assert made == ["observe", "inc"] * n, collections.Counter(made)
    # the count tells a recorder: installed, the same step allocates a span
    del made[:]
    with trace.recording():
        with trace.span("step"):
            pass
    assert made == ["span"]
