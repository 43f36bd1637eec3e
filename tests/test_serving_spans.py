"""The serving step's spans (ISSUE 25): ``obs.trace.span`` at the layer
boundaries of ``ContinuousBatchingScheduler.step`` and ``DecodeEngine``.

One primitive, three sinks: nothing (no recorder, no profiler — the
default, and free), the Chrome-JSON recorder, and the ``jax.profiler``
session's own ``.xplane.pb`` (the clock the device's lines are on).  The
names are a contract: the benchmark's readers
(``benchmark/lib/program_spans.py``), ``PERF.md`` and
``docs/api/observability.md`` key on them.
"""

import glob
import json
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import _logging, obs
from apex_tpu import serving as sv
from apex_tpu.models import LlamaConfig, LlamaForCausalLM
from apex_tpu.obs import trace

CFG = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, max_position_embeddings=256)

# every span of the plain path, and the two more that speculation adds
PLAIN = {"serving.submit", "serving.step", "serving.admit",
         "serving.prefill", "serving.decode", "serving.readback",
         "serving.finish", "serving.publish", "engine.prefill_chunk",
         "engine.decode", "engine.sample"}
SPEC = {"serving.spec", "engine.verify_draft"}


@pytest.fixture(scope="module")
def engine():
    model = LlamaForCausalLM(CFG)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    return sv.DecodeEngine(model, params, slots=2, max_len=96,
                           prefill_len=32)


def _requests(new_tokens=8):
    rng = np.random.default_rng(7)
    return [sv.Request(f"r{i}", rng.integers(0, 128, 8 + i).tolist(),
                       new_tokens) for i in range(2)]


def _three_steps(engine, requests=None, **kw):
    """Two requests, three steps on a clock that never moves (every
    duration an event or a histogram carries is then 0.0)."""
    engine.reset()
    sched = sv.ContinuousBatchingScheduler(
        engine, clock=sv.VirtualClock(), log_interval=1, **kw)
    lengths = []
    for r in requests or _requests():
        sched.submit(r)
    for _ in range(3):
        sched.step()
        lengths.append(engine.lengths())
    tokens = {rid: sched.progress_of(rid) for rid in sched.active_rids}
    return sched, lengths, tokens


def _inside(child, parent):
    return (parent["ts"] <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"])


def test_three_steps_record_exactly_the_tables_spans(engine):
    with trace.recording() as rec:
        sched, lengths, _ = _three_steps(engine)
    evs = rec.to_chrome_trace()["traceEvents"]
    assert {e["name"] for e in evs} == PLAIN
    by_id = {e["args"]["span_id"]: e for e in evs}
    steps = [e for e in evs if e["name"] == "serving.step"]
    assert [s["args"]["step"] for s in steps] == [1, 2, 3]
    assert steps[0]["args"]["queued"] == 2 and steps[1]["args"]["active"] == 2

    def root(e):
        while "parent_id" in e["args"]:
            e = by_id[e["args"]["parent_id"]]
        return e

    for e in evs:
        if e["name"] in ("serving.step", "serving.submit"):
            assert "parent_id" not in e["args"]
        else:
            top = root(e)
            assert top["name"] == "serving.step" and _inside(e, top)
    # the engine's spans sit under the scheduler phase that called them
    parent_of = {e["name"]: by_id[e["args"]["parent_id"]]["name"]
                 for e in evs if "parent_id" in e["args"]
                 and e["name"] in ("engine.prefill_chunk", "engine.decode")}
    assert parent_of == {"engine.prefill_chunk": "serving.prefill",
                         "engine.decode": "serving.decode"}
    assert {by_id[e["args"]["parent_id"]]["name"] for e in evs
            if e["name"] == "engine.sample"} == {"serving.prefill",
                                                  "serving.decode"}

    # one wait a step, after everything the step enqueues: the first
    # step's for both prompts' first tokens (its decode runs meanwhile),
    # each later one for the decode enqueued a step before (ISSUE 36)
    reads = [e for e in evs if e["name"] == "serving.readback"]
    assert [(r["args"]["what"], r["args"]["lag"]) for r in reads] == [
        ("first_token", 0), ("decode", 1), ("decode", 1)]
    for step, read in zip(steps, reads):
        assert _inside(read, step)
        assert read["ts"] >= max(
            e["ts"] + e["dur"] for e in evs
            if e["name"].startswith("engine.") and _inside(e, step))
    assert [e["args"]["ahead"] for e in evs
            if e["name"] == "serving.decode"] == [0, 1, 1]
    assert sorted(e["args"]["rid"] for e in evs
                  if e["name"] == "serving.submit") == ["r0", "r1"]

    # counts ride the spans where the work happens
    prefills = [e["args"]["chunks"] for e in evs
                if e["name"] == "serving.prefill"]
    assert prefills == [2, 0, 0]
    chunks = [e["args"] for e in evs if e["name"] == "engine.prefill_chunk"]
    assert [(c["slot"], c["bucket"], c["tokens"]) for c in chunks] == [
        (0, engine.bucket_for(8), 8), (1, engine.bucket_for(9), 9)]
    decodes = [e["args"] for e in evs if e["name"] == "engine.decode"]
    assert [d["lanes"] for d in decodes] == [2, 2, 2]
    # kv_tokens = the host mirror's sum over the active lanes BEFORE the
    # append: both lanes decode and nothing finishes, so it is the sum
    # after the step less one token a lane
    assert [d["kv_tokens"] for d in decodes] == [
        int(n.sum()) - 2 for n in lengths]
    assert decodes[0]["kv_tokens"] == 8 + 9
    assert [e["args"]["lanes"] for e in evs
            if e["name"] == "serving.decode"] == [2, 2, 2]
    assert [e["args"]["finished"] for e in evs
            if e["name"] == "serving.finish"] == [0, 0, 0]
    assert sched.steps_run == 3


def test_speculation_adds_its_two_spans_and_only_when_on(engine):
    repetitive = [sv.Request("rep", [5, 6, 7, 8] * 4, 12)]
    with trace.recording() as rec:
        _three_steps(engine, repetitive,
                     speculation=sv.SpeculationConfig(max_draft=4))
    evs = rec.to_chrome_trace()["traceEvents"]
    names = {e["name"] for e in evs}
    assert SPEC <= names <= PLAIN | SPEC
    verify = [e["args"] for e in evs if e["name"] == "engine.verify_draft"]
    assert verify and all(v["slot"] == 0 and 1 <= v["drafted"] <= 4
                          for v in verify)
    by_id = {e["args"]["span_id"]: e for e in evs}
    assert {by_id[e["args"]["parent_id"]]["name"] for e in evs
            if e["name"] == "engine.verify_draft"} == {"serving.spec"}


def test_off_path_yields_none_and_builds_no_span(engine, monkeypatch):
    assert trace._RECORDER is None
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with trace.span("free", n=1) as s:
        assert s is None and trace.current_span() is None

    def no_span(*a, **kw):
        raise AssertionError("a Span was built with tracing off")

    monkeypatch.setattr(trace, "Span", no_span)
    monkeypatch.setattr(trace, "TraceAnnotation", type(
        "NoAnnotation", (), {"is_enabled": staticmethod(lambda: False),
                             "__init__": no_span}))
    sched, _, tokens = _three_steps(engine)
    # three steps deliver three tokens: the first, and two of the three
    # decoded ones (the third is read by the fourth step)
    assert sched.steps_run == 3 and set(tokens.values()) == {3}


_WALL = ("time",)


def _observed_run(engine, recorder_on):
    events = []

    def sink(event):
        events.append({k: v for k, v in event.items() if k not in _WALL})

    obs.metrics.reset()
    _logging.add_event_sink(sink)
    try:
        if recorder_on:
            with trace.recording() as rec:
                sched, _, _ = _three_steps(engine, _requests(3))
            assert len(rec) > 20
        else:
            sched, _, _ = _three_steps(engine, _requests(3))
    finally:
        _logging.remove_event_sink(sink)
    tokens = {rid: res.tokens for rid, res in sched.pop_results().items()}
    return tokens, events, obs.snapshot()


def test_a_recorder_changes_no_token_event_or_metric(engine):
    """Tracing observes: the tokens, the event stream (minus the wall
    stamp) and the registry of a run under a recorder are those of a run
    without one."""
    off = _observed_run(engine, recorder_on=False)
    on = _observed_run(engine, recorder_on=True)
    assert off[0] == on[0] and set(off[0]) == {"r0", "r1"}
    assert [e["event"] for e in off[1]].count("serving_step") == 3
    assert off[1] == on[1]
    assert off[2] == on[2]


def test_under_jax_profiler_the_spans_are_on_the_profiles_host_plane(
        engine, tmp_path):
    """The same spans, written by the profiler itself: one clock with the
    device's lines (on the CPU the host plane is all there is)."""
    from jax.profiler import ProfileData

    engine.reset()
    with jax.profiler.trace(str(tmp_path)):
        assert trace._RECORDER is None
        with trace.span("outer", k=1) as s:
            # profiler only: attributes still settable, no current span
            assert s is not None and trace.current_span() is None
        _, lengths, _ = _three_steps(engine)
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    found = [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
             for line in host.lines for e in line.events
             if e.name.startswith(("serving.", "engine."))]
    assert {n for n, *_ in found} == PLAIN
    steps = [(s, s + d) for n, s, d, _ in found if n == "serving.step"]
    assert len(steps) == 3
    for name, s, d, _ in found:
        if name not in ("serving.step", "serving.submit"):
            assert any(a <= s and s + d <= b for a, b in steps), name
    stats = {}
    for name, _, _, st in found:
        stats.setdefault(name, []).append(st)
    assert [st["step"] for st in stats["serving.step"]] == [1, 2, 3]
    # set at exit, after the annotation was entered: still in the profile
    assert [st["chunks"] for st in stats["serving.prefill"]] == [2, 0, 0]
    assert [st["kv_tokens"] for st in stats["engine.decode"]] == [
        int(n.sum()) - 2 for n in lengths]
    assert [st["what"] for st in stats["serving.readback"]] == [
        "first_token", "decode", "decode"]
    assert not jax.profiler.TraceAnnotation.is_enabled()


def test_log_sink_builds_its_line_only_when_info_is_heard(monkeypatch):
    """``json.dumps`` of every event was paid with nothing listening; the
    line, when it is written, is byte for byte the old one."""
    calls = []
    real = json.dumps

    def counting(*a, **kw):
        calls.append(a)
        return real(*a, **kw)

    monkeypatch.setattr(_logging.json, "dumps", counting)
    logger = logging.getLogger("apex_tpu.events")
    lines = []

    class Keep(logging.Handler):
        def emit(self, record):
            lines.append(record.getMessage())

    handler, level = Keep(), logger.level
    logger.addHandler(handler)
    try:
        logger.setLevel(logging.WARNING)    # what a fresh process has
        _logging._log_sink({"event": "quiet", "b": 1})
        assert calls == [] and lines == []
        logger.setLevel(logging.INFO)
        event = {"event": "heard", "z": 1, "a": np.float32(0.5)}
        _logging._log_sink(event)
    finally:
        logger.setLevel(level)
        logger.removeHandler(handler)
    assert len(calls) == 1
    assert lines == [real(event, sort_keys=True, default=str)]
