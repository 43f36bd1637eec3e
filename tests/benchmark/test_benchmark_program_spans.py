"""The readers of the program's own spans (ISSUE 25): the arithmetic on
hand-made events with known answers, the None paths (no trace, no device
plane, a program without the spans), and the five metrics on a fixture cut
from a chip run (benchmark/fixtures/serve_program_trace.json: three whole
scheduler steps of mistral-7b-l16.chat-closed with their programs)."""

import json
import os
import tempfile

import pytest

from benchmark.lib import bytes as by
from benchmark.lib import program_spans as ps
from benchmark.lib import trace as tr
from benchmark.reducers import (ReduceContext, decode_roofline,
                                idle_under_span_ms, span_ms)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURE = os.path.join(ROOT, "benchmark", "fixtures",
                       "serve_program_trace.json")

# a model small enough to count by hand: 2 layers of q+o 128, k+v 64,
# gate+up+down 384 parameters, a head of 256 -> 1,408 x 2 bytes; 5 norm
# scales of 8 float32 -> 160 bytes; 2 x 2 x 1 x 4 x 2 = 32 bytes a token
TINY = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
        "num_attention_heads": 2, "num_key_value_heads": 1,
        "vocab_size": 32,
        "assumed": {"head_dim": 4, "weights_dtype": "bfloat16",
                    "norm_dtype": "float32"}}
FIXED, A_TOKEN = 2 * 1408 + 160, 32


def _step(t, dur, *, decode_at, kv, readback, prefill=()):
    """The spans of one scheduler step that starts at ``t``."""
    out = [("serving.step", t, dur, {"step": t}),
           ("serving.admit", t + 1, 4, {}),
           ("serving.prefill", t + 6, 34 if prefill else 4,
            {"chunks": 1 if prefill else 0})]
    out += prefill
    d = t + decode_at
    out += [("serving.decode", d - 8, 28, {"lanes": 2}),
            ("engine.decode", d, 12, {"lanes": 2, "kv_tokens": kv}),
            ("engine.sample", d + 13, 5, {}),
            ("serving.readback", d + 21, readback, {"what": "decode"}),
            ("serving.finish", d + 22 + readback, 7, {"finished": 0}),
            ("serving.publish", d + 30 + readback, 10, {})]
    return out


def _hand_made():
    """Two whole steps (at 200 and 420) between two cut ones.  The gaps
    between programs over the whole steps:
    (160, 215) under step 0's publish, (235, 236) under the first-token
    readback, (238, 255) under serving.decode, (355, 358) and (545, 550)
    under the decode readbacks, (362, 445) under no span (the caller's
    loop, 400-420, where it also submits); (556, 645) resumes in the cut
    step 3 and is left out."""
    spans = (
        _step(0, 196, decode_at=80, kv=100, readback=75)
        + _step(200, 200, decode_at=50, kv=200, readback=109, prefill=[
            ("engine.prefill_chunk", 210, 10,
             {"slot": 0, "bucket": 16, "tokens": 9}),
            ("engine.sample", 222, 4, {}),
            ("serving.readback", 228, 10,
             {"what": "first_token", "rid": "r1"})])
        + _step(420, 180, decode_at=20, kv=300, readback=119)
        + _step(620, 190, decode_at=20, kv=400, readback=129)
        + [("serving.submit", 405, 6, {"rid": "r2"})])
    modules = [("jit__decode(1)", 100, 50), ("jit__sample_one(2)", 152, 8),
               ("jit__prefill(3)", 215, 20), ("jit__sample_one(4)", 236, 2),
               ("jit__decode(1)", 255, 100), ("jit__sample_one(2)", 358, 4),
               ("jit__decode(1)", 445, 100), ("jit__sample_one(2)", 550, 6),
               ("jit__decode(1)", 645, 100)]
    return ps.ProgramTrace(modules=modules,
                           spans=sorted(spans, key=lambda e: e[1]),
                           device="/device:TPU:0")


def _rc(config=None, trace=True):
    return ReduceContext(
        tr.Trace(modules=[], ops=[], spans=[]) if trace else None,
        {}, config or TINY, {}, "TPU v5 lite")


@pytest.fixture
def hand_made(monkeypatch):
    pt = _hand_made()
    monkeypatch.setattr(ps, "load", lambda: pt)
    return pt


def _args(metric):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           f"{metric}.json")) as f:
        return json.load(f).get("args", {})


def test_whole_steps_are_those_inside_the_devices_window(hand_made):
    assert [e[1] for e in ps.whole(hand_made)] == [200, 420]
    assert [e[1] for e in ps.whole(hand_made, r"^engine\.decode$")] == [
        250, 440, 640]


def test_span_ms_is_a_self_time_per_step(hand_made):
    # step at 200: 200 long, waits 10 + 109, engine 10 + 4 + 12 + 5
    # step at 420: 180 long, waits 119, engine 12 + 5
    assert span_ms.reduce(_rc(), **_args("sched_host_ms.serve")) == \
        pytest.approx((50 + 44) / 2 / 1e6)
    assert span_ms.reduce(_rc(), **_args("engine_host_ms.serve")) == \
        pytest.approx((31 + 17) / 2 / 1e6)
    # any span per any other: the sampler calls inside a serving.decode
    assert ps.span_ms(hand_made, r"^engine\.sample$",
                      per=r"^serving\.decode$") == pytest.approx(5e-6)
    assert ps.span_ms(hand_made, "^absent$", per="^absent$") is None
    # submit lies outside every step and is in nobody's self time
    assert ps.span_ms(hand_made, r"^serving\.submit$") == 0.0


def test_idle_readings_and_the_remainder_add_up_to_the_gaps(hand_made):
    readback = idle_under_span_ms.reduce(
        _rc(), **_args("idle_readback_ms.serve"))
    host = idle_under_span_ms.reduce(_rc(), **_args("idle_host_ms.serve"))
    nobody = idle_under_span_ms.reduce(_rc(), span=None)
    assert readback == pytest.approx((1 + 3 + 5) / 2 / 1e6)
    assert host == pytest.approx((55 + 17) / 2 / 1e6)
    assert nobody == pytest.approx(83 / 2 / 1e6)
    # the summed gaps between the programs of the two whole steps, found
    # again without the attribution
    mods = [m for m in hand_made.modules if 152 <= m[1] < 600]
    _, _, gaps = tr.busy_union(mods)
    assert sum(b - a for a, b in gaps) == 164
    assert readback + host + nobody == pytest.approx(164 / 2 / 1e6,
                                                     abs=1e-9, rel=1e-12)
    by_name, steps = ps.step_gaps(hand_made)
    assert steps == 2 and by_name == {
        "serving.publish": 55.0, "serving.decode": 17.0,
        "serving.readback": 9.0, ps.NO_SPAN: 83.0}


def test_decode_roofline_pairs_each_execution_with_its_span(hand_made):
    pairs = ps.paired(hand_made, "^jit__decode", r"^engine\.decode$")
    assert [(m[1], s[3]["kv_tokens"]) for m, s in pairs] == [
        (100, 100), (255, 200), (445, 300), (645, 400)]
    assert by.llama_decode_step(TINY, 0) == FIXED
    assert by.llama_decode_step(TINY, 7) == FIXED + 7 * A_TOKEN
    # bytes a second of the four executions: 50, 100, 100, 100 ns long
    rates = sorted((FIXED + kv * A_TOKEN) / (dur * 1e-9) for kv, dur in
                   [(100, 50), (200, 100), (300, 100), (400, 100)])
    want = 100.0 * (rates[1] + rates[2]) / 2 / 819e9
    assert decode_roofline.reduce(
        _rc(), **_args("decode_roofline.serve")) == pytest.approx(want)
    assert 15.0 < want < 15.5
    # an execution dispatched before the trace began has no span: skipped
    hand_made.spans[:] = [e for e in hand_made.spans if e[1] >= 200]
    assert [m[1] for m, _ in ps.paired(
        hand_made, "^jit__decode", r"^engine\.decode$")] == [255, 445, 645]


def test_a_device_clock_that_leads_is_pulled_back_to_causality(hand_made):
    """On the chip the device's line reports a program 0.2-0.8 ms before
    the span that enqueues it begins.  Dispatches still pair with their
    own executions, and the device's events are read later by the least
    shift that lets none start before its span."""
    assert ps.device_lead_ns(hand_made) == 0.0
    early = ps.ProgramTrace(
        modules=[(n, s - 30, d) for n, s, d in hand_made.modules],
        spans=hand_made.spans, device=hand_made.device)
    pairs = ps.paired(early, "^jit__decode", r"^engine\.decode$")
    assert [(m[1] + 30, s[3]["kv_tokens"]) for m, s in pairs] == [
        (100, 100), (255, 200), (445, 300), (645, 400)]
    # dispatch spans start 20, 5, 5, 5 (decode) and 5 (prefill) before
    # their programs: 30 early leaves the latter 25 before their spans
    assert ps.device_lead_ns(early) == 25.0
    assert [m[1] for m in ps.device_modules(early)][:3] == [95, 147, 210]
    assert [e[1] for e in ps.whole(early)] == [200, 420]
    # a lone span has no spacing to judge by and takes the nearest
    lone = ps.ProgramTrace(modules=early.modules, device=early.device,
                           spans=[e for e in early.spans if e[1] == 440])
    assert [m[1] for m, _ in ps.paired(
        lone, "^jit__decode", r"^engine\.decode$")] == [415]


def test_the_bytes_of_a_mistral_decode_step():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mistral-7b-l16.json")) as f:
        config = json.load(f)
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    fixed = 2 * (16 * per_layer + 4096 * 32768) + 4 * 33 * 4096
    assert by.llama_decode_step(config, 0) == fixed
    assert 7.2e9 < fixed < 7.3e9
    assert by.llama_decode_step(config, 1) - fixed == 65536


@pytest.mark.parametrize("reader,metric", [
    (span_ms, "sched_host_ms.serve"), (span_ms, "engine_host_ms.serve"),
    (idle_under_span_ms, "idle_readback_ms.serve"),
    (idle_under_span_ms, "idle_host_ms.serve"),
    (decode_roofline, "decode_roofline.serve")])
def test_every_reader_returns_none_when_there_is_nothing_to_read(
        reader, metric, monkeypatch):
    args = _args(metric)

    def never():
        raise AssertionError("no trace was made: nothing to look for")

    monkeypatch.setattr(ps, "load", never)
    assert reader.reduce(_rc(trace=False), **args) is None
    # a trace without a device plane, or a program without the spans
    monkeypatch.setattr(ps, "load", lambda: None)
    assert reader.reduce(_rc(), **args) is None
    # spans but no whole step / no decode execution
    cut = _hand_made()
    cut.modules[:] = cut.modules[:1]
    monkeypatch.setattr(ps, "load", lambda: cut)
    if reader is not decode_roofline:
        assert reader.reduce(_rc(), **args) is None
    cut.modules[:] = [("jit__sample_one(2)", 152, 8)]
    assert reader.reduce(_rc(), **args) is None


def test_load_finds_this_processes_trace_and_needs_a_device_plane(
        tmp_path, monkeypatch):
    """A CPU profile with the program's spans in it: found under the
    temporary directory (the newest such directory), parsed, and None for
    want of a device plane."""
    import jax

    from apex_tpu.obs import trace as obs_trace

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    ps.load.cache_clear()
    try:
        assert ps.find_trace_dir() is None and ps.load() is None
        ps.load.cache_clear()
        older = tmp_path / "apexbench-trace-older"
        older.mkdir()
        os.utime(older, (1.0, 1.0))
        assert ps.find_trace_dir() == str(older)
        mine = tmp_path / "apexbench-trace-mine"
        mine.mkdir()
        with jax.profiler.trace(str(mine)):
            with obs_trace.span("serving.step", step=1):
                with obs_trace.span("engine.decode", lanes=1, kv_tokens=3):
                    pass
        assert ps.find_trace_dir() == str(mine)
        path = tr.find_xplane(str(mine))
        assert path is not None and ps.parse_xplane(path) is None
        assert ps.load() is None
        # the same file read as if its host were a chip's: the spans and
        # their stats are there
        from jax.profiler import ProfileData

        host = next(p for p in ProfileData.from_file(path).planes
                    if p.name == "/host:CPU")
        mine_evs = {e.name: dict(e.stats) for line in host.lines
                    for e in line.events if e.name.startswith(ps.PREFIXES)}
        assert mine_evs == {"serving.step": {"step": 1},
                            "engine.decode": {"lanes": 1, "kv_tokens": 3}}
    finally:
        ps.load.cache_clear()


def test_json_round_trip_keeps_stats(hand_made):
    again = ps.ProgramTrace.from_json(json.loads(json.dumps(
        hand_made.to_json())))
    assert again.modules == hand_made.modules
    assert again.spans == hand_made.spans


# --- the fixture cut from a chip run (my chip run, PR 25, seed 5): steps
# 488-490 of the traced slice.  488 finishes a request (serving.finish 4.0
# ms: the slot's release runs eager device ops), 489 admits its successor
# (serving.admit 1.4 ms) and prefills it in one 128-token chunk, 490 only
# decodes.

@pytest.fixture(scope="module")
def recorded():
    return ps.load_fixture(FIXTURE)


def test_fixture_holds_three_whole_steps_and_their_programs(recorded):
    steps = ps.whole(recorded)
    assert [e[3]["step"] for e in steps] == [488, 489, 490]
    assert 0.5e6 < ps.device_lead_ns(recorded) < 0.8e6
    pairs = ps.paired(recorded, "^jit__decode", r"^engine\.decode$")
    assert [sp[3]["kv_tokens"] for _, sp in pairs] == [7472, 7252, 7268,
                                                       7284]
    assert all(59.2e6 < m[2] < 59.4e6 for m, _ in pairs)
    chunk, = ps.paired(recorded, "^jit__prefill",
                       r"^engine\.prefill_chunk$")
    assert chunk[1][3] == {"slot": 15, "bucket": 128, "tokens": 127}
    reads = [e[3] for e in recorded.spans if e[0] == "serving.readback"]
    assert {"what": "first_token", "rid": "c15-r77"} in reads


@pytest.mark.parametrize("reader,metric,lo,hi", [
    (span_ms, "sched_host_ms.serve", 2.0, 2.6),
    (span_ms, "engine_host_ms.serve", 2.0, 2.6),
    (idle_under_span_ms, "idle_readback_ms.serve", 2.5, 3.5),
    (idle_under_span_ms, "idle_host_ms.serve", 1.5, 2.5),
    (decode_roofline, "decode_roofline.serve", 15.5, 16.5)])
def test_each_metric_reads_inside_its_range_on_the_fixture(
        recorded, monkeypatch, reader, metric, lo, hi):
    monkeypatch.setattr(ps, "load", lambda: recorded)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mistral-7b-l16.json")) as f:
        config = json.load(f)
    value = reader.reduce(_rc(config), **_args(metric))
    assert lo < value < hi, (metric, value)


def test_fixture_idle_readings_add_up_to_the_gaps(recorded):
    by_name, steps = ps.step_gaps(recorded)
    assert steps == 3
    total = sum(by_name.values()) / steps / 1e6
    assert 4.5 < total < 5.4          # 2.5 ms a decode-only step; 489: 8
    parts = [ps.idle_under_span_ms(recorded, _args(m)["span"])
             for m in ("idle_readback_ms.serve", "idle_host_ms.serve")]
    assert sum(parts) + ps.idle_under_span_ms(recorded, None) == \
        pytest.approx(total, rel=1e-9)
