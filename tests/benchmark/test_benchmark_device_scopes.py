"""Device time by component: ``lib/device_scopes.py`` and its two reducers,
on hand-made events, on a profile written here byte by byte, and on
``fixtures/serve_scope_trace.json`` (five engine executions of one traced
chip run of ``mistral-7b-l16.chat-closed``, PR 35)."""

import importlib
import json
import os
import statistics

import pytest

from benchmark.lib import device_scopes as ds
from benchmark.lib import trace as tr
from benchmark.reducers import ReduceContext, scope_coverage, scope_time

BENCH = os.path.dirname(os.path.abspath(ds.__file__ + "/.."))
ROOT = os.path.dirname(BENCH)
FIXTURE = os.path.join(BENCH, "fixtures", "serve_scope_trace.json")
NEW = ("decode_proj_mlp_ms.serve", "decode_cache_ms.serve",
       "decode_state_ms.serve", "decode_experts_ms.serve",
       "decode_head_ms.serve", "chunk_proj_mlp_ms.serve",
       "chunk_cache_ms.serve", "chunk_select_ms.serve",
       "chunk_experts_ms.serve", "chunk_head_ms.serve",
       "scope_coverage.serve")
PARTS = r"^(attn_proj|mlp|norm|embed|cache_write|cache_read|select|state|" \
        r"router|experts|head|sample|_unscoped_)$"


@pytest.fixture(scope="module")
def fixture():
    return ds.load_fixture(FIXTURE)


# ---- an instruction's component ---------------------------------------------


@pytest.mark.parametrize("op_name, component", [
    ("jit(_decode)/M/layers_0/self_attn/apex.attn_proj/q_proj/dot_general",
     "attn_proj"),
    ("jit(_decode)/M/layers_0/self_attn/apex.attn_proj/apex.cache_read/exp",
     "cache_read"),
    ("jit(_sample_one)/vmap(apex.sample)/jit(sort)/sort", "sample"),
    ("jit(_decode)/M/layers_0/add", None),
    ("jit(f)/not_apex.mlp/mul", None),
    ("jit(f)/apex.mlpx", "mlpx"),
    ("", None),
])
def test_the_innermost_scope_is_the_component(op_name, component):
    assert ds.innermost(op_name) == component


def _module():
    i = ds.Instruction
    return {
        "fused": ([i("p0", "parameter"),
                   i("add.1", "add", "jit(f)/apex.mlp/add", ("p0",))],
                  "add.1"),
        "main": ([
            i("w", "parameter", "params['w']"),
            i("copy-start.1", "copy-start", "", ("w",)),
            i("copy-done.1", "copy-done", "", ("copy-start.1",)),
            i("dot.1", "dot", "jit(f)/L/apex.attn_proj/dot_general",
              ("copy-done.1",)),
            i("fusion.1", "fusion", "", ("dot.1",), ("fused",)),
            i("layout", "fusion", "params['w']", ("w",)),
            i("dot.2", "dot", "jit(f)/apex.head/dot_general", ("layout",)),
            i("add.9", "add", "jit(f)/L/add", ("fusion.1", "dot.2")),
            i("copy.3", "copy", "", ("add.9",)),
        ], "copy.3")}


def test_resolve_applies_its_four_rules():
    got = ds.resolve(_module())
    # 1: its own scope
    assert got["dot.1"] == "attn_proj" and got["dot.2"] == "head"
    # 2: a fusion without one takes its computation's root's
    assert got["fusion.1"] == "mlp"
    # 3: what XLA made itself belongs to what consumes it, hops on
    assert got["copy-start.1"] == got["copy-done.1"] == "attn_proj"
    assert got["layout"] == "head"
    # 4: traced under no scope stays unscoped, and lends nothing
    assert got["add.9"] == ds.UNSCOPED
    assert got["copy.3"] == ds.UNSCOPED


# ---- the wire format --------------------------------------------------------


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _f(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _instruction(ident, name, opcode, op_name="", operands=(), calls=()):
    body = _f(1, name) + _f(2, opcode) + _f(35, ident)
    if op_name:
        body += _f(7, _f(1, "type") + _f(2, op_name))
    if operands:                                    # packed
        body += _f(36, b"".join(_varint(o) for o in operands))
    for c in calls:                                 # one a key
        body += _f(38, c)
    return _f(2, body)


def _hlo_proto():
    fused = (_f(1, "fused") + _f(5, 7) + _f(6, 2)
             + _instruction(1, "p0", "parameter")
             + _instruction(2, "add.1", "add", "jit(f)/apex.mlp/add", (1,)))
    main = (_f(1, "main") + _f(5, 8) + _f(6, 13)
            + _instruction(10, "w", "parameter", "params['w']")
            + _instruction(11, "dot.1", "dot",
                           "jit(f)/apex.attn_proj/dot_general", (10,))
            + _instruction(12, "fusion.1", "fusion", "", (11,), (7,))
            + _instruction(13, "while.1", "while",
                           "jit(f)/apex.select/while", (12,)))
    return _f(1, _f(1, "jit_f") + _f(3, fused) + _f(3, main))


KEY = "jit__decode(42)"


def _xspace(device="/device:TPU:0"):
    def metadata(ident, name, stats=b""):
        return _f(4, _f(1, ident) + _f(2, _f(1, ident) + _f(2, name) + stats))

    def event(ident, offset_ns, dur_ns):
        return _f(4, _f(1, ident) + _f(2, offset_ns * 1000)
                  + _f(3, dur_ns * 1000))

    def line(ident, name, events):
        return _f(3, _f(1, ident) + _f(2, name) + _f(3, 0)
                  + b"".join(events))

    runs = [event(1, s, 1000) for s in (0, 2000, 4000, 6000)]
    ops = []
    for s in (0, 2000, 4000, 6000):
        ops += [event(2, s + 100, 300),             # dot.1
                event(3, s + 400, 100),             # fusion.1
                event(4, s + 500, 400),             # while.1 ...
                event(3, s + 600, 250)]             # ... and its body's op
    tpu = (_f(1, 2) + _f(2, device)
           + line(1, "XLA Modules", runs) + line(2, "XLA Ops", ops)
           + metadata(1, KEY)
           + metadata(2, "%dot.1 = bf16[8,8]{1,0} dot(bf16[8,8] %w)")
           + metadata(3, "%fusion.1 = bf16[8,8]{1,0} fusion(%dot.1)")
           + metadata(4, "%while.1 = (s32[]) while((s32[]) %fusion.1)"))
    meta = (_f(1, 0) + _f(2, "/host:metadata")
            + metadata(42, KEY, _f(5, _f(1, 1) + _f(6, _hlo_proto())))
            + _f(5, _f(1, 1) + _f(2, _f(1, 1) + _f(2, "Hlo Proto"))))
    return _f(1, tpu) + _f(1, meta)


def test_the_profiles_own_modules_are_read_off_the_wire():
    modules = ds.profile_modules(_xspace())
    assert list(modules) == [KEY]
    name, computations = ds.hlo_computations(modules[KEY])
    assert name == "jit_f"
    assert set(computations) == {"fused", "main"}
    instrs, root = computations["main"]
    assert root == "while.1"
    assert [i.name for i in instrs] == ["w", "dot.1", "fusion.1", "while.1"]
    assert instrs[1].operands == ("w",) and instrs[2].calls == ("fused",)
    assert ds.resolve(computations)["fusion.1"] == "mlp"


def test_a_profile_joins_events_modules_and_components(tmp_path):
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(_xspace())
    st = ds.parse_xplane(str(path))
    assert st.device == "/device:TPU:0"
    assert len(st.modules) == 4 and len(st.ops) == 16
    assert [e[3] for e in st.ops[:4]] == ["attn_proj", "mlp", "select",
                                          "mlp"]
    # two whole executions; the while's body counts once
    assert ds.scope_time_ms(st, "^select$", "^jit__decode") == \
        pytest.approx((400 - 250) / 1e6)
    assert ds.scope_time_ms(st, "^mlp$", "^jit__decode") == \
        pytest.approx((100 + 250) / 1e6)
    assert ds.coverage_pct(st) == pytest.approx(100.0)


def test_a_profile_without_a_tpu_plane_reads_as_none(tmp_path):
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(_xspace(device="/host:CPU"))
    assert ds.parse_xplane(str(path)) is None


TEXT = """\
HloModule jit_f, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%p0, %p0), metadata={op_name="jit(f)/apex.mlp/add" source_file="a.py" source_line=3}
}

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%t)
}

ENTRY %main.3 (w: f32[8]) -> f32[8] {
  %w = f32[8]{0} parameter(0), metadata={op_name="w"}
  %fusion.1 = f32[8]{0} fusion(%w), kind=kLoop, calls=%fused
  %while.1 = (s32[], f32[8]{0}) while(%fusion.1), condition=%body, body=%body, metadata={op_name="jit(f)/apex.select/while"}
  ROOT %copy.1 = f32[8]{0} copy(f32[8]{0} %fusion.1)
}
"""


def test_a_compiled_programs_text_gives_the_same_table():
    computations = ds.text_computations(TEXT)
    assert set(computations) == {"fused", "body", "main.3"}
    instrs, root = computations["main.3"]
    assert root == "copy.1"
    by_name = {i.name: i for i in instrs}
    assert by_name["fusion.1"].opcode == "fusion"
    assert by_name["fusion.1"].calls == ("fused",)
    assert by_name["while.1"].opcode == "while"
    assert by_name["while.1"].calls == ("body", "body")
    assert by_name["copy.1"].operands == ("fusion.1",)
    got = ds.resolve(computations)
    assert got["fusion.1"] == "mlp" and got["while.1"] == "select"
    assert got["copy.1"] == ds.UNSCOPED             # nothing consumes it


# ---- the arithmetic, on hand-made events ------------------------------------


def test_self_time_leaves_out_what_is_nested():
    ops = [("while", 0, 100, "select"), ("a", 10, 20, "select"),
           ("inner", 40, 50, "cache_read"), ("b", 45, 10, "mlp"),
           ("after", 100, 7, "head")]
    assert [e[2] for e in ds.self_times(ops)] == [30, 20, 40, 10, 7]


def _trace():
    """Five executions: a small chunk, a decode step, two executions of the
    large chunk's program (30 and 50 long) and a decode step; the first and
    the last hold half their ops, as a slice's do."""
    mods = [("jit__prefill(1)", 0, 10), ("jit__decode(9)", 20, 10),
            ("jit__prefill(2)", 40, 30), ("jit__prefill(2)", 80, 50),
            ("jit__decode(9)", 140, 10)]
    table = {"jit__prefill(1)": {"x": "mlp"},
             "jit__prefill(2)": {"x": "mlp", "r": "cache_read"},
             "jit__decode(9)": {"x": "mlp", "r": "cache_read",
                                "cp": ds.UNSCOPED}}
    ops = [("%x = f32[] fusion()", 5, 5),
           ("%x = f32[] fusion()", 20, 6), ("%r = f32[] fusion()", 26, 2),
           ("%cp = f32[] copy()", 28, 2),
           ("%x = f32[] fusion()", 40, 20), ("%r = f32[] fusion()", 60, 10),
           ("%x = f32[] fusion()", 80, 30), ("%r = f32[] fusion()", 110, 20),
           ("%x = f32[] fusion()", 140, 3), ("%unknown = f32[] add()", 200, 1)]
    return ds.join(mods, ops, table, "/device:TPU:0")


def test_join_names_instructions_and_leaves_the_rest_unscoped():
    st = _trace()
    assert st.ops[0] == ("x", 5, 5, "mlp")
    assert st.ops[3] == ("cp", 28, 2, ds.UNSCOPED)
    assert st.ops[-1] == ("unknown", 200, 1, ds.UNSCOPED)  # in no execution


def test_the_slices_first_and_last_execution_are_left_out():
    st = _trace()
    assert [(n, s) for n, s, _, _ in ds.executions(st)] == [
        ("jit__decode(9)", 20), ("jit__prefill(2)", 40),
        ("jit__prefill(2)", 80)]
    # the one whole decode step, not the mean with the cut one
    assert ds.scope_time_ms(st, "^mlp$", "^jit__decode") == \
        pytest.approx(6 / 1e6)
    # with two executions nothing can be left out
    short = ds.ScopeTrace(st.modules[:2], st.ops[:4])
    assert len(ds.executions(short)) == 2


def test_pick_largest_reads_module_medians_program_and_one_execution():
    st = _trace()
    plain = tr.Trace(modules=st.modules, ops=[], spans=[])
    assert ds.largest_program(st, "^jit__prefill") == (
        "jit__prefill(2)", 40)
    assert tr.module_median_ms(plain, "^jit__prefill", "largest") == \
        pytest.approx(40 / 1e6)
    # the whole execution at the median, or the nearest shorter: 30 long
    mlp = ds.scope_time_ms(st, "^mlp$", "^jit__prefill", "largest")
    read = ds.scope_time_ms(st, "^cache_read$", "^jit__prefill", "largest")
    assert (mlp, read) == (pytest.approx(20 / 1e6), pytest.approx(10 / 1e6))
    assert mlp + read <= tr.module_median_ms(plain, "^jit__prefill",
                                             "largest")
    # pooled, the mean over the whole executions of every program
    assert ds.scope_time_ms(st, "^mlp$", "^jit__prefill") == \
        pytest.approx(25 / 1e6)
    assert ds.scope_time_ms(st, "^state$", "^jit__prefill") is None


def test_coverage_is_the_scoped_share_and_none_without_scopes():
    st = _trace()
    assert ds.coverage_pct(st, "^jit__decode") == pytest.approx(80.0)
    bare = ds.ScopeTrace(st.modules, [(n, s, d, ds.UNSCOPED)
                                      for n, s, d, _ in st.ops])
    assert ds.coverage_pct(bare) is None            # the parent's programs
    assert ds.scope_time_ms(bare, "^mlp$", "^jit__decode") is None


# ---- the fixture cut from the chip ------------------------------------------


def test_fixture_holds_a_few_whole_steps(fixture):
    assert fixture.device == "/device:TPU:0"
    runs = ds.executions(fixture)
    assert [n.split("(")[0] for n, _, _, _ in runs] == [
        "jit__prefill", "jit__prefill", "jit__decode", "jit__prefill",
        "jit__decode"]
    assert len({n for n, _, _, _ in runs}) == 3     # two prefill programs


def test_fixture_parts_add_up_to_each_programs_busy_time(fixture):
    plain = tr.Trace(modules=[], ops=[(n, s, d) for n, s, d, _ in
                                      fixture.ops], spans=[])
    for name, start, dur, parts in ds.executions(fixture):
        inside = [e for e in plain.ops if start <= e[1] < start + dur]
        busy, _, _ = tr.busy_union(inside)
        assert sum(parts.values()) == pytest.approx(busy, rel=0.03), name
        assert sum(parts.values()) <= dur


def test_fixture_reads_what_the_chip_run_read(fixture):
    assert ds.coverage_pct(fixture) > 98.0
    decode = {c: ds.scope_time_ms(fixture, f"^{c}$", "^jit__decode")
              for c in ("mlp", "attn_proj", "cache_read", "head")}
    assert decode["mlp"] == pytest.approx(7.49, abs=0.05)
    assert decode["attn_proj"] == pytest.approx(2.22, abs=0.05)
    assert decode["cache_read"] == pytest.approx(0.96, abs=0.05)
    assert decode["head"] == pytest.approx(0.39, abs=0.02)
    assert ds.scope_time_ms(fixture, "^state$", "^jit__decode") is None


def test_fixture_chunk_parts_stay_within_the_chunks_median(fixture):
    plain = tr.Trace(modules=fixture.modules, ops=[], spans=[])
    chunk = tr.module_median_ms(plain, "^jit__prefill", "largest")
    assert chunk == pytest.approx(25.2, abs=0.1)
    program, median = ds.largest_program(fixture, "^jit__prefill")
    assert median / 1e6 == chunk == statistics.median(
        d for n, _, d in fixture.modules if n == program) / 1e6
    whole = ds.scope_time_ms(fixture, PARTS, "^jit__prefill", "largest")
    assert chunk * 0.97 <= whole <= chunk
    named = sum(ds.scope_time_ms(fixture, spec["args"]["scopes"],
                                 "^jit__prefill", "largest") or 0.0
                for spec in (_spec(m) for m in NEW if m.startswith("chunk_")))
    assert named <= whole


# ---- the reducers and the manifest's entries --------------------------------


def _spec(metric):
    with open(os.path.join(BENCH, "layer_metrics", f"{metric}.json")) as f:
        return json.load(f)


def _rc(trace):
    return ReduceContext(trace, {}, {}, {}, "TPU v5 lite")


def test_reducers_read_none_without_a_trace():
    assert scope_time.reduce(_rc(None), scopes="^mlp$",
                             module="^jit__decode") is None
    assert scope_coverage.reduce(_rc(None)) is None


def test_reducers_read_none_where_the_trace_has_no_device_plane(monkeypatch):
    # the CPU rehearsal: a trace was made, the profile has no TPU plane
    monkeypatch.setattr(ds, "load", lambda: None)
    rc = _rc(tr.Trace(modules=[], ops=[], spans=[]))
    assert scope_time.reduce(rc, scopes="^mlp$",
                             module="^jit__decode") is None
    assert scope_coverage.reduce(rc) is None


def test_reducers_read_the_parsed_profile(monkeypatch, fixture):
    monkeypatch.setattr(ds, "load", lambda: fixture)
    rc = _rc(tr.Trace(modules=[], ops=[], spans=[]))
    for metric in NEW:
        spec = _spec(metric)
        reducer = importlib.import_module(
            f"benchmark.reducers.{spec['reducer']}")
        value = reducer.reduce(rc, **spec["args"])
        # a dense model: no state, no experts, no selector
        dense = not any(w in metric for w in ("state", "experts", "select"))
        assert (value is not None) == dense, metric


def test_each_metrics_data_file_carries_its_manifest_entry():
    """No PR but a ``benchmark`` one can list them: two tests that are
    here count the entries of the long-context cells
    (``test_benchmark_window.py``: the manifest's last four are Mellum's;
    ``test_benchmark_sparse.py``: dots3 has eighteen).  Each data file
    holds the entry it waits for."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    serving = [w["name"] for w in manifest["workloads"]
               if w["traffic"] != "pretrain"]
    entries = {m: _spec(m)["manifest_entry"] for m in NEW}
    for name, m in entries.items():
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"} and m["name"] == name
        assert m["source"] == "device_trace" and m["moves"] == "serve_tok_s"
        assert set(m["workloads"]) <= set(serving)
        assert m["layer"] in {x["layer"] for x in manifest["per_layer"]}
        assert m["better"] == ("higher" if m["unit"] == "%" else "lower")
        assert _spec(name)["reducer"] in ("scope_time", "scope_coverage")
    assert entries["scope_coverage.serve"]["workloads"] == serving
    assert entries["chunk_select_ms.serve"]["workloads"] == [
        "dots3-note-ep8-l5.longdoc-closed"]
    assert entries["decode_state_ms.serve"]["workloads"] == [
        "nemotron3-super-ep4-l11.chat-closed-64"]
    assert entries["chunk_experts_ms.serve"]["workloads"] == serving[1:]
