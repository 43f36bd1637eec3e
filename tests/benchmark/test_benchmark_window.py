"""The Mellum cell's benchmark files: the configuration against the published
one, the byte and operation counts against the issue's arithmetic, the new
reducers on hand-made spans and counters (and their silence where a program
has neither), the reference check's power to tell a fault, and the runner
end to end on the CPU rehearsal path."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import test_benchmark_manifest as rules
from benchmark.lib import window_bytes, window_flops
from benchmark.lib.trace import Trace
from benchmark.reducers import (
    ReduceContext,
    counter_ratio,
    expert_gmm_roofline,
    op_time,
    window_chunk_mfu,
    window_decode_roofline,
)

ROOT = rules.ROOT
MANIFEST = "tests/benchmark/manifest_window.json"
CELL = "tiny-mellum.tiny-repo-closed"
REAL = "benchmark/configs/mellum2-12b-l8.json"
REAL_CELL = "mellum2-12b-l8.repo-closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
FULL, WINDOW = "full_attention", "sliding_attention"
OTHERS = ("benchmark/configs/nemotron3-super-ep4-l11.json",
          "benchmark/configs/dots3-note-ep8-l5.json",
          "benchmark/configs/mistral-7b-l16.json")


def _load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


@pytest.mark.parametrize("rule", [
    "test_top_level_keys_are_the_contracts",
    "test_names_and_units_use_the_allowed_characters",
    "test_end_to_end_metrics_have_bounds_and_setup_s",
    "test_every_layer_metric_moves_a_metric_its_cells_report",
    "test_every_cell_finds_its_files",
    "test_every_layer_metric_has_a_reader"])
def test_window_manifest_keeps_the_manifest_rules(rule):
    getattr(rules, rule)(_load(MANIFEST))


def test_configuration_keeps_published_widths_and_states_its_cut():
    c = _load(REAL)
    widths = {
        "hidden_size": 2304, "intermediate_size": 7168,
        "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
        "moe_intermediate_size": 896, "num_experts": 64,
        "num_experts_per_tok": 8, "sliding_window": 1024,
        "vocab_size": 98304, "rms_norm_eps": 1e-06, "norm_topk_prob": True,
        "max_position_embeddings": 131072}
    assert {k: c[k] for k in widths} == widths
    assert c["rope_parameters"] == {
        FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
               "original_max_position_embeddings": 8192, "beta_fast": 32,
               "beta_slow": 1, "attention_factor": 1.2772588722239782},
        WINDOW: {"rope_type": "default", "rope_theta": 500000}}
    assert c["reduced"] == ["num_hidden_layers", "layer_types",
                            "mlp_layer_types"]
    published = c["published"]
    assert set(published) == set(c["reduced"])
    # two whole periods of the published pattern, from its start
    assert c["layer_types"] == [WINDOW, WINDOW, WINDOW, FULL] * 2
    assert c["mlp_layer_types"] == ["sparse"] * 8
    assert len(c["layer_types"]) == c["num_hidden_layers"] == 8
    assert published["num_hidden_layers"] == 28
    assert published["layer_types"] == [WINDOW, WINDOW, WINDOW, FULL] * 7
    assert published["layer_types"][:8] == c["layer_types"]
    assert c["published_layers_kept"] == list(range(8))
    assert c["experts_held"] == [0, 64]          # every expert, no share
    for said in ("four pipeline stages of 7", "7.59 GB", "9.94 GB",
                 "unused", "multi-token-prediction"):
        assert said in c["reduced_why"], said
    assert "four-stage pipeline" in c["stands_for"]
    assert "no chip shares a layer" in c["stands_for"]
    for convention in ("routing", "sliding_window", "yarn", "rope",
                       "attention", "weights", "weights_dtype", "float32",
                       "multi_token_prediction"):
        assert convention in c["assumed"], convention
    assert "no selection bias" in c["assumed"]["routing"]
    assert "no per-head norm" in c["assumed"]["attention"]
    entry = next(e for e in _load("BENCHMARK.json")["configs"]
                 if e["name"] == c["name"])
    assert entry["source"] == c["source"] and entry["file"] == REAL
    assert entry["reduced"] == c["reduced"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_configuration_holds_every_number_of_the_catalogs_entry():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    c = _load(REAL)
    assert c["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if c.get(k) != v)
    assert differs == sorted(c["reduced"])
    assert {k: row["config"][k] for k in c["reduced"]} == c["published"]


def test_the_cell_is_the_issues_traffic():
    manifest = _load("BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mellum2-12b-l8", "repo-closed", 1)
    assert manifest["workloads"][-1] == cell     # appended, nothing moved
    assert manifest["configs"][-1]["name"] == "mellum2-12b-l8"
    t = _load("benchmark/traffic/repo-closed.json")
    assert (t["runner"], t["loop"], t["clients"], t["strata"]) == (
        "serve_window", "closed", 16, 16)
    assert t["engine"] == {"slots": 16, "max_len": 32768,
                           "prefill_len": 1024}
    # longdoc-closed's lengths, on purpose
    long = _load("benchmark/traffic/longdoc-closed.json")
    assert t["prompt_len"] == long["prompt_len"] == {
        "dist": "uniform", "min": 18432, "max": 22528}
    assert t["output_len"] == long["output_len"] == {"dist": "fixed",
                                                     "value": 512}
    check = t["check"]
    assert check["prompt_len"] == 4096 == 4 * _load(REAL)["sliding_window"]
    assert check["decode_tokens"] == 8
    assert 0 < check["tolerance"] < 0.2 and "e4m3" in check["tolerance_why"]
    # it reports serve_tok_s and setup_s, and the gaps' tail per layer
    mine = [m["name"] for m in manifest["end_to_end"]
            if REAL_CELL in m.get("workloads", [REAL_CELL])]
    assert mine == ["serve_tok_s", "setup_s"]
    layer = [m["name"] for m in manifest["per_layer"]
             if REAL_CELL in m.get("workloads", [])]
    assert layer[-4:] == ["window_decode_roofline.serve",
                          "window_chunk_mfu.serve",
                          "expert_gmm_roofline.serve",
                          "window_rows_read.serve"]
    assert [m["name"] for m in manifest["per_layer"]][-4:] == layer[-4:]
    assert set(layer[:-4]) == {
        "batch_occupancy.serve", "device_idle.serve", "sched_host_ms.serve",
        "engine_host_ms.serve", "idle_readback_ms.serve",
        "idle_host_ms.serve", "experts_touched.serve",
        "expert_load_max_over_mean.serve", "pairs_here.serve",
        "moe_gmm_ms.serve", "decode_step_ms.serve_tok_s",
        "prefill_chunk_ms.serve_tok_s", "itl_p95_ms.serve_tok_s",
        "ttft_p90_ms.serve_tok_s"}
    # the four new metrics are this cell's alone
    for m in manifest["per_layer"][-4:]:
        assert m["workloads"] == [REAL_CELL] and m["moves"] == "serve_tok_s"


def test_decode_step_bytes_are_the_issues_arithmetic():
    c = _load(REAL)
    assert window_bytes.attention_matrices(c) == (
        2 * 2304 * 4096 + 2 * 2304 * 512)               # 21.23 M
    assert window_bytes.expert_matrices(c) == 3 * 2304 * 896
    assert window_bytes.row_bytes(c) == 2048 and window_bytes.held(c) == 64
    need = window_bytes.mellum_decode_step
    zero = dict(lanes=0, kv_tokens=0, window_rows=0)
    other = need(c, touched_share=0.0, **zero)
    assert other == window_bytes.outside_experts(c)
    # 0.34 GB of attention matrices, 0.45 GB of head, routers and scales
    assert 0.79e9 < other < 0.80e9
    experts = need(c, touched_share=1.0, **zero) - other
    assert experts == 8 * 64 * 3 * 2304 * 896 * 2        # 6.34 GB
    assert round(0.867 * experts / 1e9, 2) == 5.50
    rows = need(c, touched_share=0.0, lanes=0, kv_tokens=1000,
                window_rows=10) - other
    assert rows == 1000 * 2 * 2048 + 10 * 2048
    appended = need(c, touched_share=0.0, **dict(zero, lanes=16)) - other
    assert appended == 16 * 8 * 2048
    # the issue's step: 16 lanes at ~20.7k rows
    live = 16 * 20700
    step = need(c, lanes=16, kv_tokens=live, window_rows=6 * 16 * 1024,
                touched_share=0.867)
    assert 7.8e9 < step < 7.9e9
    # read at full extent the six window layers would add 3.9 GB
    assert round((6 * live - 6 * 16 * 1024) * 2048 / 1e9, 1) == 3.9


def test_prefill_chunk_operations_are_the_issues_arithmetic():
    c = _load(REAL)
    chunk = window_flops.mellum_prefill_chunk
    prompt = sum(chunk(c, tokens=1024, offset=o)
                 for o in range(0, 20480, 1024))
    assert 32.0e12 < prompt < 32.5e12     # the issue: 23.2 + 6.9 + 2.1
    first, last = chunk(c, tokens=1024, offset=0), chunk(c, tokens=1024,
                                                         offset=19456)
    assert first < last
    # matrices: 8 layers x (21.23 + 0.147 + 8 x 6.193) M a row, and the head
    # once a chunk
    per_row = 8 * (21233664 + 2304 * 64 + 8 * 3 * 2304 * 896)
    flat = 2.0 * (1024 * per_row + 2304 * 98304)
    assert 1.16e12 < flat < 1.17e12
    # attention at offset 0: half a square a full layer, and a window layer
    # the same (a 1,024 chunk is one window)
    seen = 1024 * 1025 // 2
    assert first == pytest.approx(flat + 4.0 * 32 * 128 * 8 * seen)
    # past the first chunk a window layer's work stays, a full layer's grows
    # by a 1,024 rows a query
    grow = chunk(c, tokens=1024, offset=20480) - last
    assert grow == pytest.approx(4.0 * 32 * 128 * 2 * 1024 * 1024)
    # a chip that holds a share of the experts computes that share
    shared = dict(c, experts_held=[16, 16])
    assert chunk(shared, tokens=1024, offset=0) < first


COUNTERS = {"moe_steps": 80, "moe_tokens": 1200, "moe_pairs": 9600,
            "moe_touched": 4400, "moe_max_load": 500, "window_rows": 7000,
            "window_live_rows": 140000, "n_routed_experts": 64}


def _rc(counters, trace=None, config=REAL):
    return ReduceContext(trace, counters, _load(config),
                         _load("benchmark/traffic/repo-closed.json"),
                         "TPU v5 lite")


def test_rows_read_and_expert_metrics_from_hand_made_counters():
    spec = _load("benchmark/layer_metrics/window_rows_read.serve.json")
    assert spec["reducer"] == "counter_ratio"
    assert counter_ratio.reduce(_rc(COUNTERS), **spec["args"]) == \
        pytest.approx(5.0)
    # a program that counts no window rows (any other model, the parent)
    assert counter_ratio.reduce(_rc({"steps": 3}), **spec["args"]) is None
    # the accepted expert metrics divide by the experts held, which this
    # family's configuration names num_experts: the runner hands the count
    touched = _load("benchmark/layer_metrics/experts_touched.serve.json")
    assert counter_ratio.reduce(_rc(COUNTERS), **touched["args"]) == \
        pytest.approx(100.0 * 4400 / (80 * 64))
    load = _load(
        "benchmark/layer_metrics/expert_load_max_over_mean.serve.json")
    assert counter_ratio.reduce(_rc(COUNTERS), **load["args"]) == \
        pytest.approx(500 * 64 / 9600)
    here = _load("benchmark/layer_metrics/pairs_here.serve.json")
    assert counter_ratio.reduce(_rc(COUNTERS), **here["args"]) == \
        pytest.approx(100.0)
    without = {k: v for k, v in COUNTERS.items() if k != "n_routed_experts"}
    assert counter_ratio.reduce(_rc(without), **touched["args"]) is None


def test_decode_roofline_from_hand_made_spans(monkeypatch):
    spec = _load("benchmark/layer_metrics/window_decode_roofline.serve.json")
    args = spec["args"]
    reduce = window_decode_roofline.reduce
    assert reduce(_rc(COUNTERS), **args) is None            # no trace
    ps = window_decode_roofline.ps
    attrs = {"lanes": 16, "kv_tokens": 331200, "window_rows": 98304,
             "window_live_rows": 1987296}
    pairs = [(("jit__decode", 0, ms * 1e6), ("engine.decode", 0, 1e5, attrs))
             for ms in (11.0, 12.0, 13.0)]
    monkeypatch.setattr(ps, "of", lambda rc: object())
    monkeypatch.setattr(ps, "paired", lambda pt, module, span: pairs)
    got = reduce(_rc(COUNTERS, trace=object()), **args)
    need = window_bytes.mellum_decode_step(
        _load(REAL), lanes=16, kv_tokens=331200, window_rows=98304,
        touched_share=4400 / (80 * 64))
    assert got == pytest.approx(100.0 * need / 12e-3 / 819e9)
    assert 60 < got < 90
    # spans without the window's rows (a dense model's, a commit before the
    # rings), a run without the expert counters, another family's
    # configuration: nothing
    bare = [(m, (n, s, d, {"lanes": 16, "kv_tokens": 1}))
            for m, (n, s, d, _) in pairs]
    monkeypatch.setattr(ps, "paired", lambda pt, module, span: bare)
    assert reduce(_rc(COUNTERS, trace=object()), **args) is None
    monkeypatch.setattr(ps, "paired", lambda pt, module, span: pairs)
    assert reduce(_rc({}, trace=object()), **args) is None
    for other in OTHERS:
        assert reduce(_rc(COUNTERS, trace=object(), config=other),
                      **args) is None


def test_chunk_mfu_from_hand_made_spans(monkeypatch):
    spec = _load("benchmark/layer_metrics/window_chunk_mfu.serve.json")
    args = spec["args"]
    reduce = window_chunk_mfu.reduce
    assert reduce(_rc({}), **args) is None
    ps = window_chunk_mfu.ps

    def pair(ms, bucket, tokens, offset):
        attrs = {"slot": 0, "bucket": bucket, "tokens": tokens}
        if offset is not None:
            attrs["offset"] = offset
        return (("jit__prefill", 0, ms * 1e6),
                ("engine.prefill_chunk", 0, 1e5, attrs))

    pairs = [pair(20.0, 1024, 1024, 4096), pair(30.0, 1024, 1024, 8192),
             pair(40.0, 1024, 1024, 16384), pair(5.0, 64, 40, 20480)]
    monkeypatch.setattr(ps, "of", lambda rc: object())
    monkeypatch.setattr(ps, "paired", lambda pt, module, span: pairs)
    got = reduce(_rc({}, trace=object()), **args)
    # each chunk's own rate, then the median: the small bucket's chunk does
    # not count
    rates = sorted(window_flops.mellum_prefill_chunk(
        _load(REAL), tokens=1024, offset=o) / (ms * 1e-3)
        for ms, o in ((20.0, 4096), (30.0, 8192), (40.0, 16384)))
    assert got == pytest.approx(100.0 * rates[1] / 197e12)
    assert 15 < got < 45
    old = [pair(60.0, 1024, 1024, None)]
    monkeypatch.setattr(ps, "paired", lambda pt, module, span: old)
    assert reduce(_rc({}, trace=object()), **args) is None
    monkeypatch.setattr(ps, "paired", lambda pt, module, span: pairs)
    for other in OTHERS:
        assert reduce(_rc({}, trace=object(), config=other), **args) is None


def test_expert_gmm_roofline_from_a_hand_made_trace():
    """Twenty-four gmm calls of 0.3 ms in each of five decode executions,
    one gmm in a prefill program that must not count; the family's byte
    function is the metric file's argument."""
    ms = 1_000_000
    modules = [("jit__decode(1)", k * 100 * ms, 30 * ms) for k in range(5)]
    modules.append(("jit__prefill(2)", 40 * ms, 30 * ms))
    ops = [(f"%gmm.{j} = custom-call", k * 100 * ms + j * ms, 3 * ms // 10)
           for k in range(5) for j in range(24)]
    ops.append(("%gmm.99 = custom-call", 41 * ms, 7 * ms))
    rc = _rc(COUNTERS, trace=Trace(modules, ops, []))
    time_spec = _load("benchmark/layer_metrics/moe_gmm_ms.serve.json")
    assert op_time.reduce(rc, **time_spec["args"]) == pytest.approx(7.2)
    spec = _load("benchmark/layer_metrics/expert_gmm_roofline.serve.json")
    assert spec["args"]["bytes_module"] == "window_bytes"
    share = expert_gmm_roofline.reduce(rc, **spec["args"])
    need = window_bytes.held_expert_matrices(_load(REAL), 4400 / 5120)
    assert need == pytest.approx(0.859375 * 6.3417e9, rel=1e-3)
    assert share == pytest.approx(100.0 * need / 819e9 / 7.2e-3)
    assert 80 < share < 100
    bare = Trace(modules, [("%fusion.1 = fusion", ms, ms)], [])
    reduce = expert_gmm_roofline.reduce
    assert reduce(_rc(COUNTERS, trace=bare), **spec["args"]) is None
    assert reduce(_rc({}, trace=rc.trace), **spec["args"]) is None
    assert reduce(_rc(COUNTERS), **spec["args"]) is None
    for other in OTHERS:
        assert reduce(_rc(COUNTERS, trace=rc.trace, config=other),
                      **spec["args"]) is None
    # the next family is a data file: the hybrid family's byte functions
    # through this reader give what its own reader gives
    from benchmark.reducers import moe_gmm_roofline

    hybrid = _rc(COUNTERS, trace=rc.trace, config=OTHERS[0])
    own = _load("benchmark/layer_metrics/moe_gmm_roofline.serve.json")
    assert reduce(hybrid, ops="^gmm$", module="^jit__decode",
                  bytes_module="hybrid_bytes",
                  bytes_fn="held_expert_matrices") == pytest.approx(
        moe_gmm_roofline.reduce(hybrid, **own["args"]))


# ---- the reference check can tell a fault ---------------------------------

@pytest.fixture(scope="module")
def tiny():
    from benchmark.runners import serve_window as sw

    config = _load("benchmark/configs/tiny-mellum.json")
    traffic = _load("benchmark/traffic/tiny-repo-closed.json")
    # 59 real rows: the last chunk is a padded 11 of 16
    traffic = dict(traffic, check=dict(traffic["check"], prompt_len=59))
    model = sw.build_model(config)
    params = sw.make_params(model, config, 3000000019)
    return sw, config, traffic, model, params


def _check(tiny, *, reference_config=None):
    from apex_tpu import serving as sv

    sw, config, traffic, model, params = tiny
    eng = sv.DecodeEngine(model, params, **traffic["engine"])
    return sw.check_against_reference(eng, reference_config or config,
                                      traffic, 7)


def test_seeded_weights_follow_the_configuration(tiny):
    _, config, _, _, params = tiny
    p = params["params"]
    attn = p["layers_0"]["self_attn"]
    assert attn["q_proj"]["kernel"].shape == (64, 4 * 16)
    assert attn["k_proj"]["kernel"].shape == (64, 2 * 16)
    assert sorted(attn) == ["k_proj", "o_proj", "q_proj", "v_proj"]
    assert (np.asarray(p["layers_3"]["input_layernorm"]["scale"]) == 1).all()
    mlp = p["layers_7"]["mlp"]
    assert sorted(mlp) == ["experts_down", "experts_gate", "experts_up",
                           "router_kernel"]          # no bias, no shared
    assert mlp["experts_gate"].shape == (8, 64, 32)
    assert mlp["router_kernel"].shape == (64, 8)
    assert mlp["router_kernel"].dtype == np.float32
    assert abs(float(np.asarray(p["embed_tokens"]["embedding"]).std())
               - 0.02) < 2e-3


def test_routers_and_head_leave_the_shared_directions_out(tiny):
    """Every router column and every head row is at right angles to every
    direction taken out: the mean of the rows it reads on the reference's
    walk of the calibration sequence, and that of the late rows of longer
    sequences; layer 0's first, whose rows hang on no
    router, is recomputed here through the reference alone; the router has
    no bias and no other leaf moves."""
    import jax

    from benchmark.reference import mellum as ref

    sw, config, _, model, params = tiny
    seed = 3000000019
    raw = sw.draw_params(model, seed)
    made, shared = sw.route_as_trained(model, raw, config, seed)
    same = jax.tree.map(lambda a, b: bool((np.asarray(a) == np.asarray(b))
                                          .all()), made, params)
    assert all(jax.tree.leaves(same))                   # the fixture's
    layers = len(config["layer_types"])
    assert [len(d) for d in shared] == [2] * (layers + 1)

    def right_angles(matrix, directions, axis):
        m = np.moveaxis(np.asarray(matrix, np.float64), axis, 0)
        for d in directions:
            d = np.asarray(d, np.float64)
            assert np.abs(d / np.linalg.norm(d) @ m).max() < 1e-5 * (
                np.linalg.norm(m, axis=0).mean())

    for i in range(layers):
        mlp = made["params"][f"layers_{i}"]["mlp"]
        assert "router_bias" not in mlp
        right_angles(mlp["router_kernel"], shared[i], 0)
    right_angles(made["params"]["lm_head"], shared[-1], 1)
    ids = np.random.default_rng([seed, sw.BALANCE_TOKENS]).integers(
        0, config["vocab_size"], sw.BALANCE_TOKENS).astype(np.int32)
    layer = raw["params"]["layers_0"]
    x = ref.embed(raw, ids)
    x = x + ref.attention_out(x, layer, config, config["layer_types"][0])
    mean = np.asarray(ref.normed(x, layer["post_attention_layernorm"],
                                 config), np.float64).mean(axis=0)
    first = np.asarray(shared[0][0], np.float64)
    assert mean @ first / np.linalg.norm(mean) > 1 - 1e-5
    moved = {jax.tree_util.keystr(path) for path, ok
             in jax.tree_util.tree_leaves_with_path(jax.tree.map(
                 lambda a, b: bool((np.asarray(a) == np.asarray(b)).all()),
                 made, raw)) if not ok}
    assert moved == {"['params']['lm_head']"} | {
        f"['params']['layers_{i}']['mlp']['router_kernel']"
        for i in range(layers)}


def test_reference_check_passes_the_system_as_built(tiny):
    res = _check(tiny)
    assert res["reference_ok"], res
    # every logit the engine handed back is printed: the last row of each of
    # four chunks (16 + 16 + 16 + a padded 11) and four steps; two decide
    assert len(res["reference_rel_err_each"]) == 8
    assert max(res["reference_rel_err_each"]) < 1e-5
    assert res["reference_rel_err_first_token"] < 1e-5
    assert res["reference_rel_err_after_decode"] < 1e-5


@pytest.mark.parametrize("fault", [
    "window_one_wider", "full_layers_plain_rope", "attention_factor_left_out",
    "window_layers_yarn", "weights_not_renormalised", "one_expert_fewer"])
def test_reference_check_fails_a_fault(tiny, fault):
    config = tiny[1]
    rope = config["rope_parameters"]
    changed = {
        "window_one_wider": {"sliding_window": config["sliding_window"] + 1},
        "full_layers_plain_rope": {
            "rope_parameters": {**rope, FULL: rope[WINDOW]}},
        "attention_factor_left_out": {"rope_parameters": {
            **rope, FULL: {**rope[FULL], "attention_factor": 1.0}}},
        "window_layers_yarn": {
            "rope_parameters": {**rope, WINDOW: rope[FULL]}},
        "weights_not_renormalised": {"norm_topk_prob": False},
        "one_expert_fewer": {
            "num_experts_per_tok": config["num_experts_per_tok"] - 1},
    }
    res = _check(tiny, reference_config=dict(config, **changed[fault]))
    worst = max(res["reference_rel_err_first_token"],
                res["reference_rel_err_after_decode"])
    assert not res["reference_ok"] and worst > 10 * res[
        "reference_tolerance"], (fault, res)


# ---- the runner end to end, as the driver would run it --------------------

def _run(*extra):
    command = _load(MANIFEST)["command"]
    return subprocess.run(
        [sys.executable, *command[1:], "--manifest", MANIFEST, "--workload",
         CELL, "--seed", "3000000019", "--seconds", "1", "--rehearse",
         *extra],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, BENCH_RUN="ignored"))


@pytest.fixture(scope="module")
def untraced():
    proc = _run("--trace", "0")
    assert proc.returncode == 0, proc.stderr[-2000:]
    return [json.loads(l) for l in proc.stdout.strip().splitlines()
            if l.startswith("{")]


@pytest.fixture(scope="module")
def traced():
    proc = _run("--trace", "1")
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_rehearsal_reports_the_end_to_end_metrics(untraced):
    last = untraced[-1]
    assert set(last["metrics"]) == {"serve_tok_s", "setup_s"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 4 and last["rehearsal"] is True


def test_rehearsal_passes_every_check(untraced):
    notes = next(l for l in untraced if "notes" in l)["notes"]
    checks = notes["checks"]
    assert all(checks[k] is True for k in (
        "decode_compiles_is_1", "prefill_compiles_within_buckets",
        "no_compile_in_window", "no_request_failed", "gaps_match_tokens",
        "reference_ok"))
    assert checks["reference_rel_err_first_token"] < 1e-5
    assert checks["reference_rel_err_after_decode"] < 1e-5
    counted = notes["counters"]
    assert counted["window_live_rows"] > counted["window_rows"] > 0
    assert counted["moe_steps"] > 0 and counted["n_routed_experts"] == 8
    assert counted["moe_pairs"] == 2 * counted["moe_tokens"]


def test_traced_rehearsal_reports_the_counters(traced):
    m = traced["metrics"]
    # no device lines on the CPU: the rooflines, the share of the peak and
    # the device times are left out, the counters are there
    assert set(m) == {"batch_occupancy.serve", "experts_touched.serve",
                      "expert_load_max_over_mean.serve", "pairs_here.serve",
                      "itl_p95_ms.serve_tok_s", "ttft_p90_ms.serve_tok_s",
                      "window_rows_read.serve"}
    # prompts of 24-72 tokens against a window of 8: most rows are left
    # unread by the window layers
    assert 5 < m["window_rows_read.serve"]["value"] < 40
    # every expert is held: all of a token's choices land here
    assert m["pairs_here.serve"]["value"] == pytest.approx(100.0)
    assert 0 < m["experts_touched.serve"]["value"] <= 100
    assert traced["correct"] is True


def test_lower_precision_tool_reads_far_above_the_tolerance():
    """The tolerance's second reading, on the CPU at toy size: float8
    weights read percents where float32 against float32 reads 1e-7."""
    proc = subprocess.run(
        [sys.executable, "benchmark/tools/lower_precision_window.py",
         "--manifest", MANIFEST, "--workload", CELL, "--seeds", "5",
         "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    for at in ("_first_token", "_after_decode"):
        assert line[f"e4m3_weights_rel_err{at}"] > 1e3 * line["tolerance"]
        assert line[f"bf16_router_input_rel_err{at}"] < 1e-1
