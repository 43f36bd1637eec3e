"""The Xing4.0 cell's benchmark files and the retrieval cell beside it: the
configuration against the published one, the traffic against the cells'
stated shapes, the byte and operation counts against worked arithmetic, the
new reducers on hand-made spans and counters (and their silence where a
program has neither), the weights' calibration, the reference check's power
to tell a fault, and the runner end to end on the CPU rehearsal path."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import test_benchmark_manifest as rules
from benchmark.lib import mla_bytes, mla_flops
from benchmark.reducers import (
    ReduceContext,
    counter_ratio,
    mla_chunk_mfu,
    mla_decode_roofline,
)

ROOT = rules.ROOT
MANIFEST = "tests/benchmark/manifest_mhc.json"
CELL = "tiny-xing.tiny-mladoc-closed"
REAL = "benchmark/configs/xing4-29b-l6.json"
REAL_CELL = "xing4-29b-l6.mladoc-closed"
RAG_CELL = "mistral-7b-l16.rag-closed"
CHAT_CELL = "mistral-7b-l16.chat-closed"
# the published catalog of architectures (JSON lines), where one is at hand
CATALOG = os.environ.get("APEX_MODEL_CATALOG", "")
OTHERS = ("benchmark/configs/nemotron3-super-ep4-l11.json",
          "benchmark/configs/dots3-note-ep8-l5.json",
          "benchmark/configs/mellum2-12b-l8.json",
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
def test_mhc_manifest_keeps_the_manifest_rules(rule):
    getattr(rules, rule)(_load(MANIFEST))


def test_configuration_keeps_published_widths_and_states_its_cut(
        benchmark_manifest):
    c = _load(REAL)
    widths = {
        "hidden_size": 3584, "intermediate_size": 9216,
        "num_attention_heads": 32, "q_lora_rank": 768, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "moe_intermediate_size": 1024, "n_routed_experts": 64,
        "num_experts_per_tok": 4, "n_shared_experts": 1,
        "routed_scaling_factor": 2, "first_k_dense_replace": 2,
        "vocab_size": 131072, "hc_mult": 4, "hc_sinkhorn_iters": 20,
        "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
        "mhc_h_res_clamp_max": 30, "rms_norm_eps": 1e-06}
    assert {k: c[k] for k in widths} == widths
    assert c["rope_scaling"]["factor"] == 64
    assert c["reduced"] == ["num_hidden_layers", "num_nextn_predict_layers"]
    assert (c["num_hidden_layers"], c["num_nextn_predict_layers"]) == (6, 0)
    assert c["published"] == {"num_hidden_layers": 40,
                              "num_nextn_predict_layers": 1}
    assert c["published_layers_kept"] == list(range(6))
    assert c["experts_held"] == [0, 64]          # every expert, no share
    for said in ("8.35 GB", "3.02 GB", "11.4 GB", "multi-token-prediction"):
        assert said in c["reduced_why"], said
    assert "pipeline of one replica" in c["stands_for"]
    for convention in ("weights_dtype", "float32", "mhc_read_out", "mhc_eps",
                       "rope", "softmax_scale", "routing", "residual_streams",
                       "weights", "hc_init", "late_rows"):
        assert convention in c["assumed"], convention
    assert "arXiv:2512.24880" in c["assumed"]["mhc_read_out"]
    entry = next(e for e in benchmark_manifest["configs"]
                 if e["name"] == c["name"])
    assert entry["source"] == c["source"] and entry["file"] == REAL
    assert entry["reduced"] == c["reduced"]


@pytest.mark.skipif(not os.path.isfile(CATALOG),
                    reason="APEX_MODEL_CATALOG names no catalog")
def test_configuration_holds_every_number_of_the_catalogs_entry():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B")
    c = _load(REAL)
    assert c["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if c.get(k) != v)
    assert differs == sorted(c["reduced"])
    assert {k: row["config"][k] for k in c["reduced"]} == c["published"]


def _entries(manifest, cell):
    return {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
            if cell in m.get("workloads", [cell])}


def test_the_cells_are_the_issues_traffic(benchmark_manifest):
    """By name: the two cells, their configurations and traffic, the
    metrics each reports and those it must not; never where in the lists
    they stand, nor what else is there."""
    manifest = benchmark_manifest
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert (cells[REAL_CELL]["config"], cells[REAL_CELL]["traffic"],
            cells[REAL_CELL]["chips"]) == ("xing4-29b-l6", "mladoc-closed", 1)
    assert (cells[RAG_CELL]["config"], cells[RAG_CELL]["traffic"],
            cells[RAG_CELL]["chips"]) == ("mistral-7b-l16", "rag-closed", 1)
    mine = _entries(manifest, REAL_CELL)
    assert {"serve_tok_s", "setup_s", "decode_residual_ms.serve",
            "chunk_residual_ms.serve", "mla_decode_roofline.serve",
            "mla_chunk_mfu.serve", "experts_touched.serve",
            "expert_load_max_over_mean.serve", "pairs_here.serve",
            "moe_gmm_ms.serve", "batch_occupancy.serve",
            "scope_coverage.serve",
            "decode_experts_ms.serve", "chunk_experts_ms.serve",
            "decode_cache_ms.serve", "chunk_cache_ms.serve"} <= mine
    # what its reader cannot count for this family, and what its model does
    # not open
    assert not mine & {"expert_gmm_roofline.serve", "gated_gmm_roofline.serve",
                       "sparse_decode_roofline.serve", "dsa_keys_read.serve",
                       "chunk_select_ms.serve", "decode_state_ms.serve",
                       "decode_roofline.serve"}
    # the retrieval cell reports what the chat cell reports
    assert _entries(manifest, RAG_CELL) == _entries(manifest, CHAT_CELL)
    for name in ("decode_residual_ms.serve", "chunk_residual_ms.serve",
                 "mla_decode_roofline.serve", "mla_chunk_mfu.serve"):
        m = next(x for x in manifest["per_layer"] if x["name"] == name)
        assert (m["layer"], m["moves"], m["source"]) == (
            "engine", "serve_tok_s", "device_trace")
        assert m["workloads"] == [REAL_CELL]
    mlad = _load("benchmark/traffic/mladoc-closed.json")
    assert (mlad["runner"], mlad["loop"], mlad["clients"]) == (
        "serve_mhc", "closed", 16)
    assert mlad["engine"] == {"slots": 16, "max_len": 24576,
                              "prefill_len": 1024}
    assert mlad["prompt_len"] == {"dist": "uniform", "min": 18432,
                                  "max": 22528}
    assert mlad["output_len"] == {"dist": "fixed", "value": 512}
    assert (mlad["check"]["prompt_len"], mlad["check"]["decode_tokens"],
            mlad["check"]["routing_margin"]) == (4096, 28, 0.01)
    rag, chat = (_load(f"benchmark/traffic/{t}.json")
                 for t in ("rag-closed", "chat-closed"))
    assert (rag["runner"], rag["clients"], rag["engine"]) == (
        "serve", 16, {"slots": 16, "max_len": 2048, "prefill_len": 512})
    assert rag["prompt_len"] == {"dist": "uniform", "min": 1024, "max": 1792}
    assert rag["output_len"] == {"dist": "uniform", "min": 16, "max": 48}
    assert rag["strata"] == 16
    assert ({k: v for k, v in rag["check"].items() if k != "tolerance_why"}
            == {k: v for k, v in chat["check"].items()
                if k != "tolerance_why"})


def test_decode_step_bytes_are_the_issues_arithmetic():
    c = _load(REAL)
    assert mla_bytes.reads(c)
    assert not any(mla_bytes.reads(_load(o)) for o in OTHERS)
    assert mla_bytes.attention_matrices(c) == pytest.approx(28.41e6, rel=1e-3)
    assert mla_bytes.row_bytes(c) == 1280
    assert mla_bytes.expert_matrices(c) * 64 == pytest.approx(704.6e6,
                                                              rel=1e-3)
    # 16 lanes at ~20.8k rows, 64 % of the experts touched: ~8 GB a step
    step = mla_bytes.xing_decode_step(c, lanes=16, latent_rows=16 * 20800 * 6,
                                      touched_share=0.644)
    assert 7.5e9 < step < 8.5e9
    assert mla_bytes.xing_decode_step(
        c, lanes=16, latent_rows=0, touched_share=0.0) == pytest.approx(
        mla_bytes.outside_experts(c) + 16 * 6 * 1280)


def test_prefill_chunk_operations_are_the_issues_arithmetic():
    c = _load(REAL)
    first = mla_flops.xing_prefill_chunk(c, tokens=1024, offset=0)
    late = mla_flops.xing_prefill_chunk(c, tokens=1024, offset=19456)
    # causal attention over every visible row grows with the offset; a
    # chunk 19k rows in is over 3 x the first one's work
    assert 1.2e12 < first < 1.5e12 and 4e12 < late < 5e12
    # the head once, not a row
    more = mla_flops.xing_prefill_chunk(c, tokens=1025, offset=0)
    assert more - first < 2.0 * 1.1 * 4e9


COUNTERS = {"moe_steps": 80, "moe_tokens": 1200, "moe_pairs": 4800,
            "moe_touched": 3300, "moe_max_load": 300, "latent_rows": 10 ** 7}


def _rc(counters, trace=None, config=REAL):
    return ReduceContext(trace, counters, _load(config),
                         _load("benchmark/traffic/mladoc-closed.json"),
                         "TPU v5 lite")


def test_expert_metrics_from_hand_made_counters():
    touched = _load("benchmark/layer_metrics/experts_touched.serve.json")
    assert counter_ratio.reduce(_rc(COUNTERS), **touched["args"]) == \
        pytest.approx(100.0 * 3300 / (80 * 64))
    here = _load("benchmark/layer_metrics/pairs_here.serve.json")
    assert counter_ratio.reduce(_rc(COUNTERS), **here["args"]) == \
        pytest.approx(100.0)


def test_decode_roofline_from_hand_made_spans(monkeypatch):
    spec = _load("benchmark/layer_metrics/mla_decode_roofline.serve.json")
    args = spec["args"]
    reduce = mla_decode_roofline.reduce
    assert reduce(_rc(COUNTERS), **args) is None            # no trace
    ps = mla_decode_roofline.ps
    attrs = {"lanes": 16, "kv_tokens": 332800, "latent_rows": 1996800}
    pairs = [(("jit__decode", 0, ms * 1e6), ("engine.decode", 0, 1e5, attrs))
             for ms in (11.0, 12.0, 13.0)]
    monkeypatch.setattr(ps, "of", lambda rc: object())
    monkeypatch.setattr(ps, "paired", lambda pt, module, span: pairs)
    got = reduce(_rc(COUNTERS, trace=object()), **args)
    need = mla_bytes.xing_decode_step(
        _load(REAL), lanes=16, latent_rows=1996800,
        touched_share=3300 / (80 * 64))
    assert got == pytest.approx(100.0 * need / 12e-3 / 819e9)
    assert 60 < got < 100
    # spans without the latent rows (another model's, the parent's), a run
    # without the expert counters, another family's configuration: nothing
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
    spec = _load("benchmark/layer_metrics/mla_chunk_mfu.serve.json")
    args = spec["args"]
    reduce = mla_chunk_mfu.reduce
    assert reduce(_rc({}), **args) is None
    ps = mla_chunk_mfu.ps

    def pair(ms, bucket, tokens, offset):
        attrs = {"slot": 0, "bucket": bucket, "tokens": tokens}
        if offset is not None:
            attrs["offset"] = offset
        return (("jit__prefill", 0, ms * 1e6),
                ("engine.prefill_chunk", 0, 1e5, attrs))

    pairs = [pair(50.0, 1024, 1024, 4096), pair(60.0, 1024, 1024, 8192),
             pair(80.0, 1024, 1024, 16384), pair(5.0, 64, 40, 20480)]
    monkeypatch.setattr(ps, "of", lambda rc: object())
    monkeypatch.setattr(ps, "paired", lambda pt, module, span: pairs)
    got = reduce(_rc({}, trace=object()), **args)
    rates = sorted(mla_flops.xing_prefill_chunk(
        _load(REAL), tokens=1024, offset=o) / (ms * 1e-3)
        for ms, o in ((50.0, 4096), (60.0, 8192), (80.0, 16384)))
    assert got == pytest.approx(100.0 * rates[1] / 197e12)
    assert 10 < got < 50
    old = [pair(60.0, 1024, 1024, None)]
    monkeypatch.setattr(ps, "paired", lambda pt, module, span: old)
    assert reduce(_rc({}, trace=object()), **args) is None
    monkeypatch.setattr(ps, "paired", lambda pt, module, span: pairs)
    for other in OTHERS:
        assert reduce(_rc({}, trace=object(), config=other), **args) is None


def test_residual_metrics_read_the_residual_scope():
    for name, module in (("decode_residual_ms.serve", "^jit__decode"),
                         ("chunk_residual_ms.serve", "^jit__prefill")):
        spec = _load(f"benchmark/layer_metrics/{name}.json")
        assert spec["reducer"] == "scope_time"
        assert spec["args"]["scopes"] == "^residual$"
        assert spec["args"]["module"] == module
    assert _load("benchmark/layer_metrics/chunk_residual_ms.serve.json")[
        "args"]["pick"] == "largest"


# ---- the weights and the reference check ----------------------------------

@pytest.fixture(scope="module")
def tiny():
    from benchmark.runners import serve_mhc as sm

    config = _load("benchmark/configs/tiny-xing.json")
    traffic = _load("benchmark/traffic/tiny-mladoc-closed.json")
    # 59 real rows: the last chunk is a padded 11 of 16
    traffic = dict(traffic, check=dict(traffic["check"], prompt_len=59))
    model = sm.build_model(config)
    params = sm.make_params(model, config, 3000000019)
    return sm, config, traffic, model, params


def _check(tiny, *, reference_config=None):
    from apex_tpu import serving as sv

    sm, config, traffic, model, params = tiny
    eng = sv.DecodeEngine(model, params, **traffic["engine"])
    return sm.check_against_reference(eng, reference_config or config,
                                      traffic, 7)


def test_seeded_weights_follow_the_configuration(tiny):
    _, config, _, _, params = tiny
    p = params["params"]
    hc = p["layers_1"]["attn_hc"]
    n, h = config["hc_mult"], config["hidden_size"]
    assert hc["phi"].shape == (n * h, 2 * n + n * n)
    assert hc["phi"].dtype == np.float32
    assert np.allclose(np.asarray(hc["alpha"]), 1 / (0.02 * (n * h) ** 0.5))
    assert 0.5 < float(np.asarray(hc["bias"]).std()) < 1.5
    assert p["head_hc"]["phi"].shape == (n * h, n)
    attn = p["layers_0"]["self_attn"]
    assert sorted(attn) == ["kv_a_norm", "kv_a_proj", "kv_b_proj", "o_proj",
                            "q_a_norm", "q_a_proj", "q_b_proj"]
    mlp = p["layers_2"]["mlp"]
    assert mlp["router_kernel"].shape == (h, 8)
    assert mlp["experts_gate"].shape == (8, h, 24)
    assert "shared_gate" in mlp and "experts_gate" not in p["layers_0"]["mlp"]


def test_routers_and_head_are_calibrated_where_the_cell_serves(tiny):
    """Against the weights as drawn only the routers, their selection biases
    and the head move; each router column is at right angles to the mean of
    the rows it read on the reference's walk, and the biases are no longer
    zero."""
    import jax

    sm, config, _, model, params = tiny
    raw = sm.draw_params(model, config, 3000000019)
    moved = {jax.tree_util.keystr(path) for path, ok
             in jax.tree_util.tree_leaves_with_path(jax.tree.map(
                 lambda a, b: bool((np.asarray(a) == np.asarray(b)).all()),
                 params, raw)) if not ok}
    layers = config["num_hidden_layers"]
    dense = config["first_k_dense_replace"]
    assert moved == {"['params']['lm_head']"} | {
        f"['params']['layers_{i}']['mlp']['{k}']"
        for i in range(dense, layers) for k in ("router_kernel",
                                                "router_bias")}
    for i in range(dense, layers):
        assert np.abs(np.asarray(params["params"][f"layers_{i}"]["mlp"][
            "router_bias"])).max() > 0


def test_reference_check_passes_the_system_as_built(tiny):
    res = _check(tiny)
    assert res["reference_ok"], res
    # every logit the engine handed back is printed: the last row of each of
    # four chunks (16 + 16 + 16 + a padded 11) and four steps
    assert len(res["reference_rel_err_each"]) == 8
    assert max(res["reference_rel_err_each"]) < 1e-5
    # one number decides: at the toy's routing margin 0 every position is
    # decided, and the largest reading is the number
    assert res["reference_positions_decided"] == 8
    assert res["reference_rel_err"] == pytest.approx(
        max(res["reference_rel_err_each"]), rel=1e-2)
    assert res["reference_rel_err"] < 1e-5
    assert res["reference_rel_err_stacked"] < 1e-5


@pytest.mark.parametrize("case, readings, margins, worst, ok", [
    # a far reading where the reference's routing is near a tie is left out
    ("near_tie_left_out", [0.01, 0.3, 0.02, 0.01], [0.02, 0.001, 0.02, 0.5],
     0.02, True),
    # ... and counts where every expert layer's choice is decided
    ("decided_counts", [0.01, 0.3, 0.02, 0.01], [0.02, 0.011, 0.02, 0.5],
     0.3, False),
    # no position decided: the median reading decides
    ("none_decided", [0.01, 0.3, 0.02, 0.4], [0.0] * 4, 0.16, False),
    ("none_decided_most_close", [0.01, 0.3, 0.02, 0.01], [0.0] * 4, 0.015,
     True)])
def test_judge_counts_the_positions_the_reference_routes_decisively(
        case, readings, margins, worst, ok):
    from benchmark.runners import serve_mhc as sm

    traffic = {"engine": {"prefill_len": 1},
               "check": {"prompt_len": 4, "decode_tokens": 0,
                         "routing_margin": 0.01, "tolerance": 0.05}}
    want = np.ones((4, 3), np.float32)
    # a difference along one logit of the reading's size times the norm
    got = want + np.asarray(readings, np.float32)[:, None] * np.sqrt(
        3.0) * np.eye(3, dtype=np.float32)[0]
    # two expert layers: a position's margin is its smaller one
    res = sm.judge(got, want, np.stack([np.asarray(margins) + 1.0,
                                        np.asarray(margins)], axis=-1),
                   traffic)
    assert res["reference_rel_err"] == pytest.approx(worst, rel=1e-3), case
    assert res["reference_ok"] is ok, case


@pytest.mark.parametrize("fault", [
    "streams_mixed_evenly", "routed_scaling_factor_1",
    "shared_expert_dropped", "no_yarn_mscale"])
def test_reference_check_fails_a_fault(tiny, fault):
    config = tiny[1]
    changed = {
        # every logit clamped to 0: H_res a quarter everywhere
        "streams_mixed_evenly": {"mhc_h_res_clamp_min": 0,
                                 "mhc_h_res_clamp_max": 0},
        "routed_scaling_factor_1": {"routed_scaling_factor": 1},
        "shared_expert_dropped": {"n_shared_experts": 0},
        "no_yarn_mscale": {"rope_scaling": dict(
            config["rope_scaling"], mscale=0, mscale_all_dim=0)},
    }
    res = _check(tiny, reference_config=dict(config, **changed[fault]))
    assert not res["reference_ok"] and res["reference_rel_err"] > 10 * res[
        "reference_tolerance"], (fault, res)


# ---- the runner end to end, as the benchmark command runs it -------------

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
    assert counted["latent_rows"] > counted["moe_steps"] > 0
    assert counted["moe_pairs"] == 2 * counted["moe_tokens"]


def test_traced_rehearsal_reports_the_counters(traced):
    m = traced["metrics"]
    # no device lines on the CPU: the rooflines, the share of the peak and
    # the device times are left out, the counters are there
    assert set(m) == {"batch_occupancy.serve", "experts_touched.serve",
                      "expert_load_max_over_mean.serve", "pairs_here.serve",
                      "itl_p95_ms.serve_tok_s", "ttft_p90_ms.serve_tok_s"}
    assert m["pairs_here.serve"]["value"] == pytest.approx(100.0)
    assert traced["correct"] is True


def test_lower_precision_tool_reads_far_above_the_tolerance():
    """The tolerance's two readings, on the CPU at toy size: the system's
    own check reads 1e-7 and float8 weights read percents, which the
    runner's own verdict refuses; in float32 the system's programs choose
    the reference's experts at every position."""
    proc = subprocess.run(
        [sys.executable, "benchmark/tools/lower_precision_mhc.py",
         "--manifest", MANIFEST, "--workload", CELL, "--seeds", "5",
         "--e4m3", "5", "--routes", "5", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["reference_ok"] is True and line["e4m3_weights_ok"] is False
    for at in ("", "_stacked", "_first_token", "_after_decode"):
        assert line[f"reference_rel_err{at}"] < 1e-5
        assert line[f"e4m3_weights_rel_err{at}"] > 1e3 * line[
            "reference_tolerance"]
    assert line["routes_differ_layers"] == [[]] * 8
    assert len(line["routes_margin_min"]) == 8
    assert min(line["routes_margin_min"]) > 0
