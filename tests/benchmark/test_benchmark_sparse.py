"""The dots3-note cell's benchmark files: the configuration against the
published one, the byte and operation counts against the issue's
arithmetic, the new reducers on hand-made spans and counters (and their
silence where a program has neither), the reference check's power to tell a
fault, and the runner end to end on the CPU rehearsal path."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import test_benchmark_manifest as rules
from benchmark.lib import sparse_bytes, sparse_flops
from benchmark.lib.trace import Trace
from benchmark.reducers import (
    ReduceContext,
    counter_ratio,
    gated_gmm_roofline,
    op_time,
    prefill_chunk_mfu,
    sparse_decode_roofline,
)

ROOT = rules.ROOT
MANIFEST = "tests/benchmark/manifest_sparse.json"
CELL = "tiny-dots3-note.tiny-longdoc-closed"
REAL = "benchmark/configs/dots3-note-ep8-l5.json"
REAL_CELL = "dots3-note-ep8-l5.longdoc-closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


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
def test_sparse_manifest_keeps_the_manifest_rules(rule):
    getattr(rules, rule)(_load(MANIFEST))


def test_configuration_keeps_published_widths_and_states_its_cut():
    c = _load(REAL)
    widths = {
        "hidden_size": 5120, "intermediate_size": 13824,
        "num_attention_heads": 128, "q_lora_rank": 1024, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "index_n_heads": 64, "index_head_dim": 128, "index_topk": 2048,
        "swa_num_attention_heads": 64, "swa_q_lora_rank": 1024,
        "swa_kv_lora_rank": 1024, "swa_qk_nope_head_dim": 192,
        "swa_qk_rope_head_dim": 64, "swa_v_head_dim": 128,
        "sliding_window_size": 513, "moe_intermediate_size": 1536,
        "num_experts_per_tok": 8, "n_shared_experts": 1,
        "rope_theta": 80000000, "swa_rope_theta": 50000,
        "first_k_dense_replace": 1}
    assert {k: c[k] for k in widths} == widths
    assert c["reduced"] == ["num_hidden_layers", "layer_types",
                            "n_routed_experts", "vocab_size"]
    published = c["published"]
    assert set(published) == set(c["reduced"])
    full, window = "full_attention", "sliding_attention"
    assert c["layer_types"] == [full, window, window, window, full]
    assert len(c["layer_types"]) == c["num_hidden_layers"] == 5
    # the kept layers are the published model's 0 and 2-5: one whole period
    kept = [published["layer_types"][i] for i in c["published_layers_kept"]]
    assert kept == c["layer_types"]
    assert published["layer_types"][:2] == [full, full]
    assert [published["layer_types"].count(k) for k in (full, window)] == [
        13, 33]
    assert published["num_hidden_layers"] == 46
    assert (published["n_routed_experts"], c["n_routed_experts"],
            c["experts_held"]) == (256, 32, [0, 32])
    assert c["vocab_size"] * 8 == published["vocab_size"] == 152064
    assert "8 chips" in c["reduced_why"] and "8-chip" in c["stands_for"]
    assert "2 sequences a chip" in c["stands_for"]
    for convention in ("apply_mla_qkv_lora_rescale", "selector",
                       "attention_gate_type", "sliding_window_size",
                       "weights", "weights_dtype", "float32"):
        assert convention in c["assumed"], convention
    entry = next(e for e in _load("BENCHMARK.json")["configs"]
                 if e["name"] == c["name"])
    assert entry["source"] == c["source"] and entry["file"] == REAL
    assert entry["reduced"] == c["reduced"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_configuration_holds_every_number_of_the_catalogs_entry():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "dots3-note-prev")
    c = _load(REAL)
    assert c["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if c.get(k) != v)
    assert differs == sorted(c["reduced"])
    assert {k: row["config"][k] for k in c["reduced"]} == c["published"]


def test_the_cell_is_the_issues_traffic():
    manifest = _load("BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "dots3-note-ep8-l5", "longdoc-closed", 1)
    t = _load("benchmark/traffic/longdoc-closed.json")
    assert (t["runner"], t["loop"], t["clients"], t["strata"]) == (
        "serve_sparse", "closed", 16, 16)
    assert t["engine"] == {"slots": 16, "max_len": 32768,
                           "prefill_len": 1024}
    # the issue's narrowing about its means (20,480 and 512): the wide
    # ranges spread serve_tok_s by 7-8 % over six seeds (lengths_why)
    assert t["prompt_len"] == {"dist": "uniform", "min": 18432, "max": 22528}
    assert t["output_len"] == {"dist": "fixed", "value": 512}
    assert "12,288-28,672" in t["lengths_why"]
    check = t["check"]
    assert check["prompt_len"] == 4096 == 2 * _load(REAL)["index_topk"]
    assert check["decode_tokens"] == 8 and check["tolerance"] == 0.24
    # it reports serve_tok_s and setup_s, and the gaps' tail per layer
    mine = [m["name"] for m in manifest["end_to_end"]
            if REAL_CELL in m.get("workloads", [REAL_CELL])]
    assert mine == ["serve_tok_s", "setup_s"]
    layer = {m["name"] for m in manifest["per_layer"]
             if REAL_CELL in m.get("workloads", [])}
    assert {"sparse_decode_roofline.serve", "prefill_chunk_mfu.serve",
            "gated_gmm_roofline.serve", "dsa_keys_read.serve",
            "itl_p95_ms.serve_tok_s", "moe_gmm_ms.serve",
            "pairs_here.serve"} <= layer
    assert not layer & {"decode_roofline.serve", "moe_gmm_roofline.serve",
                        "hybrid_decode_roofline.serve"}
    assert len(layer) == 18


def test_decode_step_bytes_are_the_issues_arithmetic():
    c = _load(REAL)
    full = sparse_bytes.attention_matrices(c, "full_attention")
    window = sparse_bytes.attention_matrices(c, "sliding_attention")
    assert round(full / 1e6, 1) == 144.0 and round(window / 1e6, 1) == 90.8
    assert sparse_bytes.expert_matrices(c) == 3 * 5120 * 1536
    assert sparse_bytes.row_bytes(c) == {"index": 256, "latent": 1152,
                                         "window": 2176}
    need = sparse_bytes.dots3_decode_step
    zero = dict(lanes=0, index_rows=0, attended_rows=0, window_rows=0)
    other = need(c, touched_share=0.0, **zero)
    assert other == sparse_bytes.outside_experts(c)
    assert 1.94e9 < other < 1.96e9          # every matrix outside the experts
    experts = need(c, touched_share=1.0, **zero) - other
    assert experts == 4 * 32 * 3 * 5120 * 1536 * 2           # 6.04 GB
    rows = need(c, touched_share=0.0, lanes=0, index_rows=1000,
                attended_rows=100, window_rows=10) - other
    assert rows == 1000 * 256 + 100 * 1152 + 10 * 2176
    appended = need(c, touched_share=0.0, **dict(zero, lanes=16)) - other
    assert appended == 16 * (2 * 1408 + 3 * 2176)
    # a lane with 20,480 live rows: 5.2 MB of keys and 2.4 MB of rows a
    # full layer, where every row read would be 23.6 MB
    assert round(20480 * 256 / 1e6, 1) == 5.2
    assert round(2048 * 1152 / 1e6, 1) == 2.4


def test_prefill_chunk_operations_are_the_issues_arithmetic():
    c = _load(REAL)
    chunk = sparse_flops.dots3_prefill_chunk
    prompt = sum(chunk(c, tokens=1024, offset=o)
                 for o in range(0, 20480, 1024))
    assert 69e12 < prompt < 72e12        # the issue: 39.6 + 23.4 + 6.9 + 1.5
    first, last = chunk(c, tokens=1024, offset=0), chunk(c, tokens=1024,
                                                         offset=19456)
    assert 2.2e12 < first < 2.5e12 and 3.8e12 < last < 4.0e12
    # the selector's scores alone grow with the offset once every query
    # attends index_topk rows and a full window
    grow = chunk(c, tokens=1024, offset=20480) - last
    assert grow == pytest.approx(2.0 * 2 * 1024 * 1024 * 64 * 128)
    # sums of visible keys, capped
    seen = sparse_flops._visible
    assert seen(0, 4, 100) == 1 + 2 + 3 + 4
    assert seen(2, 4, 4) == 3 + 4 + 4 + 4 and seen(10, 3, 4) == 12


COUNTERS = {"moe_steps": 40, "moe_tokens": 600, "moe_pairs": 610,
            "moe_touched": 500, "moe_max_load": 130, "index_rows": 90000,
            "attended_rows": 9000, "window_rows": 7000}


def _rc(counters, trace=None, config=REAL):
    return ReduceContext(trace, counters, _load(config),
                         _load("benchmark/traffic/longdoc-closed.json"),
                         "TPU v5 lite")


def test_keys_read_and_expert_metrics_from_hand_made_counters():
    spec = _load("benchmark/layer_metrics/dsa_keys_read.serve.json")
    assert spec["reducer"] == "counter_ratio"
    assert counter_ratio.reduce(_rc(COUNTERS), **spec["args"]) == \
        pytest.approx(10.0)
    # a program that counts no rows (any other model, the parent): nothing
    assert counter_ratio.reduce(_rc({"steps": 3}), **spec["args"]) is None
    touched = _load("benchmark/layer_metrics/experts_touched.serve.json")
    assert counter_ratio.reduce(_rc(COUNTERS), **touched["args"]) == \
        pytest.approx(100.0 * 500 / (40 * 32))
    here = _load("benchmark/layer_metrics/pairs_here.serve.json")
    assert counter_ratio.reduce(_rc(COUNTERS), **here["args"]) == \
        pytest.approx(100.0 * 610 / (600 * 8))


def test_decode_roofline_from_hand_made_spans(monkeypatch):
    spec = _load("benchmark/layer_metrics/sparse_decode_roofline.serve.json")
    args = spec["args"]
    reduce = sparse_decode_roofline.reduce
    assert reduce(_rc(COUNTERS), **args) is None            # no trace
    ps = sparse_decode_roofline.ps
    attrs = {"lanes": 16, "kv_tokens": 320000, "index_rows": 640000,
             "attended_rows": 65536, "window_rows": 24624}
    pairs = [(("jit__decode", 0, ms * 1e6), ("engine.decode", 0, 1e5, attrs))
             for ms in (8.0, 10.0, 12.0)]
    monkeypatch.setattr(ps, "of", lambda rc: object())
    monkeypatch.setattr(ps, "paired", lambda pt, module, span: pairs)
    got = reduce(_rc(COUNTERS, trace=object()), **args)
    need = sparse_bytes.dots3_decode_step(
        _load(REAL), lanes=16, index_rows=640000, attended_rows=65536,
        window_rows=24624, touched_share=500 / (40 * 32))
    assert got == pytest.approx(100.0 * need / 10e-3 / 819e9)
    assert 40 < got < 70
    # spans without the row counts (a commit before the latent cache), a run
    # without the expert counters, another family's configuration: nothing
    bare = [(m, (n, s, d, {"lanes": 16, "kv_tokens": 1}))
            for m, (n, s, d, _) in pairs]
    monkeypatch.setattr(ps, "paired", lambda pt, module, span: bare)
    assert reduce(_rc(COUNTERS, trace=object()), **args) is None
    monkeypatch.setattr(ps, "paired", lambda pt, module, span: pairs)
    assert reduce(_rc({}, trace=object()), **args) is None
    assert reduce(_rc(COUNTERS, trace=object(),
                      config="benchmark/configs/nemotron3-super-ep4-l11.json"),
                  **args) is None


def test_prefill_mfu_from_hand_made_spans(monkeypatch):
    spec = _load("benchmark/layer_metrics/prefill_chunk_mfu.serve.json")
    args = spec["args"]
    reduce = prefill_chunk_mfu.reduce
    assert reduce(_rc({}), **args) is None
    ps = prefill_chunk_mfu.ps

    def pair(ms, bucket, tokens, offset):
        attrs = {"slot": 0, "bucket": bucket, "tokens": tokens}
        if offset is not None:
            attrs["offset"] = offset
        return (("jit__prefill", 0, ms * 1e6),
                ("engine.prefill_chunk", 0, 1e5, attrs))

    pairs = [pair(60.0, 1024, 1024, 4096), pair(70.0, 1024, 1024, 8192),
             pair(80.0, 1024, 1024, 16384), pair(5.0, 64, 40, 20480)]
    monkeypatch.setattr(ps, "of", lambda rc: object())
    monkeypatch.setattr(ps, "paired", lambda pt, module, span: pairs)
    got = reduce(_rc({}, trace=object()), **args)
    need = sparse_flops.dots3_prefill_chunk(_load(REAL), tokens=1024,
                                            offset=8192)
    # the median chunk's; the small bucket's chunk does not count
    assert got == pytest.approx(100.0 * need / 70e-3 / 197e12)
    assert 15 < got < 30
    old = [pair(60.0, 1024, 1024, None)]
    monkeypatch.setattr(ps, "paired", lambda pt, module, span: old)
    assert reduce(_rc({}, trace=object()), **args) is None


def test_gated_gmm_roofline_from_a_hand_made_trace():
    """Twelve gmm calls of 0.5 ms in each of five decode executions, one gmm
    in a prefill program that must not count."""
    ms = 1_000_000
    modules = [("jit__decode(1)", k * 100 * ms, 30 * ms) for k in range(5)]
    modules.append(("jit__prefill(2)", 40 * ms, 30 * ms))
    ops = [(f"%gmm.{j} = custom-call", k * 100 * ms + j * 2 * ms, ms // 2)
           for k in range(5) for j in range(12)]
    ops.append(("%gmm.99 = custom-call", 41 * ms, 7 * ms))
    rc = _rc(COUNTERS, trace=Trace(modules, ops, []))
    time_spec = _load("benchmark/layer_metrics/moe_gmm_ms.serve.json")
    assert op_time.reduce(rc, **time_spec["args"]) == pytest.approx(6.0)
    spec = _load("benchmark/layer_metrics/gated_gmm_roofline.serve.json")
    share = gated_gmm_roofline.reduce(rc, **spec["args"])
    need = sparse_bytes.held_expert_matrices(_load(REAL), 500 / 1280)
    assert need == pytest.approx(0.390625 * 6.0398e9, rel=1e-3)
    assert share == pytest.approx(100.0 * need / 819e9 / 6e-3)
    bare = Trace(modules, [("%fusion.1 = fusion", ms, ms)], [])
    reduce = gated_gmm_roofline.reduce
    assert reduce(_rc(COUNTERS, trace=bare), **spec["args"]) is None
    assert reduce(_rc({}, trace=rc.trace), **spec["args"]) is None
    assert reduce(_rc(COUNTERS), **spec["args"]) is None
    assert reduce(_rc(COUNTERS, trace=rc.trace,
                      config="benchmark/configs/nemotron3-super-ep4-l11.json"),
                  **spec["args"]) is None


# ---- the reference check can tell a fault ---------------------------------

@pytest.fixture(scope="module")
def tiny():
    from benchmark.runners import serve_sparse as ss

    config = _load("benchmark/configs/tiny-dots3-note.json")
    traffic = _load("benchmark/traffic/tiny-longdoc-closed.json")
    # 59 real rows: the last chunk is a padded 11 of 16
    traffic = dict(traffic, check=dict(traffic["check"], prompt_len=59))
    model = ss.build_model(config)
    params = ss.make_params(model, config, 3000000019)
    return ss, config, traffic, model, params


def _check(tiny, *, reference_config=None):
    from apex_tpu import serving as sv

    ss, config, traffic, model, params = tiny
    eng = sv.DecodeEngine(model, params, **traffic["engine"])
    return ss.check_against_reference(eng, reference_config or config,
                                      traffic, 7)


def test_seeded_weights_follow_the_configuration(tiny):
    _, config, _, _, params = tiny
    p = params["params"]
    attn = p["layers_0"]["self_attn"]
    assert (np.asarray(attn["q_a_norm"]["scale"]) == 1).all()
    assert (np.asarray(attn["index_k_norm"]["bias"]) == 0).all()
    assert attn["kv_b_proj"].shape == (8, 2, 16)
    held = config["experts_held"][1]
    mlp = p["layers_1"]["mlp"]
    assert mlp["experts_gate"].shape == (held, 64, 24)
    assert mlp["experts_down"].shape == (held, 24, 64)
    assert mlp["router_kernel"].shape == (
        64, config["published"]["n_routed_experts"])
    assert "router_kernel" not in p["layers_0"]["mlp"]
    assert abs(float(np.asarray(p["embed_tokens"]["embedding"]).std())
               - 0.02) < 2e-3


def test_routers_are_balanced_and_leave_the_shared_direction_out(tiny):
    """On the calibration sequence, through the reference alone: every
    router column and every head row is at right angles to the mean of the
    rows it reads, and each expert layer's loads are nearer equal with the
    selection bias than without."""
    import jax

    from benchmark.reference import dots3 as ref

    ss, config, _, _, params = tiny
    tree = params["params"]
    ids = np.random.default_rng(3000000019).integers(
        0, config["vocab_size"], ss.BALANCE_TOKENS).astype(np.int32)

    def shared(rows):
        mean = np.asarray(rows, np.float64).mean(axis=0)
        return mean / np.linalg.norm(mean)

    seen, x = 0, ref.embed(params, ids)
    for i, kind in enumerate(config["layer_types"]):
        layer = tree[f"layers_{i}"]
        x = x + ref.attention_out(x, layer, config, kind)
        h = ref.normed(x, layer["post_attention_layernorm"], config)
        if i >= config["first_k_dense_replace"]:
            mlp = layer["mlp"]
            kernel = np.asarray(mlp["router_kernel"], np.float64)
            assert np.abs(shared(h) @ kernel).max() < 1e-2 * np.linalg.norm(
                kernel, axis=0).mean()
            scores = ref.router_scores(h, mlp["router_kernel"])
            worst = []
            for bias in (mlp["router_bias"], 0.0):
                _, chosen = jax.lax.top_k(scores + bias,
                                          config["num_experts_per_tok"])
                load = np.bincount(np.asarray(chosen).reshape(-1),
                                   minlength=16)
                worst.append(load.max() / load.mean())
            assert worst[0] < worst[1] and worst[0] < 1.5
            seen += 1
        x = x + ref.mlp_out(h, layer, i, config,
                            held=config["experts_held"][0])
    assert seen == 4
    head = np.asarray(tree["lm_head"], np.float64)
    final = shared(ref.normed(x, tree["norm"], config))
    assert np.abs(head @ final).max() < 1e-2 * np.linalg.norm(
        head, axis=1).mean()


def test_reference_check_passes_the_system_as_built(tiny):
    res = _check(tiny)
    assert res["reference_ok"], res
    # decided by every logit the engine handed back, stacked: the last row
    # of each of four chunks (16 + 16 + 16 + a padded 11) and four steps
    assert res["reference_rel_err"] < 1e-5
    assert len(res["reference_rel_err_each"]) == 8
    assert res["reference_rel_err_first_token"] < 1e-5
    assert res["reference_rel_err_after_decode"] < 1e-5


def test_check_compares_each_chunks_last_row_and_each_greedy_token():
    from benchmark.runners.serve_sparse import check_positions

    real = _load("benchmark/traffic/longdoc-closed.json")
    assert check_positions(real) == [1023, 2047, 3071, 4095] + list(
        range(4096, 4104))
    tiny = _load("benchmark/traffic/tiny-longdoc-closed.json")
    assert check_positions(tiny) == [15, 31, 47, 63, 64, 65, 66, 67]
    cut = dict(tiny, check=dict(tiny["check"], prompt_len=59))
    assert check_positions(cut) == [15, 31, 47, 58, 59, 60, 61, 62]


@pytest.mark.parametrize("fault", [
    "every_row_read", "one_key_fewer", "no_gate", "no_rescale",
    "window_one_wider", "window_layers_full_theta", "one_expert_fewer"])
def test_reference_check_fails_a_fault(tiny, fault):
    config = tiny[1]
    changed = {
        "every_row_read": {"index_topk": 10 ** 6},
        "one_key_fewer": {"index_topk": config["index_topk"] - 1},
        "no_gate": {"attention_gate_type": None,
                    "swa_attention_gate_type": None},
        "no_rescale": {"apply_mla_qkv_lora_rescale": False},
        "window_one_wider": {
            "sliding_window_size": config["sliding_window_size"] + 1},
        "window_layers_full_theta": {"swa_rope_theta": config["rope_theta"]},
        "one_expert_fewer": {
            "num_experts_per_tok": config["num_experts_per_tok"] - 1},
    }
    res = _check(tiny, reference_config=dict(config, **changed[fault]))
    worst = res["reference_rel_err"]
    # a window of 5 turns a key by little whatever the theta: that fault
    # fails the comparison by less than the others' tenfold
    margin = 1.5 if fault == "window_layers_full_theta" else 10
    assert not res["reference_ok"] and worst > margin * res[
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
    assert checks["reference_rel_err"] < 1e-5
    assert checks["reference_rel_err_first_token"] < 1e-5
    assert checks["reference_rel_err_after_decode"] < 1e-5
    counted = notes["counters"]
    assert counted["index_rows"] > counted["attended_rows"] > 0
    assert counted["window_rows"] > 0 and counted["moe_steps"] > 0


def test_traced_rehearsal_reports_the_counters(traced):
    m = traced["metrics"]
    # no device lines on the CPU: the rooflines, the share of the peak and
    # the device times are left out, the counters are there
    assert set(m) == {"batch_occupancy.serve", "experts_touched.serve",
                      "expert_load_max_over_mean.serve", "pairs_here.serve",
                      "itl_p95_ms.serve_tok_s", "ttft_p90_ms.serve_tok_s",
                      "dsa_keys_read.serve"}
    # prompts of 24-72 tokens against a top-8: most keys are left unread
    assert 5 < m["dsa_keys_read.serve"]["value"] < 40
    # 4 of 16 experts held, top-2: a quarter of the choices land here
    assert 15 < m["pairs_here.serve"]["value"] < 35
    assert 0 < m["experts_touched.serve"]["value"] <= 100
    assert traced["correct"] is True


def test_lower_precision_tool_reads_far_above_the_tolerance():
    """The tolerance's second reading, on the CPU at toy size: float8
    weights read percents where float32 against float32 reads 1e-7, and
    bfloat16 inputs to the router and the selector flip nothing worth a
    1e-4."""
    proc = subprocess.run(
        [sys.executable, "benchmark/tools/lower_precision_sparse.py",
         "--manifest", MANIFEST, "--workload", CELL, "--seeds", "5",
         "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    for at in ("", "_first_token", "_after_decode"):
        assert line[f"e4m3_weights_rel_err{at}"] > 1e3 * line["tolerance"]
        assert line[f"bf16_router_input_rel_err{at}"] < 1e-3
        assert line[f"bf16_selector_inputs_rel_err{at}"] < 1e-1
