"""The Nemotron-H cell's benchmark files: the configuration, the byte count,
the new reducers on hand-made counters, the reference check's power to tell
a fault, and the runner end to end on the CPU rehearsal path."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import test_benchmark_manifest as rules
from benchmark.lib import hybrid_bytes
from benchmark.lib.trace import Trace
from benchmark.reducers import (
    ReduceContext,
    counter_ratio,
    hybrid_decode_roofline,
    moe_gmm_roofline,
    op_time,
)

ROOT = rules.ROOT
MANIFEST = "tests/benchmark/manifest_hybrid.json"
CELL = "tiny-nemotron-h.tiny-chat-closed-hybrid"
REAL = "benchmark/configs/nemotron3-super-ep4-l11.json"


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
def test_hybrid_manifest_keeps_the_manifest_rules(rule):
    getattr(rules, rule)(_load(MANIFEST))


def test_configuration_keeps_published_widths_and_states_its_cut():
    c = _load(REAL)
    assert (c["hidden_size"], c["mamba_num_heads"], c["mamba_head_dim"],
            c["n_groups"], c["ssm_state_size"], c["conv_kernel"],
            c["moe_latent_size"], c["moe_intermediate_size"],
            c["moe_shared_expert_intermediate_size"],
            c["num_experts_per_tok"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"]) == (
        4096, 128, 64, 8, 128, 4, 1024, 2688, 5376, 22, 32, 2, 128)
    assert c["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                            "n_routed_experts", "vocab_size",
                            "num_nextn_predict_layers"]
    published = c["published"]
    assert set(published) == set(c["reduced"])
    # one whole period of the pattern, in the model's 40 : 40 : 8
    assert published["hybrid_override_pattern"].startswith(
        c["hybrid_override_pattern"])
    assert c["hybrid_override_pattern"] == "MEMEMEM*EME"
    assert len(c["hybrid_override_pattern"]) == c["num_hidden_layers"] == 11
    full = published["hybrid_override_pattern"]
    assert [full.count(k) for k in "ME*"] == [40, 40, 8]
    assert (published["n_routed_experts"], c["n_routed_experts"],
            c["experts_held"]) == (512, 128, [0, 128])
    assert c["vocab_size"] * 4 == published["vocab_size"] == 131072
    entry = next(e for e in _load("BENCHMARK.json")["configs"]
                 if e["name"] == c["name"])
    assert entry["source"] == c["source"] and entry["file"] == REAL


def test_decode_step_bytes_are_the_issues_arithmetic():
    c = _load(REAL)
    need = hybrid_bytes.nemotron_h_decode_step
    other = need(c, lanes=0, kv_tokens=0, touched_share=0.0)
    assert 1.98e9 < other < 2.01e9          # every matrix outside the experts
    experts = need(c, lanes=0, kv_tokens=0, touched_share=1.0) - other
    assert experts == 5 * 128 * 2 * 1024 * 2688 * 2      # 7.05 GB
    state = need(c, lanes=64, kv_tokens=0, touched_share=0.0) - other
    assert state == 2 * 64 * 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    kv = need(c, lanes=0, kv_tokens=1000, touched_share=0.0) - other
    assert kv == 1000 * 1024                 # one layer, 2 KV heads of 128
    whole = need(c, lanes=64, kv_tokens=64 * 400, touched_share=0.94)
    assert 11.2e9 < whole < 11.5e9


COUNTERS = {"moe_steps": 10, "moe_tokens": 600, "moe_pairs": 3300,
            "moe_touched": 1200, "moe_max_load": 85}


def _rc(counters, trace=None):
    return ReduceContext(trace, counters, _load(REAL), {}, "TPU v5 lite")


@pytest.mark.parametrize("metric, want", [
    ("experts_touched.serve", 100.0 * 1200 / (10 * 128)),
    ("expert_load_max_over_mean.serve", 85 * 128 / 3300),
    ("pairs_here.serve", 100.0 * 3300 / (600 * 22))])
def test_expert_metrics_from_hand_made_counters(metric, want):
    spec = _load(f"benchmark/layer_metrics/{metric}.json")
    assert spec["reducer"] == "counter_ratio"
    assert counter_ratio.reduce(_rc(COUNTERS), **spec["args"]) == \
        pytest.approx(want)
    # a run that counted nothing (a dense model, the parent) reports nothing
    assert counter_ratio.reduce(_rc({"steps": 3}), **spec["args"]) is None
    assert counter_ratio.reduce(_rc(dict(COUNTERS, moe_steps=0, moe_pairs=0,
                                         moe_tokens=0)),
                                **spec["args"]) is None


def test_hybrid_roofline_from_a_hand_made_trace(monkeypatch):
    spec = _load("benchmark/layer_metrics/hybrid_decode_roofline.serve.json")
    args = spec["args"]
    assert hybrid_decode_roofline.reduce(_rc(COUNTERS), **args) is None
    ps = hybrid_decode_roofline.ps
    attrs = {"lanes": 64, "kv_tokens": 25600}
    pairs = [(("jit__decode", 0, ms * 1e6), ("engine.decode", 0, 1e5, attrs))
             for ms in (20.0, 25.0, 30.0)]
    monkeypatch.setattr(ps, "of", lambda rc: object())
    monkeypatch.setattr(ps, "paired", lambda pt, module, span: pairs)
    got = hybrid_decode_roofline.reduce(_rc(COUNTERS, trace=object()), **args)
    need = hybrid_bytes.nemotron_h_decode_step(
        _load(REAL), lanes=64, kv_tokens=25600,
        touched_share=1200 / (10 * 128))
    assert got == pytest.approx(100.0 * need / 25e-3 / 819e9)
    assert 50 < got < 60
    assert hybrid_decode_roofline.reduce(_rc({}, trace=object()),
                                         **args) is None


def test_gmm_time_and_roofline_from_a_hand_made_trace():
    """Ten gmm calls of 1 ms in each of three decode executions, one gmm in a
    prefill program that must not count."""
    ms = 1_000_000
    modules = [("jit__decode(1)", k * 100 * ms, 30 * ms) for k in range(5)]
    modules.append(("jit__prefill(2)", 40 * ms, 30 * ms))
    ops = [(f"%gmm.{j} = custom-call", k * 100 * ms + j * 2 * ms, ms)
           for k in range(5) for j in range(10)]
    ops.append(("%gmm.99 = custom-call", 41 * ms, 7 * ms))
    ops.append(("%fusion.1 = fusion", 1 * ms, ms))
    rc = _rc(COUNTERS, trace=Trace(modules, ops, []))
    time_spec = _load("benchmark/layer_metrics/moe_gmm_ms.serve.json")
    assert time_spec["reducer"] == "op_time"
    assert op_time.reduce(rc, **time_spec["args"]) == pytest.approx(10.0)
    spec = _load("benchmark/layer_metrics/moe_gmm_roofline.serve.json")
    share = moe_gmm_roofline.reduce(rc, **spec["args"])
    need = hybrid_bytes.held_expert_matrices(_load(REAL), 1200 / 1280)
    assert need == pytest.approx(0.9375 * 7.046e9, rel=1e-3)
    assert share == pytest.approx(100.0 * need / 819e9 / 10e-3)
    # no kernel of that name (lax.ragged_dot), no counters, no trace: nothing
    bare = Trace(modules, [("%fusion.1 = fusion", ms, ms)], [])
    assert moe_gmm_roofline.reduce(_rc(COUNTERS, trace=bare),
                                   **spec["args"]) is None
    assert moe_gmm_roofline.reduce(_rc({}, trace=rc.trace),
                                   **spec["args"]) is None
    assert moe_gmm_roofline.reduce(_rc(COUNTERS), **spec["args"]) is None


# ---- the reference check can tell a fault ---------------------------------

@pytest.fixture(scope="module")
def tiny():
    from benchmark.runners import serve_hybrid as sh

    config = _load("benchmark/configs/tiny-nemotron-h.json")
    traffic = _load("benchmark/traffic/tiny-chat-closed-hybrid.json")
    # 27 real rows in the 32 bucket: the padding has something to spoil
    traffic = dict(traffic, check=dict(traffic["check"], prompt_len=27))
    model = sh.build_model(config)
    params = sh.make_params(model, config, 3000000019)
    return sh, config, traffic, model, params


def _check(tiny, *, model=None, reference_config=None):
    from apex_tpu import serving as sv

    sh, config, traffic, good, params = tiny
    eng = sv.DecodeEngine(model or good, params, **traffic["engine"])
    res = sh.check_against_reference(eng, reference_config or config,
                                     traffic, 7)
    return res


def test_seeded_weights_follow_the_configuration(tiny):
    _, config, _, _, params = tiny
    p = params["params"]
    mixer = p["layers_0"]["mixer"]
    a = np.exp(np.asarray(mixer["A_log"]))
    assert (a >= 1).all() and (a <= 16).all()
    dt = np.log1p(np.exp(np.asarray(mixer["dt_bias"])))   # softplus
    assert (dt >= 0.99e-3).all() and (dt <= 0.1001).all()
    assert (np.asarray(mixer["D"]) == 1).all()
    assert (np.asarray(mixer["conv1d"]["bias"]) == 0).all()
    assert (np.asarray(p["layers_1"]["norm"]["scale"]) == 1).all()
    held = config["experts_held"][1]
    assert p["layers_1"]["mixer"]["experts_w1"].shape == (
        held, config["moe_latent_size"], config["moe_intermediate_size"])
    assert p["layers_1"]["mixer"]["router_kernel"].shape[1] == \
        config["published"]["n_routed_experts"]
    assert abs(float(np.asarray(p["lm_head"]).std()) - 0.02) < 2e-3


def test_selection_bias_balances_the_calibration_sequence(tiny):
    """On the sequence it was set from, read through the reference alone
    (the bias is made without the program), every expert layer's loads are
    nearer equal with the bias than without (and within 1.5 x the mean)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import nemotron_h as ref

    sh, config, _, _, params = tiny
    ids = np.random.default_rng(3000000019).integers(
        0, config["vocab_size"], sh.BALANCE_TOKENS).astype(np.int32)

    def worst_load(tree):
        out, x = {}, ref.embed(tree, ids)
        for i, kind in enumerate(config["hybrid_override_pattern"]):
            layer = tree["params"][f"layers_{i}"]
            h = ref.normed(x, layer, config)
            if kind == "E":
                mixer = layer["mixer"]
                _, chosen = jax.lax.top_k(
                    ref.router_scores(h, mixer["router_kernel"])
                    + mixer["router_bias"], config["num_experts_per_tok"])
                load = np.bincount(np.asarray(chosen).reshape(-1),
                                   minlength=16)
                out[f"layers_{i}"] = load.max() / load.mean()
            x = x + ref.layer_out(kind, h, layer["mixer"], config,
                                  held=config["experts_held"][0])
        return out

    zeroed = jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a)
        if "router_bias" in str(path[-1]) else a, params)
    with_bias, without = worst_load(params), worst_load(zeroed)
    assert set(with_bias) == {"layers_1", "layers_4"}
    for name in with_bias:
        assert with_bias[name] < without[name] and with_bias[name] < 1.5


def test_routers_and_head_leave_the_shared_direction_out(tiny):
    """What all calibration rows share reaches neither an expert's logit
    nor a token's: every router column and every head row is at right
    angles to the mean of the rows it reads (so lanes of a greedy decode
    do not all emit the same few tokens and route alike)."""
    from benchmark.reference import nemotron_h as ref

    sh, config, _, _, params = tiny
    tree = params["params"]
    ids = np.random.default_rng(3000000019).integers(
        0, config["vocab_size"], sh.BALANCE_TOKENS).astype(np.int32)

    def shared(rows):
        mean = np.asarray(rows, np.float64).mean(axis=0)
        return mean / np.linalg.norm(mean)

    seen, x = 0, ref.embed(params, ids)
    for i, kind in enumerate(config["hybrid_override_pattern"]):
        layer = tree[f"layers_{i}"]
        h = ref.normed(x, layer, config)
        if kind == "E":
            kernel = np.asarray(layer["mixer"]["router_kernel"], np.float64)
            assert np.abs(shared(h) @ kernel).max() < 1e-2 * np.linalg.norm(
                kernel, axis=0).mean()
            seen += 1
        x = x + ref.layer_out(kind, h, layer["mixer"], config,
                              held=config["experts_held"][0])
    assert seen == 2
    head = np.asarray(tree["lm_head"], np.float64)
    final = shared(ref.normed(x, {"norm": tree["norm_f"]}, config))
    assert np.abs(head @ final).max() < 1e-2 * np.linalg.norm(
        head, axis=1).mean()


def test_reference_check_passes_the_system_as_built(tiny):
    res = _check(tiny)
    assert res["reference_ok"], res
    assert res["reference_rel_err_first_token"] < 1e-5
    assert res["reference_rel_err_after_decode"] < 1e-5


class _NotToldTheLength:
    """The fault the engine's ``length`` argument exists to prevent: a model
    that takes a padded bucket for real rows."""

    def __init__(self, model):
        self.model, self.config = model, model.config
        self.cache_layers = model.cache_layers

    def apply(self, params, ids, *, length=None, **kw):
        if length is not None:
            length = ids.shape[1]
        return self.model.apply(params, ids, length=length, **kw)


@pytest.mark.parametrize("fault", ["dropped_layer", "one_expert_fewer",
                                   "no_routed_scaling", "padding_advances"])
def test_reference_check_fails_a_fault(tiny, fault):
    _, config, _, model, _ = tiny
    kw = {
        "dropped_layer": {"reference_config": dict(
            config, hybrid_override_pattern=config[
                "hybrid_override_pattern"][:-1])},
        "one_expert_fewer": {"reference_config": dict(
            config, num_experts_per_tok=config["num_experts_per_tok"] - 1)},
        "no_routed_scaling": {"reference_config": dict(
            config, routed_scaling_factor=1.0)},
        "padding_advances": {"model": _NotToldTheLength(model)},
    }[fault]
    res = _check(tiny, **kw)
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
    # as the 64-client cell: judged by its tokens a second; the gap's
    # 95th percentile is a per-layer record (traced below)
    assert set(last["metrics"]) == {"serve_tok_s", "setup_s"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 4 and last["rehearsal"] is True


def test_rehearsal_passes_every_check(untraced):
    checks = next(l for l in untraced if "notes" in l)["notes"]["checks"]
    assert all(checks[k] is True for k in (
        "decode_compiles_is_1", "prefill_compiles_within_buckets",
        "no_compile_in_window", "no_request_failed", "gaps_match_tokens",
        "reference_ok"))
    assert checks["reference_rel_err_first_token"] < 1e-5
    assert checks["reference_rel_err_after_decode"] < 1e-5


def test_traced_rehearsal_reports_the_expert_counters(traced):
    m = traced["metrics"]
    # no device lines on the CPU: the roofline and the device times are
    # left out, the counters are there
    assert set(m) == {"batch_occupancy.serve", "experts_touched.serve",
                      "expert_load_max_over_mean.serve", "pairs_here.serve",
                      "itl_p95_ms.serve_tok_s", "ttft_p90_ms.serve_tok_s"}
    assert m["itl_p95_ms.serve_tok_s"]["value"] > 0
    # 8 of 16 experts held, top-3: half of the choices land here
    assert 40 < m["pairs_here.serve"]["value"] < 60
    assert 0 < m["experts_touched.serve"]["value"] <= 100
    assert m["expert_load_max_over_mean.serve"]["value"] >= 1
    assert traced["correct"] is True


def test_lower_precision_tool_reads_far_above_the_tolerance():
    """The tolerance's second reading, on the CPU at toy size: float8
    weights read percents where float32 against float32 reads 1e-7, and a
    bfloat16 router input flips no choice worth a 1e-5."""
    proc = subprocess.run(
        [sys.executable, "benchmark/tools/lower_precision.py", "--manifest",
         MANIFEST, "--workload", CELL, "--seeds", "5", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    for at in ("first_token", "after_decode"):
        assert line[f"e4m3_weights_rel_err_{at}"] > 1e3 * line[
            "tolerance"]
        assert line[f"bf16_router_input_rel_err_{at}"] < 1e-3
