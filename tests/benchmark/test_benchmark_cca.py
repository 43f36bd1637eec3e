"""The ZAYA1 cell's benchmark files and the open-loop chat cell beside it:
the configuration against the published one, the traffic against the
cells' stated shapes, the open loop's arrivals and lateness, the byte counts
against worked arithmetic, the new readers on hand-made spans and counters
(and their silence where a program has neither), the weights' calibration,
the reference check's power to tell a fault, and both runners end to end on
the CPU rehearsal path."""

import json
import os
import statistics
import subprocess
import sys

import numpy as np
import pytest

import test_benchmark_manifest as rules
from benchmark.lib import cca_bytes, open_loop, serving
from benchmark.lib import traffic as tf
from benchmark.reducers import ReduceContext, cca_decode_roofline

ROOT = rules.ROOT
MANIFEST = "tests/benchmark/manifest_cca.json"
CELL = "tiny-zaya.tiny-reason-closed"
OPEN_CELL = "tiny-mistral.tiny-chat-open"
REAL = "benchmark/configs/zaya1-8b-l10.json"
REAL_CELL = "zaya1-8b-l10.reason-closed"
CHAT_OPEN = "mistral-7b-l16.chat-open"
CHAT_CELL = "mistral-7b-l16.chat-closed"
HYBRID_CELL = "nemotron3-super-ep4-l11.chat-closed-64"
OTHERS = ("benchmark/configs/nemotron3-super-ep4-l11.json",
          "benchmark/configs/dots3-note-ep8-l5.json",
          "benchmark/configs/mellum2-12b-l8.json",
          "benchmark/configs/mistral-7b-l16.json",
          "benchmark/configs/xing4-29b-l6.json")
# the catalog entry's config (Zyphra/ZAYA1-8B config.json), but the list of
# 40 "hybrid" layer types and the two rope blocks, checked beside it
PUBLISHED = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "lm_head_bias": False,
    "max_position_embeddings": 131072, "model_type": "zaya",
    "moe_intermediate_size": 2048, "num_attention_heads": 8,
    "num_experts": 16, "num_experts_per_tok": 1, "num_hidden_layers": 40,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
    "rms_norm_eps": 1e-05, "router_hidden_size": 256, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 262272}


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
def test_cca_manifest_keeps_the_manifest_rules(rule):
    getattr(rules, rule)(_load(MANIFEST))


def test_configuration_keeps_published_widths_and_states_its_cut(
        benchmark_manifest):
    c = _load(REAL)
    differs = sorted(k for k, v in PUBLISHED.items() if c[k] != v)
    assert differs == ["num_hidden_layers"] == c["reduced"]
    assert c["num_hidden_layers"] == 10
    assert c["published"] == {"num_hidden_layers": 40}
    assert c["layer_types"] == ["hybrid"] * 40
    assert c["rope_parameters"]["hybrid"] == {
        "partial_rotary_factor": 0.5, "rope_theta": 5000000,
        "rope_type": "default"}
    assert c["published_layers_kept"] == list(range(10))
    assert c["experts_held"] == [0, 16]          # every expert, no share
    for said in ("5.23 GB", "5.37 GB", "1,024 B", "pipeline of 4 one-chip "
                 "stages of 10", "over 16 GB"):
        assert said in c["reduced_why"], said
    assert "every expert and the whole vocabulary" in c["stands_for"]
    for convention in ("weights_dtype", "float32", "value_shift",
                       "convolutions", "qk_mean", "normalisation", "rope",
                       "router", "router_gamma", "mixture_of_depths",
                       "weights", "late_rows"):
        assert convention in c["assumed"], convention
    for chosen in ("value_shift", "convolutions", "qk_mean", "normalisation",
                   "router"):
        assert c["assumed"][chosen].startswith("(a)"), chosen
        assert "Zyphra" in c["assumed"][chosen], chosen
    assert "rows reach 8,192" in c["assumed"]["weights"]
    entry = next(e for e in benchmark_manifest["configs"]
                 if e["name"] == c["name"])
    assert entry["source"] == c["source"] and entry["file"] == REAL
    assert entry["reduced"] == c["reduced"]


def _entries(manifest, cell):
    return {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
            if cell in m.get("workloads", [cell])}


def test_the_cells_are_the_issues_traffic(benchmark_manifest):
    """By name: the two cells, their configurations and traffic, the
    metrics each reports and those it must not."""
    manifest = benchmark_manifest
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert (cells[REAL_CELL]["config"], cells[REAL_CELL]["traffic"],
            cells[REAL_CELL]["chips"]) == ("zaya1-8b-l10", "reason-closed", 1)
    assert (cells[CHAT_OPEN]["config"], cells[CHAT_OPEN]["traffic"],
            cells[CHAT_OPEN]["chips"]) == ("mistral-7b-l16", "chat-open", 1)
    mine = _entries(manifest, REAL_CELL)
    assert {"serve_tok_s", "setup_s", "decode_qk_mix_ms.serve",
            "chunk_qk_mix_ms.serve", "cca_decode_roofline.serve",
            "cca_gmm_roofline.serve", "experts_touched.serve",
            "expert_load_max_over_mean.serve", "pairs_here.serve",
            "moe_gmm_ms.serve", "batch_occupancy.serve",
            "scope_coverage.serve", "device_idle.serve",
            "decode_experts_ms.serve", "chunk_experts_ms.serve",
            "decode_cache_ms.serve", "chunk_cache_ms.serve"} <= mine
    # readers that cannot count this family: the generic expert roofline
    # counts layer_types' published 40 layers
    assert not mine & {"expert_gmm_roofline.serve", "decode_roofline.serve",
                       "hybrid_decode_roofline.serve",
                       "decode_state_ms.serve"}
    # the open cell reports what the closed chat cell reports, and its wait
    assert _entries(manifest, CHAT_OPEN) == _entries(
        manifest, CHAT_CELL) | {"queue_wait_ms.serve"}
    for name in ("decode_qk_mix_ms.serve", "chunk_qk_mix_ms.serve",
                 "cca_decode_roofline.serve", "cca_gmm_roofline.serve",
                 "queue_wait_ms.serve"):
        m = next(x for x in manifest["per_layer"] if x["name"] == name)
        assert m["moves"] == "serve_tok_s"
        assert m["workloads"] == ([CHAT_OPEN] if name.startswith("queue")
                                  else [REAL_CELL])
    reason = _load("benchmark/traffic/reason-closed.json")
    assert (reason["runner"], reason["loop"], reason["clients"],
            reason["strata"], reason["drain"]) == (
        "serve_cca", "closed", 64, 16, "cut")
    assert reason["engine"] == {"slots": 64, "max_len": 8192,
                                "prefill_len": 512}
    assert reason["prompt_len"] == {"dist": "lognormal", "median": 512,
                                    "sigma": 0.8, "min": 128, "max": 2048}
    assert reason["output_len"] == {"dist": "lognormal", "median": 3072,
                                    "sigma": 0.5, "min": 1024, "max": 6144}
    outputs = tf.stratum_lengths(reason["output_len"], 16)
    assert statistics.mean(outputs) == pytest.approx(3340, abs=5)
    assert max(tf.stratum_lengths(reason["prompt_len"], 16)) + max(
        outputs) <= 8192
    assert (reason["check"]["prompt_len"], reason["check"]["decode_tokens"]
            ) == (1024, 32)
    chat_open, chat = (_load(f"benchmark/traffic/{t}.json")
                       for t in ("chat-open", "chat-closed"))
    assert (chat_open["runner"], chat_open["loop"]) == ("serve_open", "open")
    for key in ("engine", "prompt_len", "output_len", "strata", "check"):
        assert chat_open[key] == chat[key] or key == "check" and {
            k: v for k, v in chat_open[key].items() if k != "tolerance_why"
        } == {k: v for k, v in chat[key].items() if k != "tolerance_why"}
    mean_out = statistics.mean(tf.stratum_lengths(chat["output_len"], 16))
    assert mean_out == pytest.approx(113.6, abs=0.05)
    # 0.8 of what chat-closed completes (1,005.5 tokens/s, ledger)
    assert chat_open["rate_per_s"] == pytest.approx(
        0.8 * 1005.5 / mean_out, abs=0.005)


# ---- the open loop ------------------------------------------------------

def test_stratified_arrivals_in_a_window_barely_depend_on_the_seed():
    """Over five seeds, the arrivals of ``chat-open`` that fall in a 50 s
    window after its ramp number the same to within one block of 16."""
    traffic = _load("benchmark/traffic/chat-open.json")
    counts = []
    for seed in (1, 7, 3000000019, 3000004201, 2 ** 31 + 5):
        t, n, gaps = 0.0, 0, open_loop.arrival_gaps(traffic, seed)
        while t <= traffic["ramp_s"] + 50.0:
            n += t > traffic["ramp_s"]
            t += next(gaps)
        counts.append(n)
    assert max(counts) - min(counts) <= traffic["strata"]
    assert statistics.mean(counts) == pytest.approx(
        50 * traffic["rate_per_s"], rel=0.05)
    # every block of 16 gaps spans the same time: 16 at the offered rate
    block = open_loop.stratum_gaps(traffic["rate_per_s"], 16)
    assert sum(block) == pytest.approx(16 / traffic["rate_per_s"])
    gaps = open_loop.arrival_gaps(traffic, 11)
    for _ in range(3):
        assert sorted(next(gaps) for _ in range(16)) == block


def test_the_open_loop_keeps_its_schedule_and_reports_lateness():
    """On a virtual clock where every step takes 20 ms, arrivals due while
    a step runs are submitted after it and the lateness is recorded; where
    one step stalls for 0.5 s, the arrivals that find the queue full wait
    for room and none is refused."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import serving as sv
    from apex_tpu.models import LlamaConfig, LlamaForCausalLM

    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=1, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    engine = sv.DecodeEngine(model, params, slots=4, max_len=64,
                             prefill_len=16)
    clk = sv.VirtualClock()
    sched = sv.ContinuousBatchingScheduler(engine, clock=clk, max_queue=4)
    stalls = [0.5]

    class Timed:
        def __getattr__(self, name):
            return getattr(sched, name)

        def step(self):
            # one step stalls the host as a profiler's start does: twice
            # the queue's room falls due meanwhile
            clk.advance(0.02 + (stalls.pop() if stalls
                                and clk() > 1.0 else 0.0))
            return sched.step()

    traffic = {"strata": 4, "rate_per_s": 20.0,
               "prompt_len": {"dist": "fixed", "value": 6},
               "output_len": {"dist": "fixed", "value": 3}}
    opened = []
    rec, lateness = open_loop.run_open_loop(
        Timed(), tf.request_stream(traffic, 128, 5),
        open_loop.arrival_gaps(traffic, 5), clock=clk, ramp_s=0.5,
        window_s=2.0,
        make_request=lambda rid, s: sv.Request(rid, s.prompt,
                                               s.max_new_tokens),
        on_open=lambda: opened.append(clk()), sleep=clk.advance)
    sched.close()
    assert opened and rec.t_close - rec.t_open == pytest.approx(2.0, abs=0.05)
    in_window = [late for due, late in lateness.values()
                 if rec.in_window(due)]
    assert len(in_window) == pytest.approx(2.0 * 20, abs=4)
    # late by less than a step, but for the arrivals the stall held back:
    # they wait at their clients for room in the queue, none is refused
    assert any(late > 0 for _, late in lateness.values())
    held = [late for _, late in lateness.values() if late >= 0.02 + 1e-9]
    assert 4 <= len(held) and max(held) <= 0.5 + 0.2
    assert not stalls
    assert all(r.result.finish_reason in sv.SERVED_REASONS
               for r in rec.served)
    assert len(rec.served) == len(lateness)


# ---- the reason cell's ramp ---------------------------------------------

def test_in_progress_starts_the_lanes_where_a_steady_loop_has_them():
    """``reason-closed``'s first 64 requests: each keeps its prompt, with
    the tokens it has made after it, and the rest of an output length
    drawn by length from the first 64's own; the shares done are the 64
    quantiles; then the plain stream goes on, and a seed gives one set."""
    traffic = _load("benchmark/traffic/reason-closed.json")
    assert traffic["ramp"] == "in_progress"
    clients, vocab, seed = traffic["clients"], 1000, 3000004201

    def first(n, stream):
        return [next(stream) for _ in range(n)]

    plain = first(clients + 1, tf.request_stream(traffic, vocab, seed))
    warm = first(clients + 1, serving.in_progress(
        tf.request_stream(traffic, vocab, seed), clients, vocab, seed))
    assert warm[-1] == plain[-1]
    warm, plain = warm[:clients], plain[:clients]
    assert all(isinstance(w, serving.InProgress) for w in warm)
    totals = [w.done + w.max_new_tokens for w in warm]
    lengths = [p.max_new_tokens for p in plain]
    assert set(totals) <= set(lengths)
    # weighted by length: the mean in flight is E[o^2] / E[o]
    assert np.mean(totals) == pytest.approx(
        np.mean(np.square(lengths)) / np.mean(lengths), rel=0.03)
    assert np.mean(totals) > 1.1 * np.mean(lengths)
    shares = sorted(w.done / t for w, t in zip(warm, totals))
    assert shares == pytest.approx(
        (np.arange(clients) + 0.5) / clients, abs=1e-3)
    for w, p in zip(warm, plain):
        assert w.index == p.index and w.max_new_tokens >= 1
        assert w.prompt[:len(p.prompt)] == p.prompt
        assert len(w.prompt) == len(p.prompt) + w.done
        assert len(w.prompt) + w.max_new_tokens <= traffic["engine"][
            "max_len"]
    again = first(clients, serving.in_progress(
        tf.request_stream(traffic, vocab, seed), clients, vocab, seed))
    assert again == warm


def test_the_window_opens_once_every_lane_has_a_token():
    """On a virtual clock, four lanes started in progress: the window opens
    once each has a token, long before the longest first request ends; the
    lanes end at staggered steps, and every request is served whole."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import serving as sv
    from apex_tpu.models import LlamaConfig, LlamaForCausalLM

    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=1, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    engine = sv.DecodeEngine(model, params, slots=4, max_len=128,
                             prefill_len=16)
    clk = sv.VirtualClock()
    sched = sv.ContinuousBatchingScheduler(engine, clock=clk)

    class Timed:
        def __getattr__(self, name):
            return getattr(sched, name)

        def step(self):
            clk.advance(0.01)
            return sched.step()

    traffic = {"strata": 4,
               "prompt_len": {"dist": "fixed", "value": 6},
               "output_len": {"dist": "uniform", "min": 16, "max": 64}}
    stream = serving.in_progress(tf.request_stream(traffic, 128, 7), 4, 128,
                                 7)
    opened = []
    rec = serving.run_started_closed_loop(
        Timed(), stream, clients=4, clock=clk, window_s=0.3,
        make_request=lambda rid, s: sv.Request(rid, s.prompt,
                                               s.max_new_tokens),
        on_open=lambda: opened.append(clk()))
    sched.close()
    warm = [r for r in rec.served if isinstance(r.spec, serving.InProgress)]
    assert len(opened) == 1 and len(warm) == 4
    assert all(r.t_submit + r.result.ttft_s <= rec.t_open for r in warm)
    assert rec.t_open < max(r.t_done for r in warm) - 0.1
    assert len({r.t_done for r in warm}) == 4
    assert rec.t_close - rec.t_open == pytest.approx(0.3, abs=0.011)
    assert any(not isinstance(r.spec, serving.InProgress)
               for r in rec.served)
    assert all(r.result.finish_reason in sv.SERVED_REASONS
               and len(r.result.tokens) == r.spec.max_new_tokens
               for r in rec.served)
    assert sum(n for _, n, _ in rec.steps) == sum(
        len(r.result.tokens) for r in rec.served)


def test_first_chunks_stamps_a_requests_first_chunk_only():
    now = [10.0]
    sink = open_loop.FirstChunks(lambda: now[0])
    sink({"event": "serving_prefill_chunk", "rid": "a", "offset_tokens": 0,
          "duration_s": 0.5})
    now[0] = 11.0
    sink({"event": "serving_prefill_chunk", "rid": "a", "offset_tokens": 512,
          "duration_s": 0.1})
    sink({"event": "serving_request_admitted", "rid": "b"})
    assert sink.at == {"a": 9.5}


# ---- the byte counts and the readers ---------------------------------------

def test_decode_step_bytes_are_the_issues_arithmetic():
    c = _load(REAL)
    assert cca_bytes.reads(c)
    assert not any(cca_bytes.reads(_load(o)) for o in OTHERS)
    assert cca_bytes.tail_width(c) == 2688
    assert cca_bytes.row_bytes(c) == 1024
    # a layer: CCA 5.24 M + convolutions 0.33 M in bf16, the router 0.66 M
    # float32, experts 201.3 M
    assert cca_bytes.attention_elements(c) == pytest.approx(5.57e6, rel=2e-3)
    assert cca_bytes.router_floats(c) == pytest.approx(0.66e6, rel=1e-2)
    assert 16 * cca_bytes.expert_matrices(c) == pytest.approx(201.3e6,
                                                              rel=1e-3)
    # 64 lanes at ~2,570 live rows, every expert touched: ~6.9 GB a step
    step = cca_bytes.zaya_decode_step(c, lanes=64, kv_tokens=64 * 2570,
                                      touched_share=1.0)
    assert 6.7e9 < step < 7.1e9
    # the head (the tied table) and the K/V rows by the issue's numbers
    assert cca_bytes.zaya_decode_step(
        c, lanes=0, kv_tokens=0, touched_share=0.0) == pytest.approx(
        1.07e9 + 10 * (2 * 5.57e6 + 4 * 0.66e6), rel=1e-2)
    assert cca_bytes.zaya_decode_step(
        c, lanes=0, kv_tokens=64 * 2570, touched_share=0.0) - \
        cca_bytes.zaya_decode_step(c, lanes=0, kv_tokens=0,
                                   touched_share=0.0) == 64 * 2570 * 10 * 1024


COUNTERS = {"moe_steps": 100, "moe_tokens": 6000, "moe_pairs": 6000,
            "moe_touched": 1560, "moe_max_load": 900,
            "n_routed_experts": 16}


def _rc(counters, trace=None, config=REAL):
    return ReduceContext(trace, counters, _load(config),
                         _load("benchmark/traffic/reason-closed.json"),
                         "TPU v5 lite")


def test_decode_roofline_from_hand_made_spans(monkeypatch):
    spec = _load("benchmark/layer_metrics/cca_decode_roofline.serve.json")
    args = spec["args"]
    reduce = cca_decode_roofline.reduce
    assert reduce(_rc(COUNTERS), **args) is None            # no trace
    ps = cca_decode_roofline.ps
    attrs = {"lanes": 64, "kv_tokens": 64 * 2570}
    pairs = [(("jit__decode", 0, ms * 1e6), ("engine.decode", 0, 1e5, attrs))
             for ms in (10.0, 11.0, 12.0)]
    monkeypatch.setattr(ps, "of", lambda rc: object())
    monkeypatch.setattr(ps, "paired", lambda pt, module, span: pairs)
    got = reduce(_rc(COUNTERS, trace=object()), **args)
    need = cca_bytes.zaya_decode_step(_load(REAL), lanes=64,
                                      kv_tokens=64 * 2570,
                                      touched_share=1560 / (100 * 16))
    assert got == pytest.approx(100.0 * need / 11e-3 / 819e9)
    assert 60 < got < 100
    # spans without the counts, a run without the expert counters, another
    # family's configuration: nothing
    bare = [(m, (n, s, d, {"lanes": 64})) for m, (n, s, d, _) in pairs]
    monkeypatch.setattr(ps, "paired", lambda pt, module, span: bare)
    assert reduce(_rc(COUNTERS, trace=object()), **args) is None
    monkeypatch.setattr(ps, "paired", lambda pt, module, span: pairs)
    assert reduce(_rc({}, trace=object()), **args) is None
    for other in OTHERS:
        assert reduce(_rc(COUNTERS, trace=object(), config=other),
                      **args) is None


def test_expert_roofline_counts_the_layers_held():
    """``cca_gmm_roofline.serve`` is the generic expert reader on this
    family's byte function: the 10 layers the chip holds, where
    ``layer_types`` lists 40."""
    spec = _load("benchmark/layer_metrics/cca_gmm_roofline.serve.json")
    assert spec["reducer"] == "expert_gmm_roofline"
    assert spec["args"]["bytes_module"] == "cca_bytes"
    c = _load(REAL)
    assert cca_bytes.held_expert_matrices(c, 1.0) == pytest.approx(
        10 * 201.3e6 * 2, rel=1e-3)
    assert cca_bytes.touched_share(COUNTERS, c) == pytest.approx(0.975)


def test_qk_mix_and_queue_wait_metrics_read_their_sources():
    for name, module in (("decode_qk_mix_ms.serve", "^jit__decode"),
                         ("chunk_qk_mix_ms.serve", "^jit__prefill")):
        spec = _load(f"benchmark/layer_metrics/{name}.json")
        assert spec["reducer"] == "scope_time"
        assert spec["args"]["scopes"] == "^qk_mix$"
        assert spec["args"]["module"] == module
    assert _load("benchmark/layer_metrics/chunk_qk_mix_ms.serve.json")[
        "args"]["pick"] == "largest"
    wait = _load("benchmark/layer_metrics/queue_wait_ms.serve.json")
    assert wait == dict(wait, reducer="counter",
                        args={"key": "queue_wait_p90_ms"})


# ---- the weights and the reference check ----------------------------------

@pytest.fixture(scope="module")
def tiny():
    from benchmark.runners import serve_cca as sc

    config = _load("benchmark/configs/tiny-zaya.json")
    traffic = _load("benchmark/traffic/tiny-reason-closed.json")
    # 43 real rows: the last chunk is a padded 11 of 16
    traffic = dict(traffic, check=dict(traffic["check"], prompt_len=43))
    model = sc.build_model(config)
    params = sc.make_params(model, config, 3000000019)
    return sc, config, traffic, model, params


def _check(tiny, *, reference_config=None):
    from apex_tpu import serving as sv

    sc, config, traffic, model, params = tiny
    eng = sv.DecodeEngine(model, params, **traffic["engine"])
    return sc.check_against_reference(eng, reference_config or config,
                                      traffic, 7)


def test_seeded_weights_follow_the_configuration(tiny):
    sc, config, _, model, _ = tiny
    p = sc.draw_params(model, config, 3)["params"]
    attn, router = p["layers_1"]["self_attn"], p["layers_1"]["router"]
    d = config["head_dim"]
    assert np.allclose(np.asarray(attn["temperature"]), d ** 0.5)
    assert float(router["gamma"]) == config["assumed"]["router_gamma"]
    assert router["down"].dtype == np.float32
    # the router keeps the spread of what it reads: fan_in^-1/2
    assert float(np.asarray(router["mlp_0"]).std()) == pytest.approx(
        config["router_hidden_size"] ** -0.5, rel=0.15)
    assert float(np.asarray(attn["conv_time1"]["kernel"]).std()) == \
        pytest.approx((2 * d) ** -0.5, rel=0.15)
    assert not np.asarray(router["balance_bias"]).any()
    assert "lm_head" not in p and "router_kernel" not in p["layers_0"]["mlp"]


def test_routers_and_head_are_calibrated_where_the_cell_serves(tiny):
    """Against the weights as drawn only the routers' down-projections,
    their balancing biases and the tied table move."""
    import jax

    sc, config, _, model, params = tiny
    raw = sc.draw_params(model, config, 3000000019)
    moved = {jax.tree_util.keystr(path) for path, ok
             in jax.tree_util.tree_leaves_with_path(jax.tree.map(
                 lambda a, b: bool((np.asarray(a) == np.asarray(b)).all()),
                 params, raw)) if not ok}
    layers = config["num_hidden_layers"]
    assert moved == {"['params']['embed_tokens']['embedding']"} | {
        f"['params']['layers_{i}']['router']['{k}']"
        for i in range(layers) for k in ("down", "balance_bias")}


def test_the_calibration_reads_no_row_of_the_system(tiny, monkeypatch):
    """Both passes of the calibration walk the plain reference: a system
    whose forward raises calibrates to the same tree."""
    import jax

    from apex_tpu.models import zaya

    sc, config, _, model, params = tiny
    drawn = sc.draw_params(model, config, 3000000019)

    def refused(*args, **kwargs):
        raise AssertionError("the calibration ran the system")

    monkeypatch.setattr(zaya.ZayaForCausalLM, "__call__", refused)
    again = sc.route_as_trained(drawn, config, 3000000019)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool((np.asarray(a) == np.asarray(b)).all()),
        again, params)))


def test_reference_attention_is_the_same_in_any_query_block(tiny,
                                                            monkeypatch):
    """The reference scores a block of query rows at a time: blocks that
    cut the sequence anywhere give the whole sequence's logits."""
    from benchmark.reference import zaya as ref

    _, config, _, _, params = tiny
    ids = np.random.default_rng(1).integers(0, config["vocab_size"], 40)
    whole = np.asarray(ref.logits_at(params, ids, list(range(40)), config))
    monkeypatch.setattr(ref, "QUERY_BLOCK", 7)
    # a new function: jax would reuse the trace of ``attention`` itself
    monkeypatch.setattr(ref, "_attention", ref.jax.jit(
        lambda a, p, config: ref.attention(a, p, config),
        static_argnames="config"))
    cut = np.asarray(ref.logits_at(params, ids, list(range(40)), config))
    assert np.abs(cut - whole).max() <= 1e-5 * np.abs(whole).max()


def test_reference_check_passes_the_system_as_built(tiny):
    res = _check(tiny)
    assert res["reference_ok"], res
    # the last row of each of three chunks (16 + 16 + a padded 11) and six
    # steps
    assert len(res["reference_rel_err_each"]) == 9
    assert res["reference_positions_decided"] == 9
    assert res["reference_rel_err"] < 1e-5


@pytest.mark.parametrize("fault", [
    "every_channel_rotated", "another_rope_theta", "no_depth_carry"])
def test_reference_check_fails_a_fault(tiny, fault):
    config = tiny[1]
    changed = {
        "every_channel_rotated": {"partial_rotary_factor": 1.0},
        "another_rope_theta": {"rope_parameters": dict(
            config["rope_parameters"], hybrid=dict(
                config["rope_parameters"]["hybrid"], rope_theta=10000))},
        "no_depth_carry": None,
    }[fault]
    if changed is None:
        from benchmark.reference import zaya as ref

        real = ref.router

        def router(h, p, carry):
            return real(h, p, None)

        ref.router, restore = router, real
        try:
            res = _check(tiny)
        finally:
            ref.router = restore
    else:
        res = _check(tiny, reference_config=dict(config, **changed))
    assert not res["reference_ok"] and res["reference_rel_err"] > 10 * res[
        "reference_tolerance"], (fault, res)


# ---- the runners end to end, as the benchmark command runs them ----------

def _run(cell, *extra):
    command = _load(MANIFEST)["command"]
    return subprocess.run(
        [sys.executable, *command[1:], "--manifest", MANIFEST, "--workload",
         cell, "--seed", "3000000019", "--seconds", "1", "--rehearse",
         *extra],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, BENCH_RUN="ignored"))


def _lines(proc):
    assert proc.returncode == 0, proc.stderr[-2000:]
    return [json.loads(l) for l in proc.stdout.strip().splitlines()
            if l.startswith("{")]


@pytest.fixture(scope="module", params=[CELL, OPEN_CELL])
def untraced(request):
    return request.param, _lines(_run(request.param, "--trace", "0"))


@pytest.fixture(scope="module", params=[CELL, OPEN_CELL])
def traced(request):
    return request.param, _lines(_run(request.param, "--trace", "1"))[-1]


def test_rehearsal_reports_the_end_to_end_metrics(untraced):
    _, lines = untraced
    last = lines[-1]
    assert set(last["metrics"]) == {"serve_tok_s", "setup_s"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 4 and last["rehearsal"] is True


def test_rehearsal_passes_every_check(untraced):
    cell, lines = untraced
    notes = next(l for l in lines if "notes" in l)["notes"]
    checks = notes["checks"]
    assert all(checks[k] is True for k in (
        "decode_compiles_is_1", "prefill_compiles_within_buckets",
        "no_compile_in_window", "no_request_failed", "gaps_match_tokens",
        "reference_ok"))
    assert checks["reference_rel_err_first_token"] < 1e-5
    if cell == CELL:
        counted = notes["counters"]
        assert counted["moe_pairs"] == counted["moe_tokens"] > 0
        # the window's close cuts what is in flight
        assert notes["cut_at_close"] > 0
        # the lanes start in progress: the first tokens timed are those of
        # the requests that arrived in the window
        assert notes["ttft_ms"]["requests"] > 0
        assert notes["ttft_ms"]["p90"] == counted["ttft_p90_ms"]
    else:
        # every open-loop run reports the generator's lateness
        late = notes["lateness_ms"]
        assert 0 <= late["p50"] <= late["p99"] <= late["max"]
        assert notes["arrivals_in_window"] > 10
        assert notes["offered_tok_s"] > 0
        # timed from the arrival: the first token comes after the first
        # chunk is taken up, and a request waits at least its lateness
        counted = notes["counters"]
        assert (counted["ttft_from_arrival_p90_ms"]
                >= counted["queue_wait_p90_ms"] >= 0)


def test_traced_rehearsal_reports_the_counters(traced):
    cell, line = traced
    m = line["metrics"]
    assert line["correct"] is True
    if cell == CELL:
        # no device lines on the CPU: the rooflines and the device times
        # are left out, the counters are there
        assert set(m) == {"batch_occupancy.serve", "experts_touched.serve",
                          "expert_load_max_over_mean.serve",
                          "pairs_here.serve", "itl_p95_ms.serve_tok_s",
                          "ttft_p90_ms.serve_tok_s"}
        assert m["pairs_here.serve"]["value"] == pytest.approx(100.0)
    else:
        assert set(m) == {"batch_occupancy.serve", "ttft_p90_ms.serve",
                          "itl_p95_ms.serve_tok_s", "queue_wait_ms.serve"}
        assert m["queue_wait_ms.serve"]["value"] >= 0


def test_lower_precision_tool_reads_far_above_the_tolerance():
    """The tolerance's two readings, on the CPU at toy size: the system's
    own check reads 1e-7 and float8 weights read percents, which the
    runner's own verdict refuses."""
    proc = subprocess.run(
        [sys.executable, "benchmark/tools/lower_precision_cca.py",
         "--manifest", MANIFEST, "--workload", CELL, "--seeds", "5",
         "--e4m3", "5", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["reference_ok"] is True and line["e4m3_weights_ok"] is False
    for at in ("", "_stacked", "_first_token", "_after_decode"):
        assert line[f"reference_rel_err{at}"] < 1e-5
        assert line[f"e4m3_weights_rel_err{at}"] > 1e3 * line[
            "reference_tolerance"]
