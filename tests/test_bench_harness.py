"""bench.py's contract: one config, one attempt, no chip -> non-zero.

The harness used to retry on error markers, fall back to a smaller model
and run a CPU smoke under the chip's metric names when it found no TPU.
These tests pin what replaced that: exactly one ``run_config`` call per
run, an ``ok: false`` line and exit 1 when it raises, exit non-zero after
the result line when a diagnostic block failed, a refusal to run without a
TPU unless the CPU was asked for by name, and an error — never a default —
for a device whose peak is not on record.
"""

import io
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import bench  # noqa: E402


def _fake_platform(monkeypatch, platform):
    monkeypatch.setattr(
        bench.jax, "devices",
        lambda *a: [type("D", (), {"platform": platform})()])


def _run_main(monkeypatch, *args, **kw):
    """Run ``bench.main``; returns ``(parsed stdout lines, exit code)``
    (exit code ``None`` when main returned normally)."""
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    code = None
    try:
        bench.main(*args, **kw)
    except SystemExit as e:
        code = e.code
    finally:
        monkeypatch.setattr(sys, "stdout", sys.__stdout__)
    lines = [json.loads(ln) for ln in out.getvalue().splitlines()
             if ln.strip()]
    return lines, code


def test_one_config_one_attempt(monkeypatch):
    calls = []

    def ok(name, **kw):
        calls.append(name)
        return {"metric": f"m_{name}", "value": 1.0,
                "recovery": {"ok": True}}

    monkeypatch.setattr(bench, "run_config", ok)
    _fake_platform(monkeypatch, "tpu")
    lines, code = _run_main(monkeypatch, None, None, None)
    assert calls == ["large"]           # the default card, nothing after it
    assert code is None
    assert [ln["metric"] for ln in lines] == ["m_large"]


def test_failure_is_not_retried_and_never_falls_back(monkeypatch):
    """An error that the old harness classified transient (and retried,
    then answered with a smaller model) now costs one attempt: an
    ``ok: false`` line, exit 1."""
    calls = []

    def broken(name, **kw):
        calls.append(name)
        raise RuntimeError("INTERNAL: stream broken")

    monkeypatch.setattr(bench, "run_config", broken)
    _fake_platform(monkeypatch, "tpu")
    lines, code = _run_main(monkeypatch, None, None, None)
    assert calls == ["large"]
    assert code == 1
    assert len(lines) == 1 and lines[0]["ok"] is False
    assert "stream broken" in lines[0]["error"]
    assert lines[0]["platform"] == "tpu"


def test_no_chip_exits_nonzero_without_running(monkeypatch):
    calls = []
    monkeypatch.setattr(bench, "run_config",
                        lambda name, **kw: calls.append(name))
    _fake_platform(monkeypatch, "cpu")
    lines, code = _run_main(monkeypatch, None, None, None)
    assert calls == [] and lines == []
    assert code not in (None, 0) and "no TPU backend" in str(code)
    # an explicit --model does not make the CPU acceptable either
    lines, code = _run_main(monkeypatch, "llama-1b", None, None)
    assert calls == [] and code not in (None, 0)


def test_explicit_cpu_handle_runs_the_cpu_card(monkeypatch):
    calls = []

    def ok(name, **kw):
        calls.append(name)
        return {"metric": f"m_{name}", "value": 1.0}

    monkeypatch.setattr(bench, "run_config", ok)
    _fake_platform(monkeypatch, "cpu")
    lines, code = _run_main(monkeypatch, None, None, None, allow_cpu=True)
    assert calls == ["cpu-smoke"] and code is None
    assert lines[0]["metric"] == "m_cpu-smoke"


def test_failed_diagnostic_block_exits_nonzero_after_the_line(monkeypatch):
    def result(name, **kw):
        return {"metric": f"m_{name}", "value": 1.0,
                "recovery": {"ok": True},
                "serving": {"ok": False, "error": "boom"},
                # could not run on this device count: not a failure
                "serving_tp": {"ok": False, "skipped": "needs 2 devices"}}

    monkeypatch.setattr(bench, "run_config", result)
    _fake_platform(monkeypatch, "tpu")
    lines, code = _run_main(monkeypatch, "large", None, None)
    assert lines[0]["metric"] == "m_large"      # the line is printed first
    assert code not in (None, 0) and "serving" in str(code)
    assert "serving_tp" not in str(code).replace("'serving'", "")


def test_unknown_device_kind_raises():
    known = type("D", (), {"device_kind": "TPU v5 lite"})()
    assert bench._peak_tflops(known) == 197.0
    unknown = type("D", (), {"device_kind": "TPU v99 mega"})()
    with pytest.raises(ValueError, match="no peak TFLOP/s on record"):
        bench._peak_tflops(unknown)


def test_tp_dryrun_refuses_fewer_devices_than_asked():
    """Asking for more devices than the backend shows is an error — the
    old path re-executed itself on forced CPU devices and reported a
    pass."""
    with pytest.raises(RuntimeError, match="needs 16 devices"):
        bench.tp_dryrun(16)


def test_recovery_metrics_block():
    """The resilience-overhead block (ISSUE 1 satellite): checkpoint
    save/validate/restore timings + bytes, with leaf sampling under a
    byte budget so TPU-size states can't blow the bench deadline."""
    import jax.numpy as jnp

    tree = {"a": jnp.ones((64, 64), jnp.float32),
            "b": jnp.ones((128,), jnp.bfloat16)}
    r = bench._recovery_metrics(tree)
    assert r["bytes"] == 64 * 64 * 4 + 128 * 2
    assert r["sampled"] is False
    assert r["n_leaves"] == 2
    for k in ("save_ms", "validate_ms", "restore_ms"):
        assert r[k] >= 0.0
    # budget smaller than the tree: sampling kicks in but never to zero
    r2 = bench._recovery_metrics(tree, byte_budget=16)
    assert r2["sampled"] is True and r2["n_leaves"] == 1
    # a FIRST leaf bigger than the whole budget is sliced, not taken
    # whole — the budget is a hard cap (code-review finding)
    r3 = bench._recovery_metrics({"big": jnp.ones((64, 64), jnp.float32)},
                                 byte_budget=256)
    assert r3["sampled"] is True
    assert r3["bytes"] <= 256


def test_ckpt_async_metrics_block():
    """The async-checkpoint block (ISSUE 8): step-loop blocking ms per
    save for sync vs async, snapshot ms, background write ms, bytes —
    and the byte-identical on-disk guarantee.  The ≥5x blocking
    reduction is measured at the default (64 MB) size; at this toy size
    only sanity is asserted."""
    import jax.numpy as jnp

    tree = {"a": jnp.ones((128, 128), jnp.float32),
            "b": jnp.ones((64,), jnp.bfloat16)}
    r = bench._ckpt_async_metrics(tree, n_saves=2)
    assert r["ok"] is True
    assert r["bytes"] == 128 * 128 * 4 + 64 * 2
    assert r["sampled"] is False
    assert r["n_saves"] == 2
    for k in ("blocking_ms_per_save_sync", "blocking_ms_per_save_async",
              "snapshot_ms", "write_ms_background",
              "blocking_reduction_x"):
        assert r[k] > 0.0, k
    # async MUST be a scheduling change only: same bytes, same files
    assert r["bytes_identical"] is True
    # budget sampling rides the same helper as the recovery block
    r2 = bench._ckpt_async_metrics(tree, byte_budget=64, n_saves=1)
    assert r2["sampled"] is True and r2["bytes"] <= 64


def test_supervisor_metrics_block():
    """The robustness-tax block (ISSUE 2 satellite): watchdog arm/disarm
    per-step cost, heartbeat write latency, and the 2-failure transient
    retry path — host-only, sleeps zeroed."""
    r = bench._supervisor_metrics(n=200)
    assert r["ok"] is True
    for k in ("watchdog_arm_disarm_us_per_step", "heartbeat_write_ms",
              "retry_2fail_recovered_ms"):
        assert r[k] > 0.0, k
    # arm/disarm is attribute swaps: if it ever costs more than 1 ms a
    # step, the watchdog became part of the problem it measures
    assert r["watchdog_arm_disarm_us_per_step"] < 1000.0


def test_elastic_metrics_block():
    """The elastic-restart block (ISSUE 3 satellite): sharded save on
    (dp=4, tp=2), reshard-restore onto dp=2 and dp=8, and the
    steady-state replica-hash verify pass — all on the suite's
    8-virtual-CPU-device mesh."""
    r = bench._elastic_metrics(rows=64, cols=64)
    assert r["ok"] is True
    assert r["bytes"] == 64 * 64 * 4 + 64 * 4
    # tp=2 cuts w and b into 2 shards each at save time
    assert r["n_shards"] == 4
    for k in ("save_dp4_ms", "restore_dp2_ms", "restore_dp8_ms",
              "verify_replicas_ms"):
        assert r[k] > 0.0, k


@pytest.mark.slow   # ~15 s: follows the spec/prefix/paged block-test
# precedent — every serving claim the block grades has a direct tier-1
# witness in test_serving*.py; the block itself runs in the slow lane
def test_serving_metrics_block():
    """The serving block (ISSUE 4 + ISSUE 7 satellites): prefill
    tokens/s, per-token decode latency, continuous-batching throughput
    at 1/4/8 streams with staggered arrivals, and the mixed-length
    bucketed-vs-padded comparison — plus BOTH compile-count regression
    guards (ONE decode compile after warmup; prefill compiles bounded
    by the bucket table)."""
    r = bench._serving_metrics(decode_tokens=12, prompt_len=4,
                               prefill_len=32, max_len=64, slots=4,
                               mixed_streams=4, mixed_decode_tokens=2,
                               mixed_attempts=1)
    assert r["ok"] is True
    assert r["prefill_tokens_per_s"] > 0.0
    assert r["decode_ms_per_token"] > 0.0
    assert set(r["throughput_tokens_per_s"]) == {"1", "4", "8"}
    for tps in r["throughput_tokens_per_s"].values():
        assert tps > 0.0
    assert r["speedup_4_vs_sequential"] > 0.0
    # the decode step function must compile exactly once per engine no
    # matter how streams arrive — retraces would be the recompile tax
    # the slotted cache exists to eliminate
    assert r["decode_compiles_after_warmup"] == 1
    # the prefill path's compile count is bounded by the bucket table —
    # a per-prompt-length retrace would blow straight through this
    assert r["prefill_buckets"] == [16, 32]
    assert 1 <= r["prefill_compiles"] <= len(r["prefill_buckets"])
    # the mixed-length comparison runs and reports a sane ratio (the
    # >= 1.5x acceptance bar is measured at the default, bigger sizes —
    # at this toy size the per-dispatch host tax flattens the ratio)
    mixed = r["mixed"]
    assert len(mixed["prompt_lens"]) == 4
    assert all(1 <= n <= 32 for n in mixed["prompt_lens"])
    assert mixed["tokens_per_s_bucketed"] > 0.0
    assert mixed["tokens_per_s_padded"] > 0.0
    assert mixed["speedup_bucketed_vs_padded"] > 0.0
    assert r["config"]["slots"] == 4


@pytest.mark.slow   # ~10 s: follows the spec/prefix/paged block-test
# precedent — tp serving itself stays witnessed by tests/test_serving_tp.py
# (stream identity, compile guards) and the block's grading by
# tests/test_bench_compare.py golden fixtures
def test_serving_tp_metrics_block():
    """The tensor-parallel serving block (ISSUE 15): tp=1 vs tp=2
    decode ms/token and aggregate tokens/s over one warmed engine pair,
    the stream-identity witness, and the compile-count guards on BOTH
    engines — sharding must not add a single extra compile to any
    program family."""
    r = bench._serving_tp_metrics(decode_tokens=8, prompt_len=8,
                                  prefill_len=16, max_len=48, slots=2,
                                  tp_size=2)
    assert r["ok"] is True, r
    # the acceptance witness: greedy streams token-identical across
    # mesh widths (raw logits are argmax-tier, documented deviation)
    assert r["streams_identical"] is True
    for side in ("tp1", "tp2"):
        assert r[side]["decode_ms_per_token"] > 0.0
        assert r[side]["aggregate_tokens_per_s"] > 0.0
        # the compile-count regression guards, sharded and unsharded
        assert r[side]["decode_compiles"] == 1, r
        assert r[side]["prefill_compiles"] == 1
    assert r["tp_vs_single_ratio"] > 0.0
    assert r["config"]["tp"] == 2


@pytest.mark.slow   # ~15 s: bench-harness plumbing stays witnessed by
# test_serving_metrics_block / _slo / _tp; spec exactness and accept-
# rate claims keep their tier-1 witnesses in test_serving_spec.py
def test_serving_spec_metrics_block():
    """The speculative-decode block (ISSUE 9): spec-vs-plain greedy
    decode tokens/s on an acceptance-friendly repetitive workload
    (bar >= 1.8x) and an adversarial random-token workload (bar >=
    1.0x — the fall-back path must not regress), with the exactness
    witness (streams token-identical every attempt) and BOTH
    compile-count regression guards: verify compiles bounded by the
    draft bucket table, decode compiles == 1 unchanged."""
    r = bench._serving_spec_metrics(attempts=1)
    assert r["ok"] is True
    # exactness is asserted inside the block on EVERY attempt — a
    # speedup from a diverged stream would be a lie, not a win
    assert r["streams_identical"] is True
    # the ISSUE-9 acceptance bars
    assert r["speedup_repetitive"] >= 1.8, r
    assert r["speedup_adversarial"] >= 1.0, r
    # compile-count guards: bounded by the draft bucket table, and the
    # batched decode step still compiles exactly once
    assert r["draft_buckets"] == [1, 2, 4, 8]
    assert 1 <= r["verify_compiles"] <= len(r["draft_buckets"])
    assert r["decode_compiles"] == 1
    for name in ("repetitive", "adversarial"):
        w = r["workloads"][name]
        assert w["tokens_per_s_plain"] > 0.0
        assert w["tokens_per_s_spec"] > 0.0
        assert w["verify_dispatches"] > 0
        assert 0 <= w["accepted"] <= w["drafted"]
        # even a fully-rejected verify emits its bonus token, so the
        # speculative path never amortizes below one token/dispatch
        assert w["tokens_per_dispatch"] >= 1.0
        assert 0.0 <= w["accept_rate"] <= 1.0
    # the friendly workload must actually accept more than the
    # adversarial one — otherwise "repetitive" is mislabeled
    assert (r["workloads"]["repetitive"]["accept_rate"]
            >= r["workloads"]["adversarial"]["accept_rate"])


@pytest.mark.slow   # ~16 s: block plumbing witnessed by
# test_serving_metrics_block; the prefix hit/identity claims keep
# their tier-1 witnesses in test_serving_prefix.py
def test_serving_prefix_metrics_block():
    """The cross-request prefix-caching block (ISSUE 10): aggregate
    prefill tokens/s for 8 requests sharing a long system prompt —
    caching off vs cold cache vs warm cache — plus a zero-overlap
    workload where the cache can only cost.  Bars: warm >= 2x cold on
    the shared-prefix workload, no regression (>= 1.0x best-of-N)
    without overlap; streams token-identical across off/cold/warm on
    every attempt (the speedup is elided prefill, never drift); and
    the compile guards — restore compiles bounded by the prefill
    bucket table, decode compiles == 1 untouched.

    The zero-overlap bar is "no regression within the harness's own
    measured noise floor": copy-based capture has a real but sub-noise
    cost (~0.5-1% on a prefill-only drain at this scale — see the
    block's docstring and PERF_NOTES), so the block compares medians
    and measures the wider of the two pools' own spreads as the
    yardstick; a genuine regression is a consistent gap between
    tight pools and fails.  attempts=3
    (the default) keeps the pooled medians robust to one slow drain —
    at attempts=2 the 2-sample on-side median is a mean, and a single
    scheduler hiccup flaked the bar."""
    r = bench._serving_prefix_metrics()
    assert r["ok"] is True
    # exactness is asserted inside the block on EVERY attempt — a
    # speedup from a diverged stream would be a lie, not a win
    assert r["streams_identical"] is True
    shared = r["shared_prefix"]
    # the ISSUE-10 acceptance bars
    assert shared["speedup_warm_vs_cold"] >= 2.0, r
    zero = r["zero_overlap"]
    assert zero["no_regression_within_noise"] is True, r
    # hard floor: a sub-noise capture tax is tolerated, a real
    # slowdown is not, no matter how noisy the host claims to be
    assert zero["ratio_on_vs_off"] >= 0.9, r
    for k in ("prefill_tokens_per_s_off", "prefill_tokens_per_s_cold",
              "prefill_tokens_per_s_warm"):
        assert shared[k] > 0.0, k
    # a hit restores the shared tokens, so a warm admission must also
    # beat the caching-off baseline, not just its own cold pass
    assert shared["speedup_warm_vs_off"] > 1.0, r
    # compile-count guards: bounded by the bucket table, and the
    # batched decode step still compiles exactly once
    assert r["prefill_buckets"] == [16, 32, 64, 128]
    assert 1 <= r["restore_compiles"] <= len(r["prefill_buckets"])
    assert 1 <= r["prefill_compiles"] <= len(r["prefill_buckets"])
    assert r["decode_compiles"] == 1


@pytest.mark.slow   # ~33 s: block plumbing witnessed by
# test_serving_metrics_block; paged identity/capacity claims keep
# their tier-1 witnesses in test_serving_paged.py
def test_serving_paged_metrics_block():
    """The paged-KV-cache block (ISSUE 11): dense-vs-paged decode
    ms/token, warm shared-prompt admission via zero-copy block-table
    aliasing (with the dense copy-based speedup measured back to back
    as the PR-9 baseline), and concurrent-stream capacity at a fixed
    cache byte budget — the acceptance bar: >= 4x the dense layout.
    Exactness (streams identical across layouts and cache states) is
    asserted inside the block on every attempt; the zero-copy claim is
    pinned structurally — the restore and region-read programs never
    compile on the paged engine — and the compile guards ride along."""
    r = bench._serving_paged_metrics(
        streams=4, attempts=1, slots=4, decode_steps=12,
        cap_max_len=128, cap_dense_slots=2, cap_prompt_len=24,
        cap_new_tokens=4, cap_submitted=12)
    assert r["ok"] is True
    assert r["streams_identical"] is True
    d = r["decode"]
    assert d["ms_per_token_dense"] > 0.0
    assert d["ms_per_token_paged"] > 0.0
    assert d["paged_overhead_ratio"] > 0.0
    w = r["warm_admission"]
    for k in ("prefill_tokens_per_s_off", "prefill_tokens_per_s_cold",
              "prefill_tokens_per_s_warm"):
        assert w[k] > 0.0, k
    # a zero-copy hit must beat its own cold pass like the copy-based
    # path did (the PR-9 bar) — the full-size margin over the dense
    # baseline is measured at the defaults and recorded in PERF_NOTES
    assert w["speedup_warm_vs_cold"] >= 2.0, r
    # THE zero-copy dispatch witness: no restore program, no region
    # read ever compiled; the hits are visible as aliased blocks
    z = r["zero_copy"]
    assert z["restore_compiles"] == 0
    assert z["read_compiles"] == 0
    assert z["alias_blocks"] > 0
    # THE ISSUE-11 capacity bar: >= 4x concurrent streams in the same
    # cache bytes (peak measured over a real drain, both layouts
    # serving every request to completion)
    c = r["capacity"]
    assert c["peak_streams_dense"] == c["dense_max_streams"]
    assert c["capacity_ratio"] >= 4.0, r
    # compile guards: one decode program, prefill bounded by buckets
    assert r["decode_compiles"] == 1
    assert 1 <= r["prefill_compiles"] <= len(r["prefill_buckets"])


@pytest.mark.slow   # ~11 s: follows the spec/prefix/paged/tp block-test
# precedent — the SLO recorder/report surface stays witnessed by
# tests/test_serving_slo.py and the policy contrast by
# tests/test_serving_policy.py; block grading by bench_compare goldens
def test_serving_slo_metrics_block():
    """The request-level SLO block (ISSUE 12): a seeded bursty
    open-loop workload at ~1x and ~2x the measured sustainable load,
    per-request lifecycle records assembled off the event stream, and
    nearest-rank p50/p95/p99 TTFT / TPOT / queue-wait + goodput per
    load — with the workload's bit-reproducibility witnessed by its
    schedule fingerprint and the compile-count guards held (the
    recorder and load generator are pure host layers)."""
    r = bench._serving_slo_metrics(n_requests=10, prompt_len=24,
                                   new_tokens=6, slots=4, burst=2,
                                   max_len=64, prefill_len=32)
    assert r["ok"] is True
    assert r["sustainable_rps"] > 0.0
    assert r["deadline_s"] > 0.0
    assert set(r["loads"]) == {"1x", "2x"}
    fingerprints = set()
    for name, load in r["loads"].items():
        assert load["completed"] + load["shed"] <= 10
        assert load["completed"] >= 1
        for series in ("ttft_s", "tpot_s", "queue_wait_s"):
            s = load[series]
            assert s["n"] == load["completed"], (name, series)
            # nearest-rank percentiles are actual samples: ordered,
            # non-negative, p50 <= p95 <= p99
            assert 0.0 <= s["p50"] <= s["p95"] <= s["p99"], (name,
                                                             series)
        assert 0.0 <= load["goodput"] <= 1.0
        assert (load["deadline_misses"]
                == 10 - round(load["goodput"] * 10))
        # the exact samples and the Prometheus histogram quantiles are
        # computed over the SAME run (registry reset per load)
        assert load["crosscheck_aligned"] is True
        # same-seed rebuild equality is asserted INSIDE the block; the
        # fingerprint must also differ across loads (different periods)
        fingerprints.add(load["fingerprint"])
    assert len(fingerprints) == 2
    # compile guards: pure host layers — one decode program, prefill
    # bounded by the bucket table
    assert r["decode_compiles"] == 1
    assert 1 <= r["prefill_compiles"] <= len(r["prefill_buckets"])
    # the ISSUE-13 control-plane variant: FIFO vs policy on one
    # SLO-differentiated workload.  At this toy size the run is not
    # reliably overloaded, so the assertions are structural (the
    # direction story lives in the default-size PERF_NOTES round);
    # the compile identity IS asserted inside the block itself
    pol = r["policy"]
    hi_count = len([i for i in range(10) if i % 3 == 0])
    for variant in ("fifo", "policy"):
        v = pol[variant]
        assert 0.0 <= v["goodput"] <= 1.0, variant
        assert v["hp_ttft_p99_s"] >= 0.0
        assert v["hp_served"] == hi_count
        assert v["completed"] <= 10
    assert pol["fifo"]["preempted"] == pol["fifo"]["shed"] == 0
    assert pol["hp_ttft_p99_speedup"] > 0.0
    assert -1.0 <= pol["goodput_delta"] <= 1.0


@pytest.mark.slow   # ~25 s: block plumbing witnessed by
# test_serving_metrics_block; the reload/rollback/A-B correctness
# claims keep their tier-1 witnesses in test_serving_reload.py
def test_serving_reload_metrics_block():
    """The hot-reload block (ISSUE 16): swap pause as p99 step-time
    inflation of a mid-drain reload run over a steady run (back-to-back
    arrivals, so walls are compute), the per-phase reload wall split,
    zero dropped streams, the zero-recompile swap guard, and the
    shadow/A-B mirror cost at paced load with the saturated worst case
    recorded alongside."""
    r = bench._serving_reload_metrics(
        n_requests=8, new_tokens=6, burst=4, ab_period_s=0.4)
    assert r["ok"] is True
    # the reload wall is the sum of its phases, restore-dominated
    # (this reloader reads the checkpoint synchronously in the hook)
    assert r["restore_s"] > 0.0
    assert r["reload_wall_s"] >= r["restore_s"]
    assert abs(r["reload_wall_s"] - (r["restore_s"] + r["validate_s"]
                                     + r["swap_s"])) < 1e-3
    # swap pause is a max(0, delta): never negative, and the reload
    # run's p99 can't undercut it
    assert r["swap_pause_ms"] >= 0.0
    assert r["reload_step_ms_p99"] > 0.0
    assert r["steady_step_ms_p99"] > 0.0
    # THE robustness bars: no stream dropped, no program recompiled
    assert r["dropped_streams"] == 0
    assert r["completed"] == 8
    assert r["decode_compiles"] == 1
    ab = r["ab"]
    assert ab["mirrored_requests"] >= 1
    assert ab["mirror_shed"] == 0
    assert ab["ab_mirror_overhead_ratio"] > 0.0
    # sharing one host thread, mirrored work can only add wall —
    # the saturated ratio is the no-headroom ceiling
    assert ab["saturated_overhead_ratio"] > 0.0
    # restore-ahead contrast (ISSUE 17 satellite): the staged phases
    # were real work, the in-run swap alone paused the streams
    pf = r["prefetch"]
    assert pf["staged_restore_s"] > 0.0
    assert pf["swap_s"] >= 0.0
    assert pf["swap_pause_ms"] >= 0.0
    assert pf["dropped_streams"] == 0 and pf["completed"] == 8


@pytest.mark.slow   # ~40 s: three warmed replicas; the failover
# correctness claims keep their tier-1 witnesses in
# tests/test_serving_fleet.py — this pins the block's shape and bars
def test_serving_fleet_metrics_block():
    """The fleet block (ISSUE 17): unperturbed baseline vs a mid-drain
    replica kill with failover on (zero dropped streams, failover
    latency from the router's own resume events, no recompiles on the
    survivors) vs the same chaos with failover off (the goodput the
    machinery buys)."""
    r = bench._serving_fleet_metrics(n_requests=9, new_tokens=6)
    assert r["ok"] is True
    assert r["replicas"] == 3
    assert r["baseline_tokens_per_s"] > 0.0
    assert r["kill_tokens_per_s"] > 0.0
    assert r["throughput_vs_baseline"] > 0.0
    # THE robustness bars: every admitted stream served, failover
    # observed, nothing recompiled on the survivors
    assert r["dropped_streams"] == 0
    assert r["failovers"] >= 1
    assert r["failover_latency_s"] >= 0.0
    assert r["shed"] == 0
    assert r["decode_compiles"] == 3      # one warmed program each
    # what failover buys: identical chaos, strictly better goodput
    assert r["goodput_failover"] == 1.0
    assert r["goodput_no_failover"] < 1.0
    assert r["goodput_delta"] > 0.0
    assert r["victims_lost_no_failover"] >= 1


@pytest.mark.slow   # ~40 s: three warmed replicas; the rollout
# correctness claims keep their tier-1 witnesses in
# tests/test_serving_rollout.py — this pins the block's shape and bars
def test_serving_rollout_metrics_block():
    """The rolling-upgrade block (ISSUE 18): a gated rollout over a
    live 3-replica fleet promotes with zero dropped streams, a passing
    canary verdict, per-replica swap pauses, and no recompiles."""
    r = bench._serving_rollout_metrics(n_requests=12, new_tokens=5)
    assert r["ok"] is True
    assert r["replicas"] == 3
    # THE acceptance bars: promoted (asserted inside the helper),
    # nothing dropped, nothing halted or rolled back on the clean path
    assert r["dropped_streams"] == 0
    assert r["halts"] == 0
    assert r["rollbacks"] == 0
    assert r["shed"] == 0
    assert r["completed"] == 12
    # the operator-facing walls are real and ordered: the verdict
    # window sits inside the rollout wall
    assert r["rollout_wall_s"] > 0.0
    assert 0.0 < r["verdict_latency_s"] < r["rollout_wall_s"]
    # the reload pause is swap-only (prefetch staged the restore)
    assert 0.0 <= r["swap_pause_s_mean"] <= r["swap_pause_s_max"]
    assert r["swap_pause_s_max"] < 1.0
    # the canary arm really served pinned traffic in its window
    assert r["canary_offered"] >= 1
    assert r["canary_completed"] >= 1
    # one warmed program per replica, before and after the upgrade
    assert r["decode_compiles"] == 3


def test_serving_slo_block_reproducible_schedule():
    """Same seed ⇒ same arrival schedule and token-stream fingerprint,
    across two fresh builds of the workload (the bench block's
    bit-reproducibility acceptance, pinned without timing)."""
    from apex_tpu.serving import burst_arrivals, make_workload, \
        zero_overlap_prompts

    def build():
        prompts = zero_overlap_prompts(6, length=8, vocab=256, seed=7)
        return make_workload(prompts,
                             burst_arrivals(6, burst=2, period_s=0.5),
                             max_new_tokens=4, deadline_s=1.0, seed=7)

    assert (build().schedule_fingerprint()
            == build().schedule_fingerprint())


@pytest.mark.slow   # ~40 s: three warmed replicas, two chaos drains;
# the attribution/trace/alert correctness claims keep their tier-1
# witnesses in tests/test_obs_fleet.py — this pins the block's shape
def test_obs_fleet_metrics_block():
    """The fleet-observability-tax block (ISSUE 20): the serving_fleet
    chaos drain bare vs fully instrumented (named replicas + request
    recorder + per-step alert engine), standalone alert evaluation at
    n_rules/step, and the per-replica trace export."""
    r = bench._obs_fleet_metrics(n_requests=9, new_tokens=6, rounds=2,
                                 n_rules=8, n_alert_evals=50)
    assert r["ok"] is True
    assert r["bare_wall_s"] > 0.0
    assert r["instrumented_wall_s"] > 0.0
    # the 1.10x budget is the graded bar (bench_compare: "overhead" is
    # lower-is-better); the hard test bar only guards against the
    # instrumentation becoming the workload on a noisy CI host
    assert 0.0 < r["overhead_ratio"] < 3.0
    assert r["alert_eval_us_per_step"] > 0.0
    assert r["trace_export_ms"] > 0.0
    # replica_down fired when the kill dropped healthy below 3 and
    # never resolved (the bench run ends with the replica still dead)
    assert r["alerts_firing"] == 1
    assert r["alert_transitions"] == 1
    assert r["traced_requests"] == 9
    # one warmed program per replica on BOTH legs — attribution,
    # recording, and alerting added zero compiles
    assert r["decode_compiles"] == 3


def test_obs_metrics_block():
    """The observability-tax block (ISSUE 6 satellite): per-update cost
    of each instrument kind, span enter/exit, and exposition latency at
    1k series — the budget that proves instrumentation is negligible
    when no exporter is attached."""
    r = bench._obs_metrics(n=5_000, n_series=200)
    assert r["ok"] is True
    for k in ("counter_inc_ns", "gauge_set_ns", "histogram_observe_ns",
              "span_ns_no_recorder", "span_ns_recording",
              "exposition_ms"):
        assert r[k] > 0.0, k
    # a metric update is a lock + dict write; a no-recorder span is one
    # global read + a generator frame.  50 µs/op is ~100x the measured
    # cost — if these trip, instrumentation became the workload
    assert r["counter_inc_ns"] < 50_000.0
    assert r["gauge_set_ns"] < 50_000.0
    assert r["histogram_observe_ns"] < 50_000.0
    assert r["span_ns_no_recorder"] < 100_000.0
    assert r["exposition_series"] == 200


_SMOKE_BLOCK_FNS = (
    "_recovery_metrics", "_ckpt_async_metrics", "_supervisor_metrics",
    "_elastic_metrics", "_serving_metrics", "_serving_tp_metrics",
    "_serving_spec_metrics", "_serving_prefix_metrics",
    "_serving_paged_metrics", "_serving_slo_metrics", "_obs_metrics",
    "_obs_fleet_metrics")


@pytest.mark.slow   # ~62 s: the slim timing smoke has itself outgrown
# the tier-1 budget; the timing protocol stays guarded here in the slow
# lane and by every bench.py capture
def test_cpu_smoke_train_step_timing(monkeypatch):
    """The timing protocol on the real (CPU) backend, diagnostic blocks
    stubbed out: tier-1 keeps the real-execution train-step path (every
    block already has its own block test above), the full all-blocks
    smoke runs under -m slow.

    steps=16 + one retry: the t(2N) > 1.2*t(N) sanity gate is a
    real-execution check, not a precision claim, and 2-step timings on a
    loaded CI host can flake it.
    """
    for fn in _SMOKE_BLOCK_FNS:
        monkeypatch.setattr(bench, fn,
                            lambda *a, **k: {"ok": False,
                                             "skipped": "slim smoke"},
                            raising=True)
    for attempt in range(2):
        try:
            result = bench.run_config("cpu-smoke", steps=16)
            break
        except AssertionError:
            if attempt:
                raise
    assert result["value"] > 0
    assert result["config"]["loss_end"] < result["config"]["loss0"]
    for key in ("recovery", "serving", "serving_tp", "obs"):
        assert result[key] == {"ok": False, "skipped": "slim smoke"}


@pytest.mark.slow   # ~107 s: every diagnostic block over one real
                    # config — each block is tier-1-guarded by its own
                    # block test above; this is the glue run
def test_cpu_smoke_end_to_end(monkeypatch):
    """The real measurement path on the real (CPU) backend, every
    diagnostic block live."""
    for attempt in range(2):
        try:
            result = bench.run_config("cpu-smoke", steps=16)
            break
        except AssertionError:
            if attempt:
                raise
    assert result["value"] > 0
    assert result["config"]["loss_end"] < result["config"]["loss0"]
    # the diagnostic blocks ride every captured config
    assert result["recovery"]["ok"] is True
    assert result["ckpt_async"]["ok"] is True
    assert result["ckpt_async"]["bytes_identical"] is True
    assert result["supervisor"]["ok"] is True
    assert result["elastic"]["ok"] is True
    assert result["serving"]["ok"] is True
    # tp block: ok under the suite's forced 8 host devices; the
    # streams-identical witness is the acceptance bar riding along
    assert result["serving_tp"]["ok"] is True
    assert result["serving_tp"]["streams_identical"] is True
    assert result["serving_tp"]["tp1"]["decode_compiles"] == 1
    assert result["serving_tp"]["tp2"]["decode_compiles"] == 1
    assert result["serving_spec"]["ok"] is True
    assert result["serving_spec"]["streams_identical"] is True
    assert result["serving_prefix"]["ok"] is True
    assert result["serving_prefix"]["streams_identical"] is True
    assert result["serving_paged"]["ok"] is True
    assert result["serving_paged"]["streams_identical"] is True
    assert result["serving_slo"]["ok"] is True
    assert result["obs"]["ok"] is True
