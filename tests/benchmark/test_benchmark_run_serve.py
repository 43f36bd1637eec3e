"""The benchmark's command, end to end, on the CPU rehearsal path: the
serving runner at a toy configuration, untraced and traced."""

import json

import pytest

from cells import metrics_of, run_cell

CELL = "tiny-mistral.tiny-chat-closed"


@pytest.fixture(scope="module")
def untraced():
    proc = run_cell(CELL, "--trace", "0", "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip().splitlines()


@pytest.fixture(scope="module")
def traced():
    proc = run_cell(CELL, "--trace", "1", "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip().splitlines()


def test_untraced_line_has_the_cells_end_to_end_metrics(untraced):
    last = json.loads(untraced[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device", "rehearsal"}
    want = metrics_of("end_to_end", CELL)
    assert set(last["metrics"]) == set(want) == {
        "serve_tok_s", "itl_p95_ms", "setup_s"}
    for name, unit in want.items():
        assert last["metrics"][name]["unit"] == unit
        assert last["metrics"][name]["value"] > 0
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 4


def test_serving_checks_and_setup_stages_are_on_earlier_lines(untraced):
    earlier = [json.loads(l) for l in untraced[:-1] if l.startswith("{")]
    notes = next(l for l in earlier if "notes" in l)["notes"]
    checks = notes["checks"]
    assert all(checks[k] is True for k in (
        "decode_compiles_is_1", "prefill_compiles_within_buckets",
        "no_compile_in_window", "no_request_failed", "gaps_match_tokens",
        "reference_ok"))
    assert checks["reference_rel_err_first_token"] < 1e-4
    assert checks["reference_rel_err_after_decode"] < 1e-4
    assert notes["prefill_compiles"] == len(notes["prefill_buckets"])
    assert notes["gaps"] > notes["requests_finished_in_window"]
    setup = next(l for l in earlier if "setup_breakdown_s" in l)
    assert {"imports", "backend_start", "init", "engine",
            "warmup_trace_compile_or_cache_load", "ramp",
            "window_and_drain", "checks"} <= set(setup["setup_breakdown_s"])


def test_traced_line_has_layer_metrics_and_leaves_out_what_it_cannot_read(
        traced):
    last = json.loads(traced[-1])
    want = metrics_of("per_layer", CELL)
    assert "batch_occupancy.serve" in want and "decode_step_ms.serve" in want
    # the CPU has no device lines: the loop's own counts are there, the
    # device time is left out, and no end-to-end metric is on a traced line
    assert set(last["metrics"]) == {"batch_occupancy.serve",
                                    "ttft_p90_ms.serve"}
    assert last["metrics"]["ttft_p90_ms.serve"]["value"] > 0
    occ = last["metrics"]["batch_occupancy.serve"]
    assert occ["unit"] == "%" and 0 < occ["value"] <= 100
    assert last["correct"] is True and last["rehearsal"] is True
