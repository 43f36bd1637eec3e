"""The benchmark's command, end to end, on the CPU rehearsal path: the
training runner at a toy configuration, and the refusal to run with no
chip."""

import json

import pytest

from cells import metrics_of, run_cell

CELL = "tiny-gpt2.tiny-pretrain"


@pytest.fixture(scope="module")
def rehearsal():
    proc = run_cell(CELL, "--trace", "0", "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip().splitlines()


def test_last_line_is_the_contracts_object(rehearsal):
    last = json.loads(rehearsal[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device", "rehearsal"}
    assert last["rehearsal"] is True                 # a CPU run says so
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert last["device"]["platform"] == "cpu"
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 2


def test_every_end_to_end_metric_of_the_cell_is_printed(rehearsal):
    last = json.loads(rehearsal[-1])
    want = metrics_of("end_to_end", CELL)
    assert set(last["metrics"]) == set(want) == {"train_tok_s", "setup_s"}
    for name, unit in want.items():
        assert last["metrics"][name]["unit"] == unit
        assert last["metrics"][name]["value"] > 0


def test_earlier_lines_carry_the_setup_breakdown_and_the_checks(rehearsal):
    earlier = [json.loads(l) for l in rehearsal[:-1] if l.startswith("{")]
    setup = next(l for l in earlier if "setup_breakdown_s" in l)
    assert {"imports", "backend_start", "init", "trace_lower",
            "compile_or_cache_load", "warmup",
            "checks"} <= set(setup["setup_breakdown_s"])
    last = json.loads(rehearsal[-1])
    assert setup["setup_s"] == last["metrics"]["setup_s"]["value"]
    # checks run behind the window: they are no part of setup_s
    stages = setup["setup_breakdown_s"]
    assert setup["setup_s"] < sum(stages.values()) - stages["checks"] + 0.5
    notes = next(l for l in earlier if "notes" in l)["notes"]
    assert notes["checks"] == {
        "losses_finite": True, "loss_falling": True,
        "kernels_on_pallas_path": True, "no_compile_in_window": True,
        "reference_rel_err": notes["checks"]["reference_rel_err"],
        "reference_tolerance": 1e-3, "reference_ok": True}
    assert notes["checks"]["reference_rel_err"] < 1e-4
    assert any("jax_compile_events_s" in l for l in earlier)


def test_without_a_chip_there_is_no_result():
    proc = run_cell(CELL, "--trace", "0")            # no --rehearse
    assert proc.returncode != 0
    assert "No result" in proc.stderr
    assert not any(l.startswith("{") for l in proc.stdout.splitlines())


def test_an_unknown_cell_is_refused():
    proc = run_cell("no-such.cell", "--trace", "0", "--rehearse")
    assert proc.returncode != 0 and proc.stdout.strip() == ""
