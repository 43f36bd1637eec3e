"""The reduction from trace events to per-layer numbers, on hand-made
events with known answers and on two fixtures recorded on the chip in
PR 24 (benchmark/fixtures/: three GPT-2-large training steps thinned to the
long ops and the Pallas kernels; six Mistral-7B-L16 decode steps with their
sampler calls, thinned to the ops of 0.3 ms and more)."""

import os
import statistics

import pytest

from benchmark.lib import flops
from benchmark.lib import trace as tr
from benchmark.reducers import (ReduceContext, counter, device_idle, mfu,
                                module_median, op_time)

FIXTURES = os.path.join(os.path.dirname(tr.__file__), os.pardir, "fixtures")


@pytest.fixture(scope="module")
def recorded():
    return tr.load_fixture(os.path.join(FIXTURES, "train_trace.json"))


@pytest.fixture(scope="module")
def recorded_serve():
    return tr.load_fixture(os.path.join(FIXTURES, "serve_trace.json"))


def _hand_made():
    mods = [("jit_step(1)", 0, 100), ("jit_step(1)", 200, 120),
            ("jit__prefill(7)", 400, 50), ("jit__prefill(8)", 500, 90),
            ("jit__prefill(8)", 600, 70)]
    ops = [("%fusion.1 = f32[8]{0} fusion(...)", 0, 40),
           ("%fusion.22 = f32[8]{0} fusion(...)", 30, 30),      # overlaps
           ("%flash_attention_dkv.50 = (bf16[1]) custom-call()", 70, 30),
           ("%flash_attention_dkv.41 = (bf16[1]) custom-call()", 200, 20),
           ("%flash_attention_fwd = bf16[1] custom-call()", 230, 10),
           ("%copy.3 = f32[2] copy(...)", 250, 70),
           ("%outside.1 = f32[2] copy(...)", 350, 10)]          # no module
    spans = [("sched_step", 90, 120), ("submit", 100, 20),
             ("sched_step", 215, 20)]
    return tr.Trace(modules=mods, ops=ops, spans=spans, device="/device:TPU:0")


@pytest.mark.parametrize("full,short", [
    ("%flash_attention_dkv.50 = (bf16[160,1024,64]{2,1,0}) custom-call(",
     "flash_attention_dkv"),
    ("%fusion.5116 = (bf16[1280]{0}) fusion(bf16[8192,1280] %x.1)", "fusion"),
    ("%fused_lm_head_de.1 = bf16[50688,1280]{1,0} custom-call(", "fused_lm_head_de"),
    ("%convert_element_type.3130 = bf16[1280]{0} convert(f32[1280]{0} %p.2)",
     "convert_element_type"),
    ("%copy-start.1280 = (f32[1,1280]) copy-start(", "copy-start"),
    ("no_equals_sign.7", "no_equals_sign"),
])
def test_short_name_drops_the_instruction_and_the_id(full, short):
    assert tr.short_name(full) == short


def test_busy_union_merges_overlaps_and_lists_gaps():
    busy, window, gaps = tr.busy_union(_hand_made().ops)
    # [0,60) + [70,100) + [200,220) + [230,240) + [250,320) + [350,360)
    assert busy == 60 + 30 + 20 + 10 + 70 + 10
    assert window == 360
    assert gaps == [(60, 70), (100, 200), (220, 230), (240, 250), (320, 350)]
    assert tr.busy_union([]) == (0.0, 0.0, [])


def test_module_medians_pool_or_pick_the_largest_program():
    t = _hand_made()
    assert tr.module_median_ms(t, "^jit_step") == pytest.approx(110e-6)
    assert tr.module_median_ms(t, "^jit__prefill") == pytest.approx(70e-6)
    # per program: (7) -> 50, (8) -> median(90, 70) = 80
    assert tr.module_median_ms(t, "^jit__prefill", "largest") == \
        pytest.approx(80e-6)
    assert tr.module_median_ms(t, "^jit_absent") is None


def test_op_time_counts_only_ops_inside_the_matched_modules():
    t = _hand_made()
    # flash ops: 30 + 20 + 10 over two executions of jit_step
    assert tr.op_time_per_module_ms(t, "^flash_attention_", "^jit_step") == \
        pytest.approx(30e-6)
    assert tr.op_time_per_module_ms(t, "^outside", "^jit_step") is None
    assert tr.op_time_per_module_ms(t, "^fusion", "^jit_absent") is None


def test_op_time_leaves_out_the_edge_executions_of_a_busy_slice():
    # a slice that starts and stops mid-step: the first and the last of
    # three executions hold only part of their ops
    mods = [("jit_step(1)", 0, 100), ("jit_step(1)", 100, 100),
            ("jit_step(1)", 200, 100)]
    ops = [("%k.1 = x", 60, 10),                            # first: 1 of 2
           ("%k.2 = x", 110, 10), ("%k.3 = x", 160, 10),    # whole
           ("%k.4 = x", 210, 10)]                           # last: 1 of 2
    t = tr.Trace(mods, ops, [])
    assert tr.op_time_per_module_ms(t, "^k$", "^jit_step") == \
        pytest.approx(20e-6)


def test_top_ops_sum_by_short_name():
    top = tr.top_ops(_hand_made(), n=3)
    assert top == [["copy", 70e-9], ["fusion", 70e-9],
                   ["flash_attention_dkv", 50e-9]] or \
        top == [["fusion", 70e-9], ["copy", 70e-9],
                ["flash_attention_dkv", 50e-9]]
    assert len(tr.top_ops(_hand_made(), n=10)) == 5


def test_gaps_go_to_the_innermost_span_covering_their_middle():
    got = dict(tr.attribute_gaps(_hand_made()))
    # (60,70): no span; (100,200) middle 150 -> sched_step (submit ended at
    # 120); (220,230) -> second sched_step; (240,250), (320,350): none
    assert got == {"sched_step": pytest.approx(110e-9),
                   "(no span)": pytest.approx(50e-9)}
    nested = tr.Trace([], [("a = x", 0, 10), ("b = x", 30, 10)],
                      [("outer", 0, 100), ("inner", 15, 10)])
    assert dict(tr.attribute_gaps(nested)) == {"inner": pytest.approx(20e-9)}


def test_recorded_fixture_reads_like_the_chip_run(recorded):
    # the device said 418.4 ms a step where the traced host clock said 494.5
    assert tr.module_median_ms(recorded, "^jit_train_step") == \
        pytest.approx(418.37, abs=0.05)
    lm = tr.op_time_per_module_ms(recorded, "^fused_lm_head_",
                                  "^jit_train_step")
    flash = tr.op_time_per_module_ms(recorded, "^flash_attention_",
                                     "^jit_train_step")
    assert lm == pytest.approx(28.8, abs=0.2)
    assert 60 < flash < 100
    names = [n for n, _ in tr.top_ops(recorded)]
    assert len(names) == len(set(names)) == 10
    assert all(" = " not in n and not n[-1].isdigit() for n in names)
    assert "flash_attention_dkv" in names


def test_recorded_serving_steps_read_decode_and_attribute_the_gaps(
        recorded_serve):
    t = recorded_serve
    # decode and its sampler alternate; only decode matches its pattern
    assert [n.split("(")[0] for n, _, _ in t.modules[:4]] == [
        "jit__decode", "jit__sample_one"] * 2
    assert tr.module_median_ms(t, "^jit__decode") == \
        pytest.approx(59.26, abs=0.02)
    assert tr.module_median_ms(t, "^jit__sample_one") == \
        pytest.approx(0.481, abs=0.002)
    # no prefill ran in these six steps: the reader finds nothing
    assert tr.module_median_ms(t, "^jit__prefill", "largest") is None
    # between a step's sampler and the next decode the host is inside
    # sched.step(): the idle time goes to that span
    gaps = dict(tr.attribute_gaps(t))
    assert max(gaps, key=gaps.get) == "sched_step"
    # S5.1 on the record: the head-repeated view is a broadcast
    assert tr.top_ops(t)[0][0] == "broadcast_in_dim"


def test_recorded_busy_union_against_a_plain_sweep(recorded):
    busy, window, gaps = tr.busy_union(recorded.ops)
    edges = sorted({s for _, s, _ in recorded.ops}
                   | {s + d for _, s, d in recorded.ops})
    ivs = [(s, s + d) for _, s, d in recorded.ops]
    ivs.sort()
    covered, i, live_end = 0, 0, -1
    for a, b in zip(edges, edges[1:]):
        while i < len(ivs) and ivs[i][0] <= a:
            live_end = max(live_end, ivs[i][1])
            i += 1
        if live_end >= b:
            covered += b - a
    assert busy == covered
    assert window == edges[-1] - edges[0]
    assert window - busy == sum(b - a for a, b in gaps)
    assert sum(s for _, s in tr.attribute_gaps(recorded, n=100)) == \
        pytest.approx((window - busy) / 1e9)


def test_reducers_read_the_trace_and_return_none_without_one(recorded):
    config = {"n_embd": 1280, "n_layer": 36,
              "assumed": {"padded_vocab_size": 50304}}
    traffic = {"batch": 8, "seq_len": 1024}
    rc = ReduceContext(recorded, {"batch_occupancy": 0.93}, config, traffic,
                       "TPU v5 lite")
    step = module_median.reduce(rc, module="^jit_train_step")
    assert step == pytest.approx(418.37, abs=0.05)
    need = flops.gpt_train_step(config, traffic)
    assert need == pytest.approx(40.27e12, rel=1e-3)
    assert mfu.reduce(rc, module="^jit_train_step",
                      flops_fn="gpt_train_step") == \
        pytest.approx(100 * need / (step / 1e3 * 197e12))
    assert 0 < device_idle.reduce(rc) < 100
    assert op_time.reduce(rc, ops="^flash_attention_",
                          module="^jit_train_step") > 60
    assert counter.reduce(rc, key="batch_occupancy", scale=100.0) == 93.0
    assert counter.reduce(rc, key="absent") is None
    none = ReduceContext(None, {}, config, traffic, "TPU v5 lite")
    assert module_median.reduce(none, module="x") is None
    assert mfu.reduce(none, module="x", flops_fn="gpt_train_step") is None
    assert device_idle.reduce(none) is None
    assert op_time.reduce(none, ops="x", module="y") is None
    with pytest.raises(KeyError):
        mfu.reduce(ReduceContext(recorded, {}, config, traffic, "TPU v9"),
                   module="^jit_train_step", flops_fn="gpt_train_step")


def test_trace_round_trips_through_json(recorded):
    again = tr.Trace.from_json(recorded.to_json())
    assert again.modules == recorded.modules
    assert statistics.median(d for _, _, d in again.ops) == \
        statistics.median(d for _, _, d in recorded.ops)
