"""The traffic generator and the closed-loop driver, on the scheduler's
virtual clock with a toy engine."""

import collections
import json
import os

import pytest

from benchmark.lib import traffic as tf
from benchmark.lib.stats import iqr_share, percentile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _traffic(name):
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           f"{name}.json")) as f:
        return json.load(f)


def test_chat_closed_parameters_are_the_issues():
    t = _traffic("chat-closed")
    assert t["clients"] == 16 and t["strata"] == 16
    assert t["engine"] == {"slots": 16, "max_len": 2048, "prefill_len": 512}
    assert t["prompt_len"] == {"dist": "lognormal", "median": 256,
                               "sigma": 0.9, "min": 32, "max": 1536}
    assert t["output_len"] == {"dist": "lognormal", "median": 96,
                               "sigma": 0.7, "min": 16, "max": 256}


def test_stratum_lengths_cover_each_sixteenth_once_and_clip():
    t = _traffic("chat-closed")
    prompts = tf.stratum_lengths(t["prompt_len"], 16)
    outputs = tf.stratum_lengths(t["output_len"], 16)
    assert prompts == sorted(prompts) and outputs == sorted(outputs)
    assert len(set(prompts)) == 16
    assert 32 <= prompts[0] and prompts[-1] <= 1536
    assert 16 <= outputs[0] and outputs[-1] == 256          # clipped
    # the median sits between the two middle strata
    assert prompts[7] < 256 < prompts[8] and outputs[7] < 96 < outputs[8]
    assert max(prompts) + max(outputs) <= 1792 < t["engine"]["max_len"]


@pytest.mark.parametrize("dist,want", [
    ({"dist": "uniform", "min": 1024, "max": 1792}, [1120, 1312, 1504, 1696]),
    ({"dist": "fixed", "value": 40}, [40, 40, 40, 40]),
])
def test_other_distributions_are_data_too(dist, want):
    assert tf.stratum_lengths(dist, 4) == want
    with pytest.raises(ValueError):
        tf.stratum_lengths({"dist": "zipf"}, 4)


def test_every_seed_sends_the_same_sizes_in_another_order():
    t = _traffic("chat-closed")

    def block(seed, k):
        stream = tf.request_stream(t, 32768, seed)
        specs = [next(stream) for _ in range(16 * (k + 1))][16 * k:]
        return specs

    a, b, a2 = block(1, 0), block(3000000019, 0), block(1, 1)
    size = lambda specs: (sorted(len(s.prompt) for s in specs),
                          sorted(s.max_new_tokens for s in specs))
    assert size(a) == size(b) == size(a2)
    assert [len(s.prompt) for s in a] != [len(s.prompt) for s in b]
    assert [s.index for s in a2] == list(range(16, 32))
    # the same seed gives the same requests
    assert [s.prompt for s in block(1, 0)] == [s.prompt for s in a]
    assert all(0 <= tok < 32768 for s in a for tok in s.prompt)


def test_percentile_is_nearest_rank_and_spread_is_iqr_over_median():
    xs = list(range(1, 101))
    assert percentile(xs, 0.90) == 90 and percentile(xs, 0.95) == 95
    assert percentile([5.0], 0.9) == 5.0
    assert percentile([], 0.5) != percentile([], 0.5)        # NaN
    with pytest.raises(ValueError):
        percentile(xs, 1.5)
    assert iqr_share([10, 10, 10, 10, 10, 10]) == 0
    assert iqr_share([9, 10, 10, 10, 10, 11]) == pytest.approx(0.05)


@pytest.fixture(scope="module")
def toy_engine():
    import jax
    import jax.numpy as jnp

    from apex_tpu import serving as sv
    from apex_tpu.models import LlamaConfig, LlamaForCausalLM

    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=1, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return sv.DecodeEngine(model, params, slots=16, max_len=64,
                           prefill_len=16)


TOY = {"strata": 16,
       "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.6,
                      "min": 2, "max": 40},
       "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                      "min": 2, "max": 16}}


@pytest.fixture(scope="module")
def loop(toy_engine):
    from apex_tpu import serving as sv

    clk = sv.VirtualClock()
    sched = sv.ContinuousBatchingScheduler(toy_engine, clock=clk)
    in_flight, opened = [], []

    class Watched:
        """The scheduler, with the number in flight read at every step."""

        def __getattr__(self, name):
            return getattr(sched, name)

        def step(self):
            in_flight.append(sched.queue_depth + sched.active_count)
            return sched.step()

    rec = tf.run_closed_loop(
        Watched(), tf.request_stream(TOY, 128, seed=7), clients=16,
        clock=clk, window_s=10.0,
        make_request=lambda rid, s: sv.Request(rid, s.prompt,
                                               s.max_new_tokens),
        on_open=lambda: opened.append(clk()),
        tick=lambda: clk.advance(0.25))
    sched.close()
    return rec, in_flight, opened, clk


def test_sixteen_requests_stay_in_flight_until_the_window_closes(loop):
    rec, in_flight, opened, _ = loop
    steps_open = [i for i, (t, _, _) in enumerate(rec.steps)
                  if t <= rec.t_close]
    assert all(in_flight[i] == 16 for i in steps_open)
    assert in_flight[-1] < 16                                # the drain
    assert opened == [rec.t_open]


def test_window_opens_once_every_client_finished_a_request(loop):
    rec, _, _, _ = loop
    first_done = {}
    for r in rec.served:
        first_done.setdefault(r.client, r.t_done)
    assert len(first_done) == 16
    assert rec.t_open == max(first_done.values())
    assert rec.t_close - rec.t_open == 10.0                  # 40 steps of 0.25
    # nothing is submitted after the window closed
    assert all(r.t_submit <= rec.t_close for r in rec.served)
    assert any(r.t_done > rec.t_close for r in rec.served)


def test_gaps_number_tokens_minus_one_and_sit_on_step_ends(loop):
    rec, _, _, _ = loop
    for r in rec.served:
        assert len(r.result.tokens) == r.spec.max_new_tokens
        assert len(r.gaps()) == len(r.result.tokens) - 1
        times = r.token_times()
        assert times[0] == r.t_submit + r.result.ttft_s
        assert times == sorted(times) and times[-1] == r.t_done
        # on the virtual clock every gap is a whole number of steps, and a
        # first and second token of one step are 0 apart
        assert all(g % 0.25 == 0 for g in r.gaps())


def test_step_counts_add_up_to_the_tokens_served(loop):
    rec, _, _, _ = loop
    assert sum(n for _, n, _ in rec.steps) == \
        sum(len(r.result.tokens) for r in rec.served)
    assert sum(d for _, _, d in rec.steps) == \
        sum(len(r.result.tokens) - 1 for r in rec.served)
    assert all(d <= 16 for _, _, d in rec.steps)


def test_each_block_of_sixteen_requests_holds_each_stratum_once(loop):
    rec, _, _, _ = loop
    by_index = sorted(rec.served, key=lambda r: r.spec.index)
    want_p = collections.Counter(tf.stratum_lengths(TOY["prompt_len"], 16))
    want_o = collections.Counter(tf.stratum_lengths(TOY["output_len"], 16))
    whole_blocks = len(by_index) // 16
    assert whole_blocks >= 2
    for k in range(whole_blocks):
        block = by_index[16 * k:16 * (k + 1)]
        assert [r.spec.index for r in block] == list(range(16 * k,
                                                           16 * k + 16))
        assert collections.Counter(len(r.spec.prompt)
                                   for r in block) == want_p
        assert collections.Counter(r.spec.max_new_tokens
                                   for r in block) == want_o
