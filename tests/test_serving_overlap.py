"""Decode-ahead (ISSUE 36): a step enqueues all of its device work, the next
decode included, and then waits for the device once, for what was enqueued
BEFORE that decode.  Sampled tokens stay on the device (the engine's kept
vector feeds the next step), a token reaches the caller one step after the
step that computed it, and everything that needs a stream's newest token on
the host settles first.

The law these tests pin: whatever the order of reads, no token of any
stream changes.  The reference is the same drain with every step settled
(``_settle`` straight after each ``step``: the serial scheduler this one
replaced), and for a greedy stream the engine driven by hand.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import serving as sv
from apex_tpu.models import LlamaConfig, LlamaForCausalLM
from apex_tpu.obs import trace

CFG = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, max_position_embeddings=256)
MAX = 96


@pytest.fixture(scope="module")
def _engine_mod():
    model = LlamaForCausalLM(CFG)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    # prefill_len 16: the 20- and 40-token prompts take several chunks
    return sv.DecodeEngine(model, params, slots=3, max_len=MAX,
                           prefill_len=16)


@pytest.fixture
def engine(_engine_mod):
    _engine_mod.reset()
    return _engine_mod


# (prompt tokens, max_new_tokens, temperature): greedy and sampled, budgets
# of one and two tokens, prompts of one chunk and of several; seven
# requests on three slots, so slots are reused while others decode
MIX = [(5, 1, 0.0), (7, 2, 0.0), (20, 9, 0.0), (40, 12, 0.8),
       (3, 6, 1.0), (17, 10, 0.0), (9, 8, 0.7)]


def _requests(eos=None, mix=MIX, **kw):
    """``eos``: ``{rid: eos_id}``."""
    rng = np.random.default_rng(3)
    return [sv.Request(f"r{i}", rng.integers(0, 128, n).tolist(), new,
                       temperature=t, top_k=5 if t else 0, seed=100 + i,
                       eos_id=(eos or {}).get(f"r{i}"), **kw)
            for i, (n, new, t) in enumerate(mix)]


def _drain(engine, requests, *, settled=False, hook=None, **kw):
    """Step until nothing is queued or active; ``settled`` reads everything
    in flight after every step; ``hook(step index, sched)`` runs between
    steps.  Returns ``({rid: (tokens, reason)}, sched, steps)``."""
    sched = sv.ContinuousBatchingScheduler(engine, max_queue=16, **kw)
    for r in requests:
        sched.submit(r)
    steps = 0
    while sched.queue_depth or sched.active_count or sched.suspended_count:
        sched.step()
        if settled:
            sched._settle("test")
        if hook is not None:
            hook(steps, sched)
        steps += 1
        assert steps < 400
    out = {rid: (r.tokens, r.finish_reason)
           for rid, r in sched.results.items()}
    return out, sched, steps


def _mid_stream_eos(stream):
    """A token of ``stream`` that first occurs past its second position and
    before its last: an EOS there ends the stream while it decodes."""
    return next(t for i, t in enumerate(stream)
                if 2 <= i < len(stream) - 1 and t not in stream[:i])


@pytest.fixture(scope="module")
def plain(_engine_mod):
    """The mixed drain with every step settled: the reference streams."""
    _engine_mod.reset()
    out, sched, _ = _drain(_engine_mod, _requests(), settled=True)
    sched.close()
    return out


# ---------------------------------------------------------------------------
# no token changes
# ---------------------------------------------------------------------------


def test_mixed_drain_equals_the_drain_with_every_step_settled(engine, plain):
    out, sched, _ = _drain(engine, _requests())
    assert out == plain
    assert {rid: len(t) for rid, (t, _) in out.items()} == {
        f"r{i}": new for i, (_, new, _) in enumerate(MIX)}
    assert {reason for _, reason in out.values()} == {"length"}
    stats = sched.overlap_stats()
    # every decode but the first was enqueued with the previous one unread
    assert stats["steps_ahead"] == stats["steps"] - 1 > 10
    assert stats["settled_early"] == {} and stats["dropped_tokens"] == 0
    assert engine.decode_compiles() == 1
    sched.close()


def test_an_eos_in_mid_stream_drops_the_one_lane_in_flight(engine, plain):
    eos = {rid: _mid_stream_eos(plain[rid][0]) for rid in ("r2", "r5")}
    want, ref, _ = _drain(engine, _requests(eos), settled=True)
    ref.close()
    engine.reset()
    out, sched, _ = _drain(engine, _requests(eos))
    assert out == want
    for rid, tok in eos.items():
        tokens, reason = out[rid]
        full = plain[rid][0]
        assert reason == "eos" and tokens == full[:full.index(tok) + 1]
    # untouched neighbours, and exactly the two lanes that were computed
    # for a stream whose EOS the host had not seen yet
    assert all(out[rid] == plain[rid] for rid in out if rid not in eos)
    assert sched.overlap_stats()["dropped_tokens"] == 2
    assert ref.overlap_stats()["dropped_tokens"] == 0
    sched.close()
    assert not engine.lengths().any()


def test_a_first_token_that_is_eos_ends_the_request_in_its_step(engine,
                                                                plain):
    first = plain["r2"][0][0]
    sched = sv.ContinuousBatchingScheduler(engine)
    req, = _requests({"r2": first}, mix=MIX)[2:3]
    sched.submit(req)
    done = []
    while not done:                      # two chunks: the second step
        done = sched.step()
    assert done == ["r2"] and sched.steps_run == 2
    assert sched.results["r2"].tokens == [first]
    assert sched.results["r2"].finish_reason == "eos"
    # the lane had joined that step's decode: dropped at close
    assert sched.overlap_stats()["dropped_tokens"] == 0
    sched.close()
    assert sched.overlap_stats()["dropped_tokens"] == 1
    assert sched.overlap_stats()["settled_early"] == {"close": 1}


def test_greedy_stream_equals_the_engine_driven_by_hand(engine, plain):
    """The host-fed caller (``engine.decode(tokens, active)``, what the
    benchmark's reference check does) through the same one program."""
    req = _requests()[5]
    logits = engine.prefill(1, req.prompt)
    stream = [int(jnp.argmax(logits))]
    active = np.zeros((engine.slots,), bool)
    active[1] = True
    while len(stream) < req.max_new_tokens:
        tokens = np.zeros((engine.slots,), np.int32)
        tokens[1] = stream[-1]
        stream.append(int(jnp.argmax(engine.decode(tokens, active)[1])))
    assert stream == plain["r5"][0]
    assert engine.decode_compiles() == 1


def test_a_token_is_delivered_one_step_after_the_step_that_computed_it(
        engine):
    sched = sv.ContinuousBatchingScheduler(engine)
    sched.submit(sv.Request("a", [1, 2, 3], 4))
    seen, finished = [], []
    for _ in range(4):
        finished.append(sched.step())
        seen.append(sched.progress_of("a"))
    # step 1 reads the first token while decode 1 runs; step k reads
    # decode k-1; the request is reported by the step that reads its last
    assert seen == [1, 2, 3, 4]
    assert finished == [[], [], [], ["a"]]
    assert sched.overlap_stats() == {
        "steps": 3, "steps_ahead": 2, "settled_early": {},
        "dropped_tokens": 0}
    sched.close()


@pytest.mark.parametrize("new_tokens, steps", [(1, 1), (2, 2), (3, 3)])
def test_small_budgets_finish_in_as_many_steps_as_tokens(engine, new_tokens,
                                                         steps):
    sched = sv.ContinuousBatchingScheduler(engine)
    sched.submit(sv.Request("a", [4, 5, 6, 7], new_tokens))
    sched.run()
    assert sched.steps_run == steps
    assert len(sched.results["a"].tokens) == new_tokens
    # a lane is not issued past its budget: nothing left on the device
    assert sched.overlap_stats()["dropped_tokens"] == 0
    assert sched.overlap_stats()["settled_early"] == {}
    sched.close()


# ---------------------------------------------------------------------------
# the settle points, each with a decode in flight
# ---------------------------------------------------------------------------


def _in_flight(sched) -> bool:
    return any(not e.first for e in sched._flight)


def test_cancel_with_a_decode_in_flight_keeps_what_was_computed(engine,
                                                                plain):
    seen = {}

    def hook(step, sched):
        if step == 4:
            assert _in_flight(sched)
            seen["read"] = sched.progress_of("r2")
            assert sched.cancel("r2") is True
            seen.update(sched.overlap_stats()["settled_early"])

    out, sched, _ = _drain(engine, _requests(), hook=hook)
    # the host had read some of r2's tokens and the device held one more:
    # the partial output is all of them, as if every step had been settled
    assert seen["cancel"] == 1 and 1 <= seen["read"] < 8
    assert out["r2"] == (plain["r2"][0][:seen["read"] + 1], "cancelled")
    assert all(out[rid] == plain[rid] for rid in out if rid != "r2")
    sched.close()


def test_cancel_is_too_late_for_a_stream_whose_last_token_was_in_flight(
        engine):
    sched = sv.ContinuousBatchingScheduler(engine)
    sched.submit(sv.Request("a", [1, 2, 3], 3))
    sched.submit(sv.Request("b", [4, 5, 6], 6))
    assert sched.step() == [] and sched.step() == []
    assert sched.progress_of("a") == 2 and _in_flight(sched)
    assert sched.cancel("a") is False          # finished by the settle
    assert sched.results["a"].finish_reason == "length"
    # the next step reports what the settle point finished
    assert sched.step() == ["a"]
    sched.run()
    sched.close()


def test_preemption_settles_first_and_the_victim_resumes_its_stream(
        engine, plain):
    reqs = _requests(mix=MIX[2:4] + MIX[5:6])   # three long ones, r0..r2
    alone, ref, _ = _drain(engine, reqs, settled=True)
    ref.close()
    engine.reset()
    hi = sv.Request("hi", [9, 8, 7, 6], 3, priority=9)

    def hook(step, sched):
        if step == 5:
            assert sched.active_count == 3 and _in_flight(sched)
            sched.submit(hi)

    out, sched, _ = _drain(engine, reqs, hook=hook,
                           policy=sv.SchedulingPolicy())
    assert sched.overlap_stats()["settled_early"] == {"preempt": 1}
    assert sched.control_stats["preempted"] == 1
    victim, = [rid for rid, (_, why) in out.items()
               if why == "preempted-resumed"]
    assert {rid: toks for rid, (toks, _) in out.items() if rid != "hi"} == {
        rid: toks for rid, (toks, _) in alone.items()}
    assert out[victim][0] == alone[victim][0] and len(out["hi"][0]) == 3
    sched.close()


def test_export_and_adopt_with_decodes_in_flight_on_both_sides(engine,
                                                               plain):
    donor = sv.ContinuousBatchingScheduler(engine, max_queue=16)
    for r in _requests()[2:4]:                    # r2 greedy, r3 sampled
        donor.submit(r)
    for _ in range(6):
        donor.step()
    assert _in_flight(donor)
    read = [donor.progress_of(rid) for rid in ("r2", "r3")]
    exports = donor.export_streams()
    assert donor.overlap_stats()["settled_early"] == {"export": 1}
    # each moves with the token the device still held, behind those read
    assert [len(e.tokens) for e in exports] == [n + 1 for n in read]
    assert all(e.kv is not None for e in exports)
    donor.close()

    adopter = sv.ContinuousBatchingScheduler(engine, max_queue=16)
    adopter.submit(_requests()[5])
    adopter.step(), adopter.step()
    assert _in_flight(adopter)
    for exp in exports:
        assert adopter.adopt_stream(exp) is True
    # adoption reads nothing that is in flight: the adopted lanes are fed
    # from the host beside the lane the device feeds
    assert adopter.overlap_stats()["settled_early"] == {}
    results = adopter.run()
    assert {rid: r.tokens for rid, r in results.items()} == {
        rid: plain[rid][0] for rid in ("r2", "r3", "r5")}
    assert engine.decode_compiles() == 1
    adopter.close()


def test_export_of_a_killed_replica_reads_nothing_and_replays(engine, plain):
    donor = sv.ContinuousBatchingScheduler(engine, max_queue=16)
    for r in _requests()[2:4]:
        donor.submit(r)
    for _ in range(5):
        donor.step()
    assert _in_flight(donor)
    exports = donor.export_streams(capture=False)
    assert donor.overlap_stats()["settled_early"] == {} and not donor._flight
    assert all(e.kv is None and e.tokens == [] for e in exports)
    donor.close()
    adopter = sv.ContinuousBatchingScheduler(engine, max_queue=16)
    for exp in exports:
        adopter.adopt_stream(exp)
    results = adopter.run()
    assert {rid: r.tokens for rid, r in results.items()} == {
        rid: plain[rid][0] for rid in ("r2", "r3")}
    adopter.close()


def test_a_router_reports_what_a_dead_replicas_export_finished(engine):
    """The watchdog's capture-export settles the dead replica: a stream
    whose last token was on its device finishes THERE, and the fleet step
    that failed the replica over reports it (nobody steps it again)."""
    model = LlamaForCausalLM(CFG)
    other = sv.DecodeEngine(model, engine.params, slots=1, max_len=MAX,
                            prefill_len=16)
    clk = sv.VirtualClock()
    router = sv.FleetRouter(
        {"r0": sv.ContinuousBatchingScheduler(engine, clock=clk),
         "r1": sv.ContinuousBatchingScheduler(other, clock=clk)},
        config=sv.FleetConfig(suspect_after_s=1.0, dead_after_s=3.0))
    router.submit(sv.Request("a", [1, 2, 3], 3))
    name = router.placement_of("a")
    for _ in range(2):
        assert router.step() == []
        clk.advance(0.25)
    assert router.replica(name).progress_of("a") == 2   # the third unread
    router.wedge(name)
    clk.advance(5.0)
    assert router.step() == ["a"]
    assert router.state_of(name) is sv.ReplicaState.DEAD
    result = router.pop_result("a")
    assert result.finish_reason == "length" and len(result.tokens) == 3
    assert router.fleet_stats["failovers"] == 0      # nothing had to move


def test_swap_weights_with_a_decode_in_flight_changes_no_stream(engine,
                                                                plain):
    def hook(step, sched):
        if step == 6:
            assert _in_flight(sched)
            sched.swap_weights(engine.params, step=7)

    out, sched, _ = _drain(engine, _requests(), hook=hook)
    assert out == plain
    assert sched.overlap_stats()["settled_early"] == {"swap_weights": 1}
    assert engine.decode_compiles() == 1
    sched.close()


def test_close_refuses_live_streams_after_reading_what_is_in_flight(engine):
    sched = sv.ContinuousBatchingScheduler(engine)
    sched.submit(sv.Request("a", [1, 2, 3], 5))
    sched.step(), sched.step()
    with pytest.raises(RuntimeError, match="1 active"):
        sched.close()
    assert sched.overlap_stats()["settled_early"] == {"close": 1}
    assert sched.progress_of("a") == 3 and not sched._flight
    sched.run()
    sched.close()


def test_speculation_settles_every_step_and_emits_the_plain_stream(engine):
    reqs = [sv.Request("rep", [5, 6, 7, 8] * 4, 14),
            sv.Request("rnd", _requests()[2].prompt, 9),
            sv.Request("tmp", _requests()[3].prompt, 8, temperature=0.8,
                       top_k=5, seed=3)]
    want, ref, plain_steps = _drain(engine, reqs)
    ref.close()
    engine.reset()
    with trace.recording() as rec:
        out, sched, steps = _drain(
            engine, reqs, speculation=sv.SpeculationConfig(max_draft=4))
    assert out == want and steps < plain_steps
    stats = sched.overlap_stats()
    assert stats["steps_ahead"] == 0 and stats["dropped_tokens"] == 0
    assert set(stats["settled_early"]) == {"speculation"}
    assert sched.spec_stats["accepted"] > 0
    evs = rec.to_chrome_trace()["traceEvents"]
    assert {e["args"]["lag"] for e in evs
            if e["name"] == "serving.readback"} == {0}
    assert {e["args"]["ahead"] for e in evs
            if e["name"] == "serving.decode"} == {0}
    sched.close()


def test_run_reads_the_lanes_left_in_flight_by_an_eos(engine, plain):
    eos = _mid_stream_eos(plain["r5"][0])
    sched = sv.ContinuousBatchingScheduler(engine)
    sched.submit(_requests({"r5": eos})[5])
    results = sched.run()
    assert results["r5"].finish_reason == "eos" and not sched._flight
    assert sched.overlap_stats()["settled_early"] == {"drain": 1}
    assert sched.overlap_stats()["dropped_tokens"] == 1
    sched.close()


# ---------------------------------------------------------------------------
# nothing waits for the device before the step's one read
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 7, 12345, 2 ** 31 - 1, 2 ** 31,
                                  2 ** 32 - 1, 2 ** 32 + 5, 2 ** 40 + 3,
                                  2 ** 63 - 1, -1, -2 ** 31])
def test_host_key_bits_are_the_device_keys(seed):
    bits = sv.request_key_bits(seed)
    assert bits.dtype == np.uint32 and bits.shape == (2,)
    assert (bits == np.asarray(jax.random.PRNGKey(seed))).all()
    assert (bits == np.asarray(sv.request_key(seed))).all()


@pytest.mark.parametrize("seed", [5, 2 ** 32 + 5, 2 ** 63 - 1, -1])
def test_host_key_bits_in_64_bit_mode(seed):
    with jax.enable_x64(True):
        want = np.asarray(jax.random.PRNGKey(seed))
        assert (sv.request_key_bits(seed) == want).all()


def test_host_key_bits_refuse_what_prngkey_refuses():
    with pytest.raises(OverflowError):
        jax.random.PRNGKey(2 ** 63)
    with pytest.raises(OverflowError):
        sv.request_key_bits(2 ** 63)


class _Guarded:
    """A sampled vector that may be read only under ``serving.readback``."""

    def __init__(self, array, reads):
        self.array, self.reads = array, reads

    def copy_to_host_async(self):
        self.array.copy_to_host_async()

    def __array__(self, *args, **kw):
        span = trace.current_span()
        assert span is not None and span.name == "serving.readback"
        self.reads.append(span.span_id)
        return np.asarray(self.array)


def test_a_step_reads_sampled_tokens_only_under_its_one_readback(
        engine, plain, monkeypatch):
    reads = []
    sample, keep = engine.sample, engine.keep_sampled
    monkeypatch.setattr(engine, "sample",
                        lambda *a: _Guarded(sample(*a), reads))
    monkeypatch.setattr(engine, "keep_sampled",
                        lambda sampled, lanes: keep(sampled.array, lanes))

    def no_key(seed):
        raise AssertionError("admission made its key on the device")

    monkeypatch.setattr(jax.random, "PRNGKey", no_key)
    with trace.recording() as rec:
        out, sched, _ = _drain(engine, _requests())
    assert out == plain and reads
    evs = rec.to_chrome_trace()["traceEvents"]
    steps = [e for e in evs if e["name"] == "serving.step"]
    for step in steps:
        inside = [e for e in evs if e is not step
                  and step["ts"] <= e["ts"]
                  and e["ts"] + e["dur"] <= step["ts"] + step["dur"]]
        waits = [e for e in inside if e["name"] == "serving.readback"]
        assert len(waits) <= 1
        engine_ends = [e["ts"] + e["dur"] for e in inside
                       if e["name"].startswith("engine.")]
        if waits and engine_ends:
            assert waits[0]["ts"] >= max(engine_ends)
    # every read happened inside one of those spans
    assert set(reads) <= {e["args"]["span_id"] for e in evs
                          if e["name"] == "serving.readback"}
    sched.close()


def test_overlap_stats_add_up_with_the_spans(engine):
    with trace.recording() as rec:
        out, sched, _ = _drain(
            engine, _requests(),
            hook=lambda i, s: i == 7 and s.swap_weights(engine.params))
    evs = rec.to_chrome_trace()["traceEvents"]
    decodes = [e["args"] for e in evs if e["name"] == "serving.decode"]
    waits = [e["args"] for e in evs if e["name"] == "serving.readback"]
    stats = sched.overlap_stats()
    assert stats["steps"] == len(decodes) == len(
        [e for e in evs if e["name"] == "engine.decode"])
    assert stats["steps_ahead"] == sum(d["ahead"] for d in decodes)
    # the swap read the decode in flight: the step after it was not ahead
    assert stats["steps_ahead"] == stats["steps"] - 2
    assert stats["settled_early"] == {"swap_weights": 1}
    # a decode read late had a newer one behind it; the settle's had none
    assert sum(w["lag"] for w in waits) == stats["steps_ahead"]
    assert sum("decode" in w["what"] for w in waits) == stats["steps"]
    # every token of every stream was delivered by exactly one read
    assert sum(len(t) for t, _ in out.values()) == sum(
        new for _, new, _ in MIX)
    assert stats["dropped_tokens"] == 0
    sched.close()


def test_mixing_device_fed_and_host_fed_lanes_keeps_one_decode_program(
        engine, plain):
    """A settle between steps leaves every lane host-fed for one step;
    lanes that join from prefill are device-fed beside them."""
    def hook(step, sched):
        if step in (3, 4, 9):
            sched._settle("test")

    out, sched, _ = _drain(engine, _requests(), hook=hook)
    assert out == plain and engine.decode_compiles() == 1
    assert sched.overlap_stats()["settled_early"] == {"test": 3}
    sched.close()
