"""An open loop: requests arrive at a rate fixed in the traffic file,
whether or not earlier ones have finished - a chat service's independent
users - against anything with the scheduler's ``submit / step / progress_of
/ pop_result`` surface.

Gaps between arrivals are exponential at ``rate_per_s`` and stratified as
``lib/traffic.py`` stratifies lengths: every consecutive block of
``strata`` arrivals holds the same ``strata`` gaps (the mid-quantile of each
``1/strata`` slice of the distribution, scaled to the rate's mean) in an
order drawn from the seed, so a block always spans the same time and a
window's arrival count barely depends on the seed.  The lengths are
``lib/traffic.py``'s request stream.

The window opens ``ramp_s`` seconds after the first arrival and lasts the
run's ``--seconds``; then arrivals stop and what is in flight drains.  The
generator is one thread with the scheduler: an arrival falls due while a
step runs and is submitted after it (or, when the scheduler's queue is full,
after the step that makes room), so each arrival's *lateness* - its submit
less its scheduled time - is recorded and reported with the run, and what
the run times from an arrival (the wait before its first prompt chunk, its
first token) is timed from its scheduled time, lateness included: a user's
request is due when it is sent, not when the loop gets to it.
"""

from __future__ import annotations

import contextlib
import math
import time

import numpy as np

from benchmark.lib import traffic as tf
from benchmark.lib.stats import percentile


def stratum_gaps(rate_per_s: float, strata: int) -> list:
    """The ``strata`` gaps a block holds, ascending: quantile ``(i + 0.5) /
    strata`` of the exponential distribution, scaled so that their mean is
    ``1 / rate_per_s`` - a block spans ``strata / rate_per_s`` seconds, the
    rate is the one offered (the mid-quantiles alone fall 2.2 % short at 16
    strata: the tail above the last one is long)."""
    q = [-math.log(1.0 - (i + 0.5) / strata) for i in range(strata)]
    return [x * strata / sum(q) / rate_per_s for x in q]


def arrival_gaps(traffic: dict, seed: int):
    """Endless generator of the gaps between arrivals, in seconds."""
    strata = int(traffic["strata"])
    gaps = stratum_gaps(float(traffic["rate_per_s"]), strata)
    # not the lengths' generator, which default_rng(seed) drives
    rng = np.random.default_rng([int(seed), strata])
    while True:
        for j in rng.permutation(strata):
            yield gaps[j]


class FirstChunks:
    """An event sink that stamps, by the scheduler's clock, when each
    request's first prompt chunk was scheduled: the ``serving_prefill_chunk``
    event at offset 0, less the enqueue time it carries."""

    def __init__(self, clock):
        self.clock = clock
        self.at: dict = {}

    def __call__(self, event: dict) -> None:
        if (event.get("event") == "serving_prefill_chunk"
                and event.get("offset_tokens") == 0):
            self.at.setdefault(event["rid"],
                               self.clock() - event.get("duration_s", 0.0))


def run_open_loop(sched, stream, gaps, *, clock, ramp_s: float,
                  window_s: float, make_request, on_open=None, on_step=None,
                  span=lambda name: contextlib.nullcontext(),
                  sleep=time.sleep):
    """Submit ``stream``'s requests at the times ``gaps`` give, stepping the
    scheduler while anything is in flight and sleeping until the next
    arrival while nothing is.  ``on_open()`` at ``ramp_s`` seconds after the
    first arrival; the window then lasts ``window_s``.  Returns
    ``(LoopRecord, lateness)``, ``lateness`` ``{rid: (scheduled,
    late_s)}`` of every arrival."""
    live: dict = {}                      # rid -> Served
    counts: dict = {}                    # rid -> tokens seen so far
    served, steps, lateness = [], [], {}
    t_first = clock()
    due = t_first
    t_open = t_close = None
    while True:
        now = clock()
        # an arrival that finds the queue full waits at its client for the
        # next step (a traced run's profiler start stalls the host for
        # seconds); its lateness holds the wait
        while (t_close is None and due <= now
               and sched.queue_depth < sched.max_queue):
            spec = next(stream)
            rid = f"a{spec.index}"
            with span("submit"):
                t = clock()
                sched.submit(make_request(rid, spec))
            live[rid] = tf.Served(spec, rid, spec.index, t)
            counts[rid] = 0
            lateness[rid] = (due, t - due)
            due += next(gaps)
        if t_open is None and now - t_first >= ramp_s:
            if on_open is not None:
                on_open()
            t_open = clock()
        elif (t_open is not None and t_close is None
              and now - t_open >= window_s):
            t_close = now
        if not live:
            if t_close is not None:
                break
            sleep(max(0.0, due - clock()))
            continue
        with span("sched_step"):
            finished = sched.step()
        now = clock()
        emitted = decoded = 0
        for rid, rec in live.items():
            n = sched.progress_of(rid)
            seen = counts[rid]
            if n > seen:
                emitted += n - seen
                from_decode = n - max(seen, 1)
                decoded += from_decode
                rec.stamps.extend([now] * from_decode)
                counts[rid] = n
        steps.append((now, emitted, decoded))
        if (t_open is not None and t_close is None
                and now - t_open >= window_s):
            t_close = now
        for rid in finished:
            rec = live.pop(rid)
            counts.pop(rid)
            rec.result = sched.pop_result(rid)
            rec.t_done = now
            served.append(rec)
        if on_step is not None:
            on_step(now)
    return tf.LoopRecord(served, steps, t_open, t_close), lateness


def drive(ctx, sched, make_request, on_open, tracer):
    """``lib/serving.py``'s loop for a ``loop: open`` traffic file:
    :func:`run_open_loop` at ``rate_per_s`` after ``ramp_s``, and beside
    the window's numbers the counters ``queue_wait_p90_ms`` - the 90th
    percentile (nearest rank) of an arrival's scheduled time to its first
    scheduled prompt chunk over the arrivals due in the window - and
    ``ttft_from_arrival_p90_ms`` - the same from the scheduled time to the
    first token (lateness + the scheduler's ``ttft_s``, which it times
    from submit) -, and the generator's lateness over the window's
    arrivals (notes: ``lateness_ms`` p50 / p99 / max,
    ``arrivals_in_window``, ``offered_tok_s``)."""
    from apex_tpu._logging import add_event_sink, remove_event_sink

    traffic = ctx.traffic
    clock = time.perf_counter
    chunks = FirstChunks(clock)
    add_event_sink(chunks)
    try:
        rec, lateness = run_open_loop(
            sched, tf.request_stream(traffic, ctx.config["vocab_size"],
                                     ctx.seed),
            arrival_gaps(traffic, ctx.seed), clock=clock,
            ramp_s=traffic["ramp_s"], window_s=ctx.seconds,
            make_request=make_request, on_open=on_open,
            on_step=tracer.poll, span=tracer.span)
    finally:
        remove_event_sink(chunks)
    due = [(r, *lateness[r.rid]) for r in rec.served
           if rec.in_window(lateness[r.rid][0])]
    waits = [chunks.at[r.rid] - t for r, t, _ in due if r.rid in chunks.at]
    ttfts = [s + r.result.ttft_s for r, _, s in due]
    late = [s for t, s in lateness.values() if rec.in_window(t)]
    outputs = tf.stratum_lengths(traffic["output_len"], traffic["strata"])
    counters = {"queue_wait_p90_ms": 1e3 * percentile(waits, 0.90),
                "ttft_from_arrival_p90_ms": 1e3 * percentile(ttfts, 0.90)} \
        if waits else {}
    return rec, counters, {
        "lateness_ms": {"p50": 1e3 * percentile(late, 0.5),
                        "p99": 1e3 * percentile(late, 0.99),
                        "max": 1e3 * max(late, default=float("nan"))},
        "arrivals_in_window": len(late),
        "queue_wait_ms": {"p50": 1e3 * percentile(waits, 0.5),
                          "p90": 1e3 * percentile(waits, 0.9)},
        "offered_tok_s": traffic["rate_per_s"] * float(np.mean(outputs))}
