"""What a serving runner does around its model, as a function: the engine
and the scheduler at their defaults, the warm-up, the loop, the window's
numbers, the checks behind it and the result the harness prints.

``runners/serve.py`` and the runners after it each repeat this body with
their own model and check, and are the yardstick of the cells they run, so
they are not edited; a runner written since calls :func:`serve_cell` with
its model, its weights, its check and the loop that drives it
(:func:`closed_loop` here, ``lib/open_loop.py::drive`` for arrivals that do
not wait for each other).
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import time

import numpy as np

from benchmark.lib import traffic as tf
from benchmark.lib.stats import percentile
from benchmark.runners.serve import SPANS, warm_up
from benchmark.runners.serve_hybrid import moe_counts


class _CutAtClose:
    """The scheduler as ``traffic.run_closed_loop`` sees it where the
    traffic file's ``drain`` is ``"cut"``: from the first step once the
    window has closed, every request still in flight is cancelled instead
    of stepped, and reported finished (``cut``).  Their tokens inside the
    window count as any; after it there is nothing to measure, and a
    request of thousands of tokens would hold the drain for a minute."""

    def __init__(self, sched, closed):
        self._sched, self._closed = sched, closed
        self.cut: list = []

    def __getattr__(self, name):
        return getattr(self._sched, name)

    def step(self):
        if not self._closed():
            return self._sched.step()
        # cancel() is False for a stream whose last token was on the
        # device: the settle finished it, and pop_finished reports it
        cut = [rid for rid in self._sched.active_rids
               + self._sched.queued_rids if self._sched.cancel(rid)]
        self.cut.extend(cut)
        return self._sched.pop_finished() + cut


@dataclasses.dataclass(frozen=True)
class InProgress(tf.Spec):
    """A request already ``done`` tokens into its output when the loop
    starts: its prompt is its own prompt and those tokens, its budget what
    is left of its output."""

    done: int = 0


def in_progress(stream, clients: int, vocab: int, seed: int):
    """``stream`` with its first ``clients`` requests turned into the ones
    a closed loop in its steady state has in flight, so that the lanes do
    not start, and end, together.  A lane seen at a random moment is inside
    a request with a chance that grows with the request's length, and is a
    uniform share of the way into it: the first ``clients`` specs keep
    their prompts and take the output lengths at the quantiles ``(c + 0.5)
    / clients`` of their own lengths weighted by length, in a seeded order;
    each is ``done`` tokens (the same quantiles, in another order) into its
    output, and those tokens are seeded ids after its prompt."""
    specs = [next(stream) for _ in range(clients)]
    lengths = np.sort([s.max_new_tokens for s in specs])
    share = (np.arange(clients) + 0.5) / clients
    rng = np.random.default_rng([int(seed), clients])
    totals = lengths[np.searchsorted(np.cumsum(lengths) / lengths.sum(),
                                     share)][rng.permutation(clients)]
    for spec, total, into in zip(specs, totals,
                                 share[rng.permutation(clients)]):
        done = int(into * total)
        yield InProgress(spec.index, spec.prompt + rng.integers(
            0, vocab, done).tolist(), int(total) - done, done)
    yield from stream


def run_started_closed_loop(sched, stream, *, clients: int, clock,
                            window_s: float, make_request, on_open=None,
                            on_step=None,
                            span=lambda name: contextlib.nullcontext()):
    """``traffic.run_closed_loop`` with another ramp: the window opens once
    every client has a token, not once every client has finished a
    request.  For a ``stream`` whose first ``clients`` requests are
    :func:`in_progress`, whose lanes are staggered from the start: the
    ramp is their prompts' chunks, not the longest first output."""
    live: dict = {}                      # rid -> Served
    counts: dict = {}                    # rid -> tokens seen so far
    served, steps = [], []
    started = set()
    t_open = t_close = None

    def submit(client: int) -> None:
        spec = next(stream)
        rid = f"c{client}-r{spec.index}"
        with span("submit"):
            t = clock()
            sched.submit(make_request(rid, spec))
        live[rid] = tf.Served(spec, rid, client, t)
        counts[rid] = 0

    for c in range(clients):
        submit(c)
    while live:
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
                started.add(rec.client)
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
            if t_close is None:
                submit(rec.client)
        if t_open is None and len(started) == clients:
            if on_open is not None:
                on_open()
            t_open = clock()
        if on_step is not None:
            on_step(now)
    return tf.LoopRecord(served, steps, t_open, t_close)


def closed_loop(ctx, sched, make_request, on_open, tracer):
    """``traffic.run_closed_loop`` with the traffic file's ``clients``:
    ``(LoopRecord, counters, notes)``.  With ``drain: "cut"`` the requests
    in flight when the window closes are cancelled and left out of the
    record (notes: ``cut_at_close``) rather than drained.  With ``ramp:
    "in_progress"`` the first requests are :func:`in_progress` and the
    window opens once every lane has a token
    (:func:`run_started_closed_loop`); then the first tokens timed are
    those of the requests that arrived in the window, cut or not (counter
    ``ttft_p90_ms``, notes ``ttft_ms``): an in-progress request's prompt
    holds its earlier output, and one that arrived in the ramp waited
    behind the 64 first prompts' chunks - no steady loop's wait."""
    opened = []
    traffic, vocab = ctx.traffic, ctx.config["vocab_size"]
    warm = traffic.get("ramp", "first_request") == "in_progress"

    def on_open_stamped():
        on_open()
        opened.append(time.perf_counter())

    if traffic.get("drain", "whole") == "cut":
        sched = _CutAtClose(sched, lambda: bool(opened) and (
            time.perf_counter() - opened[0] >= ctx.seconds))
    stream = tf.request_stream(traffic, vocab, ctx.seed)
    loop = tf.run_closed_loop
    if warm:
        stream = in_progress(stream, traffic["clients"], vocab, ctx.seed)
        loop = run_started_closed_loop
    rec = loop(
        sched, stream, clients=traffic["clients"], clock=time.perf_counter,
        window_s=ctx.seconds, make_request=make_request,
        on_open=on_open_stamped, on_step=tracer.poll, span=tracer.span)
    counters, notes = {}, {}
    if warm:
        ttfts = [r.result.ttft_s for r in rec.served
                 if rec.in_window(r.t_submit)
                 and np.isfinite(r.result.ttft_s)]
        counters["ttft_p90_ms"] = 1e3 * percentile(ttfts, 0.90)
        notes["ttft_ms"] = {"p50": 1e3 * percentile(ttfts, 0.5),
                            "p90": counters["ttft_p90_ms"],
                            "max": 1e3 * max(ttfts, default=float("nan")),
                            "requests": len(ttfts)}
    if isinstance(sched, _CutAtClose):
        cut = set(sched.cut)
        rec.served = [r for r in rec.served if r.rid not in cut]
        notes["cut_at_close"] = len(cut)
    return rec, counters, notes


def serve_cell(ctx, model, params, check, *, drive=closed_loop,
               counters=None) -> dict:
    """One run of a serving cell on ``model`` with ``params``: the result
    ``benchmark/run.py`` turns into its line.  ``check(engine)`` is the
    family's reference check, made after the drain on the timed engine (a
    dict whose booleans must all hold); ``drive(ctx, sched, make_request,
    on_open, tracer)`` runs the loop and hands back ``(LoopRecord,
    counters, notes)``; ``counters`` are constants the reducers read beside
    the run's (the experts held, under the name they divide by).

    Counters: ``runners/serve_mhc.py``'s - the expert layers' ``moe_*`` and
    ``engine.rows_read()`` after the drain less after the warm-up,
    ``itl_p95_ms``, ``batch_occupancy``, ``ttft_p90_ms``, ``steps`` - and the
    loop's own."""
    from apex_tpu import serving as sv

    config, traffic = ctx.config, ctx.traffic
    vocab = config["vocab_size"]
    engine = sv.DecodeEngine(model, params, **traffic["engine"])
    sched = sv.ContinuousBatchingScheduler(engine, clock=time.perf_counter)
    ctx.setup.mark("engine")

    def make_request(rid, spec):
        return sv.Request(rid, spec.prompt, spec.max_new_tokens)

    warm_up(sched, engine, make_request)
    counts_before = {**moe_counts(engine), **engine.rows_read()}
    ctx.setup.mark("warmup_trace_compile_or_cache_load")
    compiles_before = (engine.decode_compiles(), engine.prefill_compiles())

    tracer = ctx.tracer(SPANS)

    def on_open():
        ctx.setup.mark("ramp")
        ctx.setup.window_opens()
        tracer.window_opens(time.perf_counter())
        ctx.compiles.active = True

    rec, loop_counters, loop_notes = drive(ctx, sched, make_request, on_open,
                                           tracer)
    ctx.compiles.active = False
    tracer.stop()
    device = ctx.device_report()

    window_steps = [n for t, n, _ in rec.steps if rec.in_window(t)]
    decode_lanes = [d for t, _, d in rec.steps if rec.in_window(t)]
    window_s = rec.t_close - rec.t_open
    done_in = [r for r in rec.served if rec.in_window(r.t_done)]
    whole = [r for r in done_in if rec.in_window(r.t_submit)]
    # a request may take longer than the window (and one in flight at its
    # close may be cut): then the ones that finished in it are what is
    # judged
    attempted = ([r for r in rec.served if rec.in_window(r.t_submit)]
                 or done_in)

    def failed(r) -> bool:
        res = r.result
        return (res.finish_reason not in sv.SERVED_REASONS
                or len(res.tokens) != r.spec.max_new_tokens
                or not all(0 <= t < vocab for t in res.tokens))

    n_failed = sum(failed(r) for r in attempted)
    ttfts = ([r.result.ttft_s for r in whole]
             or [r.result.ttft_s for r in done_in])
    times = [t for t, _, _ in rec.steps if rec.in_window(t)]
    step_ms = [1e3 * (b - a) for a, b in zip(times, times[1:])]
    # the gaps that end in the window: a request that finished in it may
    # have begun in the ramp's prompt chunks
    gaps = [b - a for r in done_in
            for a, b in itertools.pairwise(r.token_times())
            if rec.in_window(b)]
    checks = {
        "decode_compiles_is_1": engine.decode_compiles() == 1,
        "prefill_compiles_within_buckets":
            engine.prefill_compiles() <= len(engine.prefill_buckets),
        "no_compile_in_window": (
            not ctx.compiles.events
            and (engine.decode_compiles(), engine.prefill_compiles())
            == compiles_before),
        "no_request_failed": n_failed == 0,
        "gaps_match_tokens": all(
            len(r.gaps()) == len(r.result.tokens) - 1 for r in rec.served),
    }
    ctx.setup.mark("window_and_drain")
    # before the check, whose own decode steps are not the traffic's
    counts = {k: v - counts_before.get(k, 0)
              for k, v in {**moe_counts(engine), **engine.rows_read()}.items()}
    counts.update(counters or {})
    checks.update(check(engine))
    sched.close()
    ctx.setup.mark("checks")
    ok = all(v for v in checks.values() if isinstance(v, bool))
    itl_p95_ms = 1e3 * percentile(gaps, 0.95)
    return {
        "correct": ok, "attempted": len(attempted), "failed": n_failed,
        "end_to_end": {
            "serve_tok_s": sum(window_steps) / window_s,
            "itl_p95_ms": itl_p95_ms},
        "counters": {
            # recorded as the per-layer itl_p95_ms.serve_tok_s, not judged
            "itl_p95_ms": itl_p95_ms,
            "batch_occupancy": float(np.mean(decode_lanes)) / engine.slots,
            "ttft_p90_ms": 1e3 * percentile(ttfts, 0.90),
            "steps": len(window_steps), **counts, **loop_counters},
        "tracer": tracer, "device": device,
        "notes": {
            "checks": checks, "window_s": window_s,
            "steps_in_window": len(window_steps),
            "requests_finished_in_window": len(done_in),
            "requests_whole_in_window": len(whole),
            "gaps": len(gaps), "tokens_in_window": sum(window_steps),
            "ttft_ms": {"p50": 1e3 * percentile(ttfts, 0.5),
                        "p90": 1e3 * percentile(ttfts, 0.9),
                        "mean": 1e3 * float(np.mean(ttfts)),
                        "max": 1e3 * max(ttfts)},
            "host_step_ms": {"median": float(np.median(step_ms)),
                             "max": max(step_ms)},
            "itl_p50_ms": 1e3 * percentile(gaps, 0.5),
            "itl_p95_ms": itl_p95_ms,
            "prefill_buckets": list(engine.prefill_buckets),
            "prefill_compiles": engine.prefill_compiles(),
            "compiles_in_window": ctx.compiles.events,
            # an untraced line prints no counters
            "counters": {**counts, **loop_counters}, **loop_notes},
    }
