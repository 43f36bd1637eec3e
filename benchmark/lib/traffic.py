"""The one general traffic generator: reads a traffic file's parameters,
makes requests from ``--seed``, and drives a closed loop of clients
against anything with the scheduler's ``submit / step / progress_of /
pop_result`` surface.

Lengths are stratified: every consecutive block of ``strata`` requests
holds the same ``strata`` prompt lengths and the same ``strata`` output
lengths (the mid-quantile of each ``1/strata`` slice of the distribution),
paired and ordered by the seed.  Every seed therefore sends the same set
of sizes in another order, so a window's token total barely depends on
the seed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import statistics

import numpy as np


def stratum_lengths(spec: dict, strata: int) -> list:
    """The ``strata`` lengths a block holds, ascending: quantile
    ``(i + 0.5) / strata`` of the distribution, clipped to [min, max]."""
    out = []
    for i in range(strata):
        q = (i + 0.5) / strata
        if spec["dist"] == "lognormal":
            x = spec["median"] * math.exp(
                spec["sigma"] * statistics.NormalDist().inv_cdf(q))
        elif spec["dist"] == "uniform":
            x = spec["min"] + (spec["max"] - spec["min"]) * q
        elif spec["dist"] == "fixed":
            x = spec["value"]
        else:
            raise ValueError(f"unknown length distribution {spec['dist']!r}")
        lo = spec.get("min", 1)
        hi = spec.get("max", x)
        out.append(int(min(max(round(x), lo), hi)))
    return out


@dataclasses.dataclass(frozen=True)
class Spec:
    """One generated request: prompt token ids and the output budget."""

    index: int
    prompt: list
    max_new_tokens: int


def request_stream(traffic: dict, vocab: int, seed: int):
    """Endless generator of :class:`Spec` from the traffic parameters."""
    strata = int(traffic["strata"])
    prompts = stratum_lengths(traffic["prompt_len"], strata)
    outputs = stratum_lengths(traffic["output_len"], strata)
    rng = np.random.default_rng(int(seed))
    index = 0
    while True:
        order_p = rng.permutation(strata)
        order_o = rng.permutation(strata)
        for j in range(strata):
            n = prompts[order_p[j]]
            yield Spec(index, rng.integers(0, vocab, n).tolist(),
                       outputs[order_o[j]])
            index += 1


@dataclasses.dataclass
class Served:
    """What the loop saw of one request."""

    spec: Spec
    rid: str
    client: int
    t_submit: float
    t_done: float = float("nan")
    result: object = None
    # clock reading at which the emitted-token count reached k, k >= 2
    stamps: list = dataclasses.field(default_factory=list)

    def token_times(self) -> list:
        """Token 1 at submit + ttft, token k >= 2 at its step's end."""
        return [self.t_submit + self.result.ttft_s] + self.stamps

    def gaps(self) -> list:
        t = self.token_times()
        return [b - a for a, b in zip(t, t[1:])]


@dataclasses.dataclass
class LoopRecord:
    served: list           # every finished request, in finish order
    steps: list            # (t_end, tokens emitted, of them by decode)
    t_open: float
    t_close: float

    def in_window(self, t: float) -> bool:
        return self.t_open < t <= self.t_close


_NO_SPAN = contextlib.nullcontext


def run_closed_loop(sched, stream, *, clients: int, clock, window_s: float,
                    make_request, on_open=None, on_step=None, tick=None,
                    span=lambda name: _NO_SPAN()) -> LoopRecord:
    """``clients`` callers, each submitting its next request the moment
    its last one finished.  Ramp: until every client has finished one
    request; then ``on_open()`` and the window of ``window_s`` seconds;
    then submission stops and in-flight requests drain.

    ``make_request(rid, spec)`` builds the scheduler's request object;
    ``tick()`` (tests) moves a virtual clock after each step;
    ``on_step(now)`` lets a traced run start and stop its profiler;
    ``span(name)`` wraps host work in a trace annotation."""
    live: dict = {}                      # rid -> Served
    counts: dict = {}                    # rid -> tokens seen so far
    served, steps = [], []
    done_once = set()
    t_open = t_close = None

    def submit(client: int) -> None:
        spec = next(stream)
        rid = f"c{client}-r{spec.index}"
        with span("submit"):
            t = clock()
            sched.submit(make_request(rid, spec))
        live[rid] = Served(spec, rid, client, t)
        counts[rid] = 0

    for c in range(clients):
        submit(c)
    while live:
        with span("sched_step"):
            finished = sched.step()
        if tick is not None:
            tick()
        now = clock()
        emitted = decoded = 0
        for rid, rec in live.items():
            n = sched.progress_of(rid)
            seen = counts[rid]
            if n > seen:
                emitted += n - seen
                # token 1 comes from prefill and is stamped from the
                # result's ttft_s; the others from the shared decode step
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
            done_once.add(rec.client)
            if t_close is None:
                submit(rec.client)
        if t_open is None and len(done_once) == clients:
            if on_open is not None:
                on_open()
            # on_open may take time (it reads device state); the window
            # starts when it returns
            t_open = clock()
        if on_step is not None:
            on_step(now)
    return LoopRecord(served, steps, t_open, t_close)
