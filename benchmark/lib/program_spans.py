"""The program's own spans, read from the profile the harness wrote.

``apex_tpu.obs.trace.span`` is a ``jax.profiler.TraceAnnotation`` while a
profiler session runs, so the serving step's spans (``serving.*`` in the
scheduler, ``engine.*`` in the engine; the table is in PERF.md §3) sit on
the ``/host:CPU`` plane of the same ``.xplane.pb`` as the device's lines,
with their attributes as event stats.  ``lib/trace.py`` keeps only the
benchmark's two spans and drops stats, and the reducers' context carries no
path, so :func:`of` finds the file itself: when the run it reduces was
traced, ``harness.TraceSlice`` made an ``apexbench-trace-*`` directory under
the temporary directory in this process, it is the newest one there (one
run at a time holds the chip), and ``run.py`` removes it only after the
reducers ran.  It is parsed once per process.

The two planes are NOT quite on one clock: on the chip the device's line
reports a program as starting 0.2-0.8 ms *before* the span that enqueues it
even begins (my chip run, PR 25; PERF.md §6).  So a dispatch is paired with
the execution nearest to it, not the next one after it, and the device's
events are read :func:`device_lead_ns` later — the least shift that lets
no program start before the span that dispatched it.

Everything else here works on the plain :class:`ProgramTrace`, so the
arithmetic is tested on hand-made events and on a fixture cut from a chip
run (``fixtures/serve_program_trace.json``, ``tools/cut_span_fixture.py``).
A program without these spans (any commit before they were added), a trace
without a device plane (the CPU rehearsal) and a run without a trace all
read as None, and the metric is left out of the line.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import json
import os
import re
import statistics
import tempfile

from benchmark.lib import trace as tr

PREFIXES = ("serving.", "engine.")
STEP = r"^serving\.step$"
NO_SPAN = "(no span)"           # lib/trace.attribute_gaps's name for it
# (program, the span that enqueues one execution of it)
DISPATCHES = ((r"^jit__decode", r"^engine\.decode$"),
              (r"^jit__prefill", r"^engine\.prefill_chunk$"),
              (r"^jit__verify", r"^engine\.verify_draft$"))


@dataclasses.dataclass
class ProgramTrace:
    """``modules``: ``(name, start_ns, dur_ns)`` of the device's
    ``XLA Modules`` line.  ``spans``: ``(name, start_ns, dur_ns, stats)``
    of the program's host spans, by start."""

    modules: list
    spans: list
    device: str = ""

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, obj: dict) -> "ProgramTrace":
        return cls(modules=[tuple(e) for e in obj["modules"]],
                   spans=[(n, s, d, dict(st)) for n, s, d, st in obj["spans"]],
                   device=obj.get("device", ""))


def load_fixture(path: str) -> ProgramTrace:
    with open(path) as f:
        return ProgramTrace.from_json(json.load(f))


def find_trace_dir() -> str | None:
    dirs = [d for d in glob.glob(os.path.join(tempfile.gettempdir(),
                                              "apexbench-trace-*"))
            if os.path.isdir(d)]
    return max(dirs, key=os.path.getmtime) if dirs else None


def parse_xplane(path: str, device_index: int = 0) -> ProgramTrace | None:
    from jax.profiler import ProfileData

    want = f"/device:TPU:{device_index}"
    modules, spans, device = [], [], ""
    for plane in ProfileData.from_file(path).planes:
        if plane.name == want:
            device = plane.name
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = [(e.name, e.start_ns, e.duration_ns)
                               for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans.extend(
                    (e.name, e.start_ns, e.duration_ns, dict(e.stats))
                    for e in line.events if e.name.startswith(PREFIXES))
    if not modules or not spans:
        return None
    spans.sort(key=lambda e: e[1])
    return ProgramTrace(modules=modules, spans=spans, device=device)


@functools.lru_cache(maxsize=1)
def load() -> ProgramTrace | None:
    trace_dir = find_trace_dir()
    path = tr.find_xplane(trace_dir) if trace_dir else None
    return parse_xplane(path) if path else None


def of(rc) -> ProgramTrace | None:
    """The program's spans of the run that ``rc`` (a ``ReduceContext``)
    reduces; None when that run made no trace."""
    return load() if rc.trace is not None else None


def paired(pt: ProgramTrace, module: str, span: str) -> list:
    """``[(module event, span event), ...]``: each span matching ``span``
    with the execution of a program matching ``module`` that starts
    nearest to it, in order, one execution a span.  Nearest, because the
    device's clock may lead the host's; a span whose execution lies
    outside the trace (further off than half the spans' spacing) pairs
    with nothing."""
    mrx, srx = re.compile(module), re.compile(span)
    mods = sorted((e for e in pt.modules if mrx.search(e[0])),
                  key=lambda e: e[1])
    spans = [e for e in pt.spans if srx.search(e[0])]
    if not mods or not spans:
        return []
    reach = (statistics.median(b[1] - a[1] for a, b in zip(spans, spans[1:]))
             / 2 if len(spans) > 1 else float("inf"))
    out, i = [], 0
    for sp in spans:
        while i + 1 < len(mods) and (abs(mods[i + 1][1] - sp[1])
                                     <= abs(mods[i][1] - sp[1])):
            i += 1
        if i < len(mods) and abs(mods[i][1] - sp[1]) <= reach:
            out.append((mods[i], sp))
            i += 1
    return out


def device_lead_ns(pt: ProgramTrace) -> float:
    """How far the device's clock runs ahead of the host's, at least: the
    largest time by which an execution is reported to start before the
    span that enqueued it began (0.0 when none is).  A lower bound: the
    enqueue happens somewhere inside the span."""
    return max([sp[1] - mod[1] for module, span in DISPATCHES
                for mod, sp in paired(pt, module, span)] + [0.0])


def device_modules(pt: ProgramTrace) -> list:
    """The device's executions on the host's clock, by start."""
    lead = device_lead_ns(pt)
    return sorted(((n, s + lead, d) for n, s, d in pt.modules),
                  key=lambda e: e[1])


def whole(pt: ProgramTrace, pattern: str = STEP) -> list:
    """The spans matching ``pattern`` that lie wholly inside the device's
    window (first program start to last program end)."""
    mods = device_modules(pt)
    lo, hi = mods[0][1], max(s + d for _, s, d in mods)
    rx = re.compile(pattern)
    return [e for e in pt.spans
            if rx.search(e[0]) and lo <= e[1] and e[1] + e[2] <= hi]


def span_ms(pt: ProgramTrace, span: str, per: str = STEP,
            minus: str | None = None) -> float | None:
    """Median over the whole ``per`` spans of: summed duration of the
    ``span`` spans inside one, less that of the ``minus`` spans inside
    it; ms on the host side of the profiler's clock.  With ``span`` =
    ``per`` and ``minus`` = its children this is a self time."""
    srx = re.compile(span)
    mrx = re.compile(minus) if minus else None
    starts = [e[1] for e in pt.spans]
    values = []
    for _, p0, pd, _ in whole(pt, per):
        total = 0.0
        i = bisect.bisect_left(starts, p0)
        while i < len(pt.spans) and pt.spans[i][1] <= p0 + pd:
            name, s, d, _ = pt.spans[i]
            if s + d <= p0 + pd:
                if srx.search(name):
                    total += d
                if mrx is not None and mrx.search(name):
                    total -= d
            i += 1
        values.append(total)
    return statistics.median(values) / 1e6 if values else None


def step_gaps(pt: ProgramTrace) -> tuple[dict, int] | None:
    """``({span name: idle ns}, whole steps)``: the gaps between program
    executions over the whole ``serving.step`` spans, each given to the
    innermost program span over its middle by ``lib/trace.attribute_gaps``
    (``(no span)`` when none covers it: the caller's loop).  A gap counts
    for the step in which the device resumed, so the one that straddles
    the first whole step's start is in and the one that straddles the
    last one's end is out: every step brings exactly its own."""
    steps = whole(pt)
    if not steps:
        return None
    t0, t1 = steps[0][1], steps[-1][1] + steps[-1][2]
    mods = device_modules(pt)
    starts = [s for _, s, _ in mods]
    first = max(bisect.bisect_left(starts, t0) - 1, 0)
    mods = mods[first:bisect.bisect_left(starts, t1)]
    ranked = tr.attribute_gaps(
        tr.Trace(modules=[], ops=mods, device=pt.device,
                 spans=[(n, s, d) for n, s, d, _ in pt.spans]),
        n=len(pt.spans) + 1)
    return {name: secs * 1e9 for name, secs in ranked}, len(steps)


def idle_under_span_ms(pt: ProgramTrace, span: str | None) -> float | None:
    """Device idle between programs, ms a step, under the program spans
    matching ``span`` (under no program span when ``span`` is None)."""
    found = step_gaps(pt)
    if found is None:
        return None
    by_name, steps = found
    if span is None:
        total = by_name.get(NO_SPAN, 0.0)
    else:
        rx = re.compile(span)
        total = sum(ns for name, ns in by_name.items()
                    if name != NO_SPAN and rx.search(name))
    return total / steps / 1e6
