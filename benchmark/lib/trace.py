"""Reduction of a profiler trace to the numbers per-layer metrics read.

The JAX profiler writes an ``.xplane.pb``; :func:`load_xplane` turns it
into a :class:`Trace` — plain lists of ``(name, start_ns, dur_ns)`` for the
device's ``XLA Modules`` and ``XLA Ops`` lines and for the benchmark's own
host spans — and everything else here works on that plain form, so the
arithmetic is tested on a small recorded fixture (``fixtures/``) without a
chip.  All device times come from the device's lines, never from the host
clock of the traced run: the profiler slows the host (a traced training
step read 494.5 ms on the host clock and 418.4 ms on the device, PERF.md).
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
import statistics

_TRAILING_ID = re.compile(r"\.\d+$")


@dataclasses.dataclass
class Trace:
    """Events of one device plane plus the benchmark's host spans.
    Each event is ``(name, start_ns, dur_ns)``."""

    modules: list
    ops: list
    spans: list
    device: str = ""

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        return cls(modules=[tuple(e) for e in obj["modules"]],
                   ops=[tuple(e) for e in obj["ops"]],
                   spans=[tuple(e) for e in obj["spans"]],
                   device=obj.get("device", ""))


def load_fixture(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load_xplane(path: str, span_names, device_index: int = 0) -> Trace:
    """Read device ``device_index``'s module and op lines and the host
    events named in ``span_names`` (the benchmark's TraceAnnotations)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    want = f"/device:TPU:{device_index}"
    span_names = set(span_names)
    modules, ops, spans, device = [], [], [], ""
    for plane in data.planes:
        if plane.name == want:
            device = plane.name
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = [(e.name, e.start_ns, e.duration_ns)
                               for e in line.events]
                elif line.name == "XLA Ops":
                    ops = [(e.name, e.start_ns, e.duration_ns)
                           for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.duration_ns)
                             for e in line.events if e.name in span_names)
    spans.sort(key=lambda e: e[1])
    return Trace(modules=modules, ops=ops, spans=spans, device=device)


def short_name(op_name: str) -> str:
    """``%flash_attention_dkv.50 = (bf16[..]) custom-call(..)`` ->
    ``flash_attention_dkv``: the text before `` = ``, no leading ``%``,
    no trailing ``.<digits>``."""
    head = op_name.split(" = ", 1)[0].strip().lstrip("%")
    return _TRAILING_ID.sub("", head)


def busy_union(events) -> tuple[float, float, list]:
    """``(busy_ns, window_ns, gaps)`` of the union of the events'
    intervals.  The window runs from the first start to the last end;
    ``gaps`` are the ``(start_ns, end_ns)`` holes in it."""
    ivs = sorted((s, s + d) for _, s, d in events)
    if not ivs:
        return 0.0, 0.0, []
    busy, gaps = 0.0, []
    cur_s, cur_e = ivs[0]
    for s, e in ivs[1:]:
        if s > cur_e:
            busy += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    busy += cur_e - cur_s
    return busy, cur_e - ivs[0][0], gaps


def module_groups(trace: Trace, pattern: str) -> dict:
    """Durations (ns) of module executions whose name matches ``pattern``,
    grouped by full module name (one fingerprint = one compiled program)."""
    rx = re.compile(pattern)
    groups: dict = {}
    for name, _, dur in trace.modules:
        if rx.search(name):
            groups.setdefault(name, []).append(dur)
    return groups


def module_median_ms(trace: Trace, pattern: str,
                     pick: str = "all") -> float | None:
    """Median device duration, in ms, of the modules matching ``pattern``.
    ``pick="all"`` pools every match; ``"largest"`` takes, among the
    distinct programs that match, the one with the largest median (the
    512-token prefill among the prefill buckets)."""
    groups = module_groups(trace, pattern)
    if not groups:
        return None
    if pick == "largest":
        return max(statistics.median(d) for d in groups.values()) / 1e6
    return statistics.median(
        d for durs in groups.values() for d in durs) / 1e6


def op_time_per_module_ms(trace: Trace, op_pattern: str,
                          module_pattern: str) -> float | None:
    """Device time, in ms per execution, spent in ops whose short name
    matches ``op_pattern`` inside executions of the modules matching
    ``module_pattern``.  With three executions or more the first and the
    last are left out: a slice that starts or stops while the device is
    busy holds only part of their ops (a traced run whose device never
    idles read 25.9 ms for 28.8, nine steps' ops over ten executions)."""
    mrx = re.compile(module_pattern)
    spans = sorted((s, s + d) for n, s, d in trace.modules if mrx.search(n))
    if not spans:
        return None
    if len(spans) >= 3:
        spans = spans[1:-1]
    starts = [s for s, _ in spans]
    orx = re.compile(op_pattern)
    total, seen = 0.0, False
    for name, s, d in trace.ops:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= spans[i][1]:
            continue
        if orx.search(short_name(name)):
            total += d
            seen = True
    return total / len(spans) / 1e6 if seen else None


def top_ops(trace: Trace, n: int = 10) -> list:
    """The ``n`` short op names with most summed device time:
    ``[[name, seconds], ...]``."""
    sums: dict = {}
    for name, _, d in trace.ops:
        k = short_name(name)
        sums[k] = sums.get(k, 0.0) + d
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def attribute_gaps(trace: Trace, n: int = 10) -> list:
    """Idle time of the device by what the host was doing: each gap
    between device ops goes to the benchmark span covering its middle
    (the innermost one when spans nest), ``"(no span)"`` when none does.
    ``[[span_name, seconds], ...]``, longest first."""
    _, _, gaps = busy_union(trace.ops)
    spans = trace.spans
    starts = [s for _, s, _ in spans]
    sums: dict = {}
    for g0, g1 in gaps:
        mid = (g0 + g1) / 2
        owner = "(no span)"
        i = bisect.bisect_right(starts, mid) - 1
        # walk back over the few spans that start before the middle;
        # the latest-starting one that still covers it is the innermost
        j = i
        while j >= 0 and j > i - 64:
            name, s, d = spans[j]
            if s <= mid < s + d:
                owner = name
                break
            j -= 1
        sums[owner] = sums.get(owner, 0.0) + (g1 - g0)
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]
