"""Readers of per-layer metrics.  Each module has one function,
``reduce(rc, **args)``, which takes its number from the trace or the
counters in ``rc`` and returns None when there is nothing to read."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class ReduceContext:
    trace: object          # benchmark.lib.trace.Trace, or None
    counters: dict         # the runner's counts over the whole window
    config: dict
    traffic: dict
    device_kind: str
