"""What every runner needs from the harness: the set-up clock, the device
report, the count of compilations, the profiler slice and the peaks."""

from __future__ import annotations

import contextlib
import gc
import json
import os
import shutil
import tempfile
import time

from benchmark.lib import trace as tr

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class SetupClock:
    """Seconds of set-up by stage, from process start to window open.
    ``mark(name)`` closes the stage that ran since the last mark."""

    def __init__(self, t0: float):
        self.t0 = t0
        self._last = t0
        self.stages: dict = {}
        self.setup_s = None

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.stages[name] = self.stages.get(name, 0.0) + now - self._last
        self._last = now

    def window_opens(self) -> None:
        """Everything up to here was set-up.  The objects tracing and
        compiling left behind are collected now and frozen out of later
        collections, so that no full collection of them stalls the window
        (two runs of 27 lost 3-4 s of their window to one stall, PERF.md)."""
        gc.collect()
        gc.freeze()
        self.setup_s = time.perf_counter() - self.t0
        self._last = time.perf_counter()

    def line(self) -> str:
        return json.dumps({"setup_breakdown_s": {
            k: round(v, 3) for k, v in self.stages.items()},
            "setup_s": self.setup_s})


class CompileCounter:
    """Listens to what JAX traces, lowers and compiles.  Sums the seconds
    by event over the whole run (the set-up line prints them: tracing and
    lowering are paid even when the persistent cache holds the program),
    and records every such event while ``active``: one inside the measured
    window breaks the contract."""

    _PREFIX = "/jax/core/compile/"

    def __init__(self):
        import jax

        self.active = False
        self.events: list = []
        self.seconds: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, secs: float, **kw) -> None:
        if not name.startswith(self._PREFIX):
            return
        key = name[len(self._PREFIX):]
        self.seconds[key] = self.seconds.get(key, 0.0) + secs
        if self.active:
            self.events.append(key)

    @contextlib.contextmanager
    def window(self):
        self.active = True
        try:
            yield self
        finally:
            self.active = False


def load_peaks(device_kind: str) -> dict:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no peaks on record for device_kind {device_kind!r}: add it "
            f"to benchmark/peaks.json with its source")
    return table[device_kind]


def device_report(chips: int) -> dict:
    """The contract's ``device`` object, as JAX reports it."""
    import jax

    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


class TraceSlice:
    """Profiles a slice of the window: ``poll(now)`` starts the profiler
    once ``start_after_s`` of the window have passed and stops it
    ``length_s`` later.  The trace goes to a temporary directory (under
    ``TMPDIR``) that ``close()`` removes after the reduction."""

    def __init__(self, enabled: bool, start_after_s: float, length_s: float,
                 span_names):
        self.start_after_s = start_after_s
        self.length_s = length_s
        self.span_names = tuple(span_names)
        self.state = "idle" if enabled else "done"
        self.dir = None
        self.t_window = None
        self._t_started = None

    def window_opens(self, now: float) -> None:
        self.t_window = now

    def poll(self, now: float) -> None:
        import jax

        if self.state == "idle" and self.t_window is not None \
                and now - self.t_window >= self.start_after_s:
            self.dir = tempfile.mkdtemp(prefix="apexbench-trace-")
            # no Python-function tracing: it slows the host loop, which
            # would inflate the very idle gaps the trace is read for
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self._t_started = time.perf_counter()
            self.state = "tracing"
        elif self.state == "tracing" \
                and time.perf_counter() - self._t_started >= self.length_s:
            self.stop()

    def stop(self) -> None:
        import jax

        if self.state == "tracing":
            jax.profiler.stop_trace()
            self.state = "done"

    def span(self, name: str):
        """A host span on the profiler's clock (free when not tracing)."""
        if self.state != "tracing":
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def load(self):
        """The reduced trace, or None when nothing was recorded."""
        self.stop()
        if self.dir is None:
            return None
        path = tr.find_xplane(self.dir)
        return tr.load_xplane(path, self.span_names) if path else None

    def close(self) -> None:
        self.stop()
        if self.dir is not None:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None
