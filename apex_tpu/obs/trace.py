"""Nestable spans on the monotonic clock, exported as Chrome trace JSON.

The metrics registry answers "how often / how long on average"; spans
answer "what was this *particular* slow step doing".  Design:

- :func:`span` is a context manager.  With **no recorder installed and
  no ``jax.profiler`` session active it is a near-no-op** — one
  module-global read and one ``TraceAnnotation.is_enabled()`` call, no
  contextvar traffic, no allocation (the hot-path contract
  ``bench.py``'s ``obs`` block measures).  While a ``jax.profiler``
  session is active, each span is also a
  ``jax.profiler.TraceAnnotation``: it lands on the ``/host:CPU`` plane
  of the same ``.xplane.pb`` as the device's lines, on the profiler's
  clock, with its attributes as the event's stats — so a device idle
  gap can be laid against what the host was doing (to within the
  profiler's own alignment of the two planes: about a millisecond on a
  v5e, PERF.md §6).  With a recorder, each span records
  a Chrome trace-event ``"X"`` (complete) event: ``ts``/``dur`` in
  monotonic microseconds
  from :func:`time.perf_counter` (never the wall clock — spans must
  not stretch under NTP steps), ``pid``/``tid``, and ``args`` carrying
  the span's attributes, id, and parent id.
- **Parent linkage via contextvars**: entering a span makes it the
  current span for the enclosing context; nested spans record their
  parent's id.  Each thread gets its own context, so the watchdog
  monitor thread can open spans without corrupting the main thread's
  stack; an executor that copies contexts propagates parentage across
  submission boundaries for free.
- **Stamping**: :func:`current_span` exposes the innermost live span so
  cross-cutting layers (the ``emit_event`` bridge) can attach events to
  whatever operation is in flight — zero call-site churn.
- **Export**: :meth:`TraceRecorder.to_chrome_trace` returns the
  ``{"traceEvents": [...]}`` object that ``chrome://tracing`` and
  `Perfetto <https://ui.perfetto.dev>`_ load directly;
  :meth:`TraceRecorder.export` atomically writes it to disk.

For stalls that need *device-side* truth, :func:`start_jax_profiler` /
:func:`stop_jax_profiler` wrap ``jax.profiler`` start/stop (opt-in,
failure-tolerant; every :func:`span` open while it runs is in the
profile, beside the device's lines), and :func:`profile_on_stall`
adapts them to the
:class:`~apex_tpu.resilience.supervisor.StepWatchdog` ``on_stall`` hook
so the first stall of a run captures a device profile on demand.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import os
import threading
import time
from typing import Iterator, List, Optional

from jax.profiler import TraceAnnotation

from apex_tpu._logging import get_logger

__all__ = [
    "Span",
    "TraceRecorder",
    "current_span",
    "install_recorder",
    "profile_on_stall",
    "recording",
    "span",
    "start_jax_profiler",
    "stop_jax_profiler",
    "uninstall_recorder",
]

logger = get_logger("obs.trace")

_RECORDER: Optional["TraceRecorder"] = None
_CURRENT: contextvars.ContextVar[Optional["Span"]] = contextvars.ContextVar(
    "apex_obs_current_span", default=None)
_SPAN_IDS = itertools.count(1)


def _now_us() -> float:
    return time.perf_counter() * 1e6


class Span:
    """One live span: name, attributes, events, parent linkage.

    Mutable only while live; the exporter snapshot is taken at exit.
    """

    __slots__ = ("name", "span_id", "parent_id", "attrs", "events",
                 "_annotation")

    def __init__(self, name: str, span_id: int, parent_id: Optional[int],
                 attrs: dict, annotation: Optional[TraceAnnotation] = None):
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.attrs = attrs
        self.events: List[dict] = []
        self._annotation = annotation

    def set_attribute(self, key: str, value) -> None:
        """Set an attribute while the span is live — a count known only
        at exit (chunks dispatched, requests finished) goes here.  Under
        a profiler session it becomes one more stat of the annotation."""
        self.attrs[key] = value
        if self._annotation is not None:
            self._annotation.set_metadata(**{key: value})

    def add_event(self, name: str, **attrs) -> None:
        """Stamp a point-in-time event onto this span (the bridge calls
        this for every ``emit_event`` fired while the span is live)."""
        ev = {"name": name, "ts_us": round(_now_us(), 3)}
        if attrs:
            ev.update(attrs)
        self.events.append(ev)


class TraceRecorder:
    """Thread-safe collector of finished span events.

    ``max_events`` bounds memory: a recorder left installed for a whole
    multi-day run (the docs recipe does exactly that) must not grow RSS
    without limit.  At the cap, NEW events are dropped and counted in
    :attr:`dropped` (the trace keeps the run's beginning — the part
    that explains how it got into trouble); the first drop logs a
    warning so the truncation is never silent.
    """

    def __init__(self, max_events: int = 500_000):
        if max_events < 1:
            raise ValueError(f"max_events must be >= 1, got {max_events}")
        self.max_events = int(max_events)
        self.dropped = 0
        self._lock = threading.Lock()
        self._events: List[dict] = []

    def record(self, event: dict) -> None:
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                first_drop = self.dropped == 1
            else:
                self._events.append(event)
                first_drop = False
        if first_drop:
            logger.warning(
                "TraceRecorder full (%d events): dropping further spans "
                "(count rides the exported trace's otherData)",
                self.max_events)

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def to_chrome_trace(self) -> dict:
        """The Chrome trace-event JSON object (Perfetto-loadable)."""
        with self._lock:
            events = list(self._events)
            dropped = self.dropped
        events.sort(key=lambda e: e["ts"])
        payload = {"traceEvents": events, "displayTimeUnit": "ms"}
        if dropped:
            payload["otherData"] = {"dropped_events": dropped,
                                    "max_events": self.max_events}
        return payload

    def export(self, path: str) -> dict:
        """Atomically write the trace JSON; returns the payload.
        Non-finite span attributes (a NaN loss stamped on a diverged
        step) are mapped to ``null`` — Perfetto's strict JSON parser
        must always load the file, never less so than when something
        went wrong."""
        from apex_tpu.utils.serialization import (
            atomic_write_json,
            json_finite,
        )

        payload = json_finite(self.to_chrome_trace())
        # default=str: span attrs are arbitrary user kwargs (a jax array
        # stamped on a span must degrade to its repr, not kill the export
        # — the same contract emit_event's log line has always had)
        atomic_write_json(path, payload, allow_nan=False, default=str)
        return payload


def install_recorder(recorder: Optional[TraceRecorder] = None
                     ) -> TraceRecorder:
    """Install (and return) the process-wide recorder; spans are
    recorded only while one is installed."""
    global _RECORDER
    if recorder is None:
        recorder = TraceRecorder()
    _RECORDER = recorder
    return recorder


def uninstall_recorder() -> Optional[TraceRecorder]:
    """Remove and return the installed recorder (None if none)."""
    global _RECORDER
    recorder, _RECORDER = _RECORDER, None
    return recorder


@contextlib.contextmanager
def recording() -> Iterator[TraceRecorder]:
    """``with recording() as rec:`` — record spans for the block only,
    restoring whatever recorder was installed before."""
    global _RECORDER
    prev = _RECORDER
    rec = TraceRecorder()
    _RECORDER = rec
    try:
        yield rec
    finally:
        _RECORDER = prev


def current_span() -> Optional[Span]:
    """The innermost live span of this context (None outside any span,
    and always None while no recorder is installed)."""
    return _CURRENT.get()


@contextlib.contextmanager
def span(name: str, **attrs) -> Iterator[Optional[Span]]:
    """``with span("train_step", step=i) as s:`` — time a region.

    Yields the live :class:`Span` (mutate attributes, add events), or
    ``None`` when no recorder is installed and no ``jax.profiler``
    session is active — that path does no contextvar writes and no
    allocation, so leaving instrumentation in hot loops is free by
    default.  Under a profiler session the span is written to the
    profile as a ``TraceAnnotation`` (attributes — plain ints and short
    strings — become its stats); with a recorder it is recorded as a
    Chrome trace event; with both, both.
    """
    recorder = _RECORDER
    profiling = TraceAnnotation.is_enabled()
    if recorder is None and not profiling:
        yield None
        return
    annotation = TraceAnnotation(name, **attrs) if profiling else None
    if recorder is None:
        # profiler only: no parent linkage to keep and current_span()
        # stays None (the event bridge stamps recorder spans only)
        with annotation:
            yield Span(name, 0, None, attrs, annotation)
        return
    parent = _CURRENT.get()
    live = Span(name, next(_SPAN_IDS),
                parent.span_id if parent is not None else None, dict(attrs),
                annotation)
    token = _CURRENT.set(live)
    t0 = time.perf_counter()
    try:
        with annotation or contextlib.nullcontext():
            yield live
    finally:
        dur_us = (time.perf_counter() - t0) * 1e6
        _CURRENT.reset(token)
        args = dict(live.attrs)
        args["span_id"] = live.span_id
        if live.parent_id is not None:
            args["parent_id"] = live.parent_id
        if live.events:
            args["events"] = live.events
        recorder.record({
            "name": name, "ph": "X", "cat": "apex",
            "ts": round(t0 * 1e6, 3), "dur": round(dur_us, 3),
            "pid": os.getpid(), "tid": threading.get_ident(),
            "args": args,
        })


# ---------------------------------------------------------------------------
# opt-in jax.profiler hook: device-side truth for a stalled step
# ---------------------------------------------------------------------------

_PROFILER_LOCK = threading.Lock()
_PROFILER_ACTIVE = False


def start_jax_profiler(logdir: str) -> bool:
    """Start a ``jax.profiler`` trace into ``logdir`` (idempotent; False
    when already running or when the profiler is unavailable).  Opt-in
    by design: nothing in apex_tpu starts it for you except the hook
    you explicitly wire via :func:`profile_on_stall`."""
    global _PROFILER_ACTIVE
    with _PROFILER_LOCK:
        if _PROFILER_ACTIVE:
            return False
        try:
            import jax

            jax.profiler.start_trace(logdir)
        except Exception as e:  # diagnostics must never kill the run
            logger.warning("jax profiler start failed: %s: %s",
                           type(e).__name__, e)
            return False
        _PROFILER_ACTIVE = True
        logger.info("jax profiler tracing into %s", logdir)
        return True


def stop_jax_profiler() -> bool:
    """Stop a running ``jax.profiler`` trace (False when none active)."""
    global _PROFILER_ACTIVE
    with _PROFILER_LOCK:
        if not _PROFILER_ACTIVE:
            return False
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception as e:
            # flag stays True: a failed stop must remain stoppable —
            # clearing it here would wedge the trace running until
            # process exit with every later call refusing at the guard
            logger.warning("jax profiler stop failed: %s: %s",
                           type(e).__name__, e)
            return False
        _PROFILER_ACTIVE = False
        return True


def profile_on_stall(logdir: str):
    """Adapter for ``StepWatchdog(on_stall=...)``: the FIRST stall of a
    run starts a device profile on demand (stop it with
    :func:`stop_jax_profiler` once the evidence is captured)::

        wd = StepWatchdog(deadline_s=60.0,
                          on_stall=profile_on_stall("/tmp/stall_profile"))
    """
    def _hook(diagnostics: dict) -> None:
        if start_jax_profiler(logdir):
            logger.warning(
                "stall at step %s: jax profiler started into %s",
                diagnostics.get("step"), logdir)
    return _hook
