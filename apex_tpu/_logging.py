"""Rank-aware logging for apex_tpu.

The reference installs a root-logger handler whose formatter prefixes every
record with distributed rank info (apex/__init__.py:31-43, pulling
``parallel_state.get_rank_info``).  Here rank info comes from
``jax.process_index`` plus (when initialized) the mesh registry in
:mod:`apex_tpu.transformer.parallel_state`.
"""

from __future__ import annotations

import json
import logging
import threading
import time


class RankInfoFilter(logging.Filter):
    """Injects a ``rank_info`` field into log records.

    Cheap by design: reads process index lazily and tolerates JAX not being
    initialized yet (import-time logging must never crash).
    """

    def filter(self, record: logging.LogRecord) -> bool:
        record.rank_info = _rank_info()
        return True


_RANK_INFO_WARNED: set = set()
# the keys are a small closed vocabulary today, but callers pass
# arbitrary strings (sink ids ride through here too) — cap the set so a
# pathological key stream can never grow it without bound
_MAX_WARNED_KEYS = 64


def _debug_once(key: str, what: str, exc: Exception) -> None:
    """Log a swallowed rank-info failure ONCE at debug level.

    The flag is set *before* logging: the debug record flows through the
    rank-aware handler, whose filter re-enters :func:`_rank_info` — the
    guard is what keeps that recursion one level deep.
    """
    if key in _RANK_INFO_WARNED or len(_RANK_INFO_WARNED) >= _MAX_WARNED_KEYS:
        return
    _RANK_INFO_WARNED.add(key)
    logging.getLogger("apex_tpu._logging").debug(
        "%s unavailable (further failures silent): %s: %s",
        what, type(exc).__name__, exc)


def _rank_info() -> str:
    try:
        import jax

        parts = [f"p{jax.process_index()}"]
    except Exception as e:
        _debug_once("process_index", "jax process index", e)
        return "p?"
    try:
        from apex_tpu.transformer import parallel_state

        if parallel_state.model_parallel_is_initialized():
            parts.append(parallel_state.get_rank_info())
    except Exception as e:
        _debug_once("parallel_state", "mesh rank info", e)
    return "|".join(parts)


_HANDLER: logging.Handler | None = None


def _install_rank_aware_logging() -> None:
    """Install one rank-aware handler on the ``apex_tpu`` logger (idempotent)."""
    global _HANDLER
    if _HANDLER is not None:
        return
    logger = logging.getLogger("apex_tpu")
    handler = logging.StreamHandler()
    handler.setFormatter(
        logging.Formatter("%(asctime)s [%(levelname)s|%(rank_info)s] %(name)s: %(message)s")
    )
    handler.addFilter(RankInfoFilter())
    logger.addHandler(handler)
    logger.propagate = False
    _HANDLER = handler


def set_logging_level(level: int | str) -> None:
    """Set the apex_tpu logging level (reference: apex/transformer/log_util.py)."""
    logging.getLogger("apex_tpu").setLevel(level)


def get_logger(name: str) -> logging.Logger:
    return logging.getLogger(f"apex_tpu.{name}")


def _log_sink(event: dict) -> None:
    """The default sink: one sorted-key JSON line on ``apex_tpu.events``
    (the exact pre-sink-registry behavior, byte for byte).  The line is
    built only when the logger would write it: a serving step emits
    several events, and ``json.dumps`` of each was paid with nothing
    listening at INFO."""
    logger = logging.getLogger("apex_tpu.events")
    if logger.isEnabledFor(logging.INFO):
        logger.info("%s", json.dumps(event, sort_keys=True, default=str))


# ordered fan-out list; the log sink is first so the canonical line is
# written even when a later sink misbehaves.  The lock makes add/remove
# idempotence hold under concurrent registration — a sink subscribed
# twice would silently double-count every event-driven metric
_EVENT_SINKS: list = [_log_sink]
_SINKS_LOCK = threading.Lock()


def add_event_sink(sink) -> None:
    """Subscribe ``sink(event_dict)`` to every :func:`emit_event`
    (idempotent, thread-safe).  Sinks must be cheap and must not raise;
    a raising sink is debug-logged once and never breaks the emitting
    code path (the event bridge in :mod:`apex_tpu.obs.bridge` is the
    canonical subscriber)."""
    with _SINKS_LOCK:
        if sink not in _EVENT_SINKS:
            _EVENT_SINKS.append(sink)


def remove_event_sink(sink) -> None:
    """Unsubscribe a sink (no-op when absent).  Removing
    :func:`_log_sink` itself silences the JSON log lines — tests that
    want a quiet stream may do that, production code should not."""
    with _SINKS_LOCK:
        try:
            _EVENT_SINKS.remove(sink)
        except ValueError:
            pass


def event_sinks() -> tuple:
    """The current fan-out list (a copy; mutate via add/remove)."""
    return tuple(_EVENT_SINKS)


def emit_event(kind: str, *, t0: float | None = None, **fields) -> dict:
    """Emit a structured (JSON) operational event and return it.

    The resilience subsystem reports state transitions — checkpoint
    saved/rejected/restored, step skipped, loss-scale floor halved —
    as machine-parseable single-line events rather than prose, so a
    fleet-level collector can alert on them (the reason silent recovery
    loops are banned; see :mod:`apex_tpu.resilience`).  Events ride the
    ordinary ``apex_tpu.events`` logger and therefore inherit the
    rank-aware handler installed at import.

    The finished event fans out to every registered sink
    (:func:`add_event_sink`); the default sink is the logger line above
    — its output is byte-identical whether or not other sinks exist —
    and :mod:`apex_tpu.obs.bridge` subscribes a sink that turns every
    event into a metric increment and a span stamp.

    Timing events pass ``t0`` — a ``time.monotonic()`` stamp taken when
    the operation started — and get a ``duration_s`` field computed on
    the monotonic clock.  ``time.time()`` (the ``time`` field) is for
    cross-host correlation only: the wall clock steps under NTP and is
    exactly what a stall watchdog must NOT measure with.
    """
    event = {"event": kind, "time": time.time(), **fields}
    if t0 is not None:
        event["duration_s"] = round(time.monotonic() - t0, 6)
    for sink in tuple(_EVENT_SINKS):
        try:
            sink(event)
        except Exception as e:  # a broken sink must not break the emitter
            # keyed by qualname, NOT id(): the debug-once set is capped,
            # and id() churn (or reuse after GC) could both exhaust the
            # cap and collide distinct sinks
            name = getattr(sink, "__qualname__", type(sink).__name__)
            _debug_once(f"event_sink:{name}", f"event sink {name!r}", e)
    return event
