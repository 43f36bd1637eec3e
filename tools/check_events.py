#!/usr/bin/env python
"""Lint: every emitted event kind is bridged or explicitly allowlisted.

The obs bridge (:mod:`apex_tpu.obs.bridge`) silently ignores event
kinds it has no handler for — by design (``apex_events_total{event=}``
still counts them), but that design has a failure mode: a typo'd
``emit_event`` kind, or a new event whose author forgot the bridge
handler, drops its *measurements* without a trace.  The queue-wait
histogram fed by ``serving_request_admitted`` would simply stop filling
if the emit site said ``serving_request_admited`` — no error, no test
failure, just a silently empty metric.

This lint closes the loop statically:

1. every string-literal kind passed to an ``emit_event(`` call under
   ``apex_tpu/`` must either have an ``obs/bridge.py`` ``_HANDLERS``
   entry or appear in the explicit :data:`ALLOWLIST` below (kinds that
   are countable-only on purpose, each with its rationale);
2. the reverse, both ways: an ``_HANDLERS`` key nothing emits is a
   dead handler (or the emit site was renamed out from under it), and
   an :data:`ALLOWLIST` entry that is handled or never emitted is
   stale — all flagged, so the three sets partition the vocabulary
   exactly;
3. a *non-literal* kind (a variable) is flagged too: dynamic kinds
   can't be linted, and none exist in-tree.

One sanctioned indirection: a method named ``_emit`` is a declared
emit *wrapper* (the serving scheduler's replica-stamping wrapper) —
its call sites are linted exactly like ``emit_event`` calls, and the
single forwarding ``emit_event(kind, ...)`` inside its body is exempt
from the literal-kind rule (the literals live at the call sites).

Run directly (``python tools/check_events.py``) or through tier-1
(``tests/test_lint_events.py``).  Scope is ``apex_tpu/`` only — tests
emit throwaway kinds into private sinks.
"""

from __future__ import annotations

import ast
import os
import sys
from typing import List, NamedTuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCAN = ("apex_tpu",)
BRIDGE = os.path.join(REPO, "apex_tpu", "obs", "bridge.py")

#: event kinds that are *countable-only* on purpose — each rides
#: ``apex_events_total{event=}`` but carries no measurement a metric
#: handler should extract (or its measurement is already published by
#: another channel).  Adding a kind here is an explicit decision; a
#: kind in neither this list nor ``_HANDLERS`` fails the lint.
ALLOWLIST = {
    # lifecycle narration: the measurements ride the *terminal* events
    # (checkpoint_saved carries bytes/duration consumed by bench, not
    # by a live metric; restore is a startup path)
    "checkpoint_saved",
    "checkpoint_restored",
    "checkpoint_snapshot",
    "checkpoint_backpressure",
    "checkpoint_commit_vetoed",
    # retry_attempt/exhausted are handled; recovery is the non-event
    "retry_recovered",
    # the failure observation is counted via replica_desync (handled);
    # these narrate the detection/repair walk around it
    "replica_verify_failed",
    "replica_resync",
    # terminal supervisor narration; supervisor_failure is handled
    "supervisor_abort",
    # guarded-step escalation narration (the very first kind this lint
    # caught uncovered): the skip decisions around it are already
    # countable via batch_skipped / apex_events_total
    "loss_scale_floor_halved",
    # data-pipeline stall warning (the watchdog_stall counter covers
    # the deadline violation itself)
    "data_stall",
    # serving lifecycle narration: queued is the lifecycle's first
    # breadcrumb (admitted carries the queue-wait measurement); the
    # step sample's gauges are set directly by the scheduler
    "serving_request_queued",
    "serving_step",
    # a refused reload carries only a reason string — countable via
    # apex_events_total{event=}; the phase timings that feed
    # apex_serving_reload_duration_seconds ride the loaded/swapped
    # events, which ARE handled
    "serving_reload_failed",
    # a resume is the second half of a preemption cycle — the
    # apex_serving_preempted_total counter counts cycles once, and the
    # suspension gap is a request-trace annotation, not a metric
    "serving_request_resumed",
    # loadgen narration: goodput is published as a gauge by the
    # generator itself; shed-at-QueueFull is charged there too
    "loadgen_started",
    "loadgen_finished",
    "loadgen_request_shed",
    # boot-time narration of which quantization legs are on — a config
    # echo with no measurement; the quant metrics (agreement, logit
    # error, bytes/token) ride serving_quant_eval, which IS handled
    "serving_quant_enabled",
    # trace-time narration of which side of a kernel's shape predicate
    # a call site took (ops/_dispatch.record_dispatch) — chip_smoke.py
    # reads it from a private sink; countable via apex_events_total
    "kernel_dispatch",
    # its sibling for a choice between two plain jax.numpy reads
    # (ops/_dispatch.record_choice: a chunk's K/V read walks the visible
    # blocks or attends the whole extent) - the benchmark reads it from a
    # private sink
    "read_dispatch",
}


class Emit(NamedTuple):
    kind: str        # the event-kind literal (or a marker for dynamic)
    relpath: str
    lineno: int
    literal: bool


def _is_emit_event(node: ast.Call) -> bool:
    func = node.func
    if isinstance(func, ast.Name):
        return func.id == "emit_event"
    if isinstance(func, ast.Attribute):
        # self._emit("kind", ...) — the sanctioned wrapper indirection
        return func.attr in ("emit_event", "_emit")
    return False


def _wrapper_spans(tree: ast.AST) -> List[tuple]:
    """Line spans of ``_emit`` method bodies — the one place a
    forwarded non-literal kind is sanctioned."""
    spans = []
    for node in ast.walk(tree):
        if (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and node.name == "_emit"):
            spans.append((node.lineno, node.end_lineno or node.lineno))
    return spans


def collect_emits_from_source(source: str, relpath: str) -> List[Emit]:
    """Every ``emit_event(...)`` / ``self._emit(...)`` call's first
    positional argument."""
    try:
        tree = ast.parse(source, filename=relpath)
    except SyntaxError as e:
        return [Emit(f"<syntax error: {e.msg}>", relpath,
                     e.lineno or 0, False)]
    wrappers = _wrapper_spans(tree)
    out: List[Emit] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not _is_emit_event(node):
            continue
        if any(lo <= node.lineno <= hi for lo, hi in wrappers) and not (
                node.args and isinstance(node.args[0], ast.Constant)):
            continue                    # the wrapper's forwarding call
        if not node.args:
            out.append(Emit("<missing kind argument>", relpath,
                            node.lineno, False))
            continue
        first = node.args[0]
        if isinstance(first, ast.Constant) and isinstance(first.value, str):
            out.append(Emit(first.value, relpath, first.lineno, True))
        else:
            out.append(Emit("<non-literal kind>", relpath,
                            node.lineno, False))
    return out


def _iter_files():
    for entry in SCAN:
        full = os.path.join(REPO, entry)
        for dirpath, _, filenames in os.walk(full):
            for name in sorted(filenames):
                if name.endswith(".py"):
                    yield os.path.join(dirpath, name)


def collect_emits() -> List[Emit]:
    emits: List[Emit] = []
    for path in _iter_files():
        with open(path) as f:
            source = f.read()
        emits.extend(collect_emits_from_source(
            source, os.path.relpath(path, REPO)))
    return emits


def collect_handlers(bridge_source: str) -> List[str]:
    """The ``_HANDLERS`` dict's string keys, parsed statically (no
    import — the lint must run in a bare interpreter)."""
    tree = ast.parse(bridge_source, filename="bridge.py")
    for node in ast.walk(tree):
        if (isinstance(node, ast.Assign)
                and any(isinstance(t, ast.Name) and t.id == "_HANDLERS"
                        for t in node.targets)
                and isinstance(node.value, ast.Dict)):
            return [k.value for k in node.value.keys
                    if isinstance(k, ast.Constant)
                    and isinstance(k.value, str)]
    raise ValueError("no _HANDLERS dict literal found in obs/bridge.py")


def check(emits: List[Emit], handlers: List[str],
          allowlist=frozenset(ALLOWLIST)) -> List[str]:
    """All violations as human-readable messages (empty == clean)."""
    problems: List[str] = []
    handled = set(handlers)
    emitted = set()
    for e in emits:
        where = f"{e.relpath}:{e.lineno}"
        if not e.literal:
            problems.append(
                f"{where}: emit_event with {e.kind} — kinds must be "
                f"string literals so the bridge coverage is lintable")
            continue
        emitted.add(e.kind)
        if e.kind not in handled and e.kind not in allowlist:
            problems.append(
                f"{where}: event kind {e.kind!r} has no obs/bridge.py "
                f"handler and no tools/check_events.py ALLOWLIST entry "
                f"— the bridge would silently drop its measurements "
                f"(add a handler, or allowlist it with a rationale)")
    for kind in sorted(handled - emitted):
        problems.append(
            f"obs/bridge.py handles {kind!r} but nothing under "
            f"apex_tpu/ emits it — dead handler, or the emit site was "
            f"renamed out from under it")
    for kind in sorted(allowlist & handled):
        problems.append(
            f"ALLOWLIST entry {kind!r} is also handled in "
            f"obs/bridge.py — remove the stale allowlist entry")
    for kind in sorted(allowlist - emitted - handled):
        problems.append(
            f"ALLOWLIST entry {kind!r} is emitted nowhere under "
            f"apex_tpu/ — remove the stale allowlist entry")
    return problems


def find_violations() -> List[str]:
    with open(BRIDGE) as f:
        bridge_source = f.read()
    return check(collect_emits(), collect_handlers(bridge_source))


def main() -> int:
    problems = find_violations()
    for p in problems:
        print(p)
    if not problems:
        emits = collect_emits()
        print(f"events lint clean ({len({e.kind for e in emits})} "
              f"kinds over {len(emits)} emit sites)")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
