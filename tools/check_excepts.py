#!/usr/bin/env python
"""Lint: no new silent broad-exception swallowing.

PR 2's theme is that failures must leave evidence — a retry event, a
debug line, a structured abort — never vanish.  This lint enforces the
floor: a handler that catches ``Exception`` / ``BaseException`` / bare
``except:`` and whose body contains *neither a ``raise`` nor any
function call* (no logging, no ``emit_event``, no ``errors.append``)
swallows the failure without a trace and fails the build, unless the
site is on the explicit allowlist below.

The rule is deliberately conservative (call-free AND raise-free) so it
has near-zero false positives: narrowing the exception type, logging at
debug, re-raising as a domain error, or recording the message all pass.
Run directly (``python tools/check_excepts.py``) or through tier-1
(``tests/test_lint_excepts.py``).
"""

from __future__ import annotations

import ast
import os
import sys
from typing import List, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# directories/files scanned, relative to the repo root (tests are
# exempt: a test intentionally swallowing is part of its arrangement)
SCAN = ("apex_tpu", "tools", "examples", "bench.py")

# "relpath::qualname" of handlers audited and accepted as-is.  Every
# entry must keep matching a real broad-and-silent handler — a stale
# entry fails the lint too, so the list can only shrink or be
# consciously re-justified.  Last audited with ISSUE 8 (the async
# checkpoint pipeline lands lint-clean: the writer thread's broad
# `except BaseException` both logs AND store-forwards the exception
# onto its SaveFuture — the store-forwarding idiom _is_silent already
# recognizes — and the write machinery's cleanup handlers re-raise; no
# entry needed.  Earlier notes: ISSUE 6 obs/ sink fan-out and profiler
# hooks debug/warning-log their swallowed failures; ISSUE 4 serving has
# no broad handlers; bench's diagnostic blocks use the logged `except
# Exception` pattern).
ALLOWLIST = {
    # availability probe: None IS the complete answer (numpy fallback)
    "apex_tpu/utils/_native.py::lib",
    # doc generator renders "(no doc)" / skips unrenderable symbols
    "tools/gen_api_docs.py::_doc_first_block",
    "tools/gen_api_docs.py::_render_symbol",
}

Violation = Tuple[str, int, str]  # (relpath, lineno, qualname)

_BROAD_NAMES = ("Exception", "BaseException")


def _is_broad(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:  # bare except:
        return True
    for node in t.elts if isinstance(t, ast.Tuple) else [t]:
        if isinstance(node, ast.Name) and node.id in _BROAD_NAMES:
            return True
        if isinstance(node, ast.Attribute) and node.attr in _BROAD_NAMES:
            return True
    return False


def _is_silent(handler: ast.ExceptHandler) -> bool:
    """No raise, no call, and no store of the caught exception object
    anywhere in the handler body = the failure leaves no trace.
    (Storing ``e`` — ``self._error = e`` in a worker thread — is the
    forwarding idiom: the exception surfaces elsewhere.)"""
    for stmt in handler.body:
        for node in ast.walk(stmt):
            if isinstance(node, (ast.Raise, ast.Call)):
                return False
            if handler.name and isinstance(node, ast.Name) \
                    and node.id == handler.name \
                    and isinstance(node.ctx, ast.Load):
                return False  # the exception object is being used
    return True


def _scan_file(path: str) -> List[Violation]:
    relpath = os.path.relpath(path, REPO)
    with open(path, "rb") as f:
        try:
            tree = ast.parse(f.read(), filename=path)
        except SyntaxError as e:
            return [(relpath, e.lineno or 0, f"<syntax error: {e.msg}>")]

    found: List[Violation] = []

    def visit(node: ast.AST, stack: Tuple[str, ...]) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            stack = stack + (node.name,)
        if isinstance(node, ast.ExceptHandler) \
                and _is_broad(node) and _is_silent(node):
            found.append((relpath, node.lineno,
                          ".".join(stack) or "<module>"))
        for child in ast.iter_child_nodes(node):
            visit(child, stack)

    visit(tree, ())
    return found


def _iter_files():
    for entry in SCAN:
        full = os.path.join(REPO, entry)
        if os.path.isfile(full):
            yield full
            continue
        for dirpath, _, filenames in os.walk(full):
            for name in sorted(filenames):
                if name.endswith(".py"):
                    yield os.path.join(dirpath, name)


def find_violations() -> List[Violation]:
    """Broad-and-silent handlers NOT covered by the allowlist."""
    out = []
    for path in _iter_files():
        for relpath, lineno, qual in _scan_file(path):
            if f"{relpath}::{qual}" not in ALLOWLIST:
                out.append((relpath, lineno, qual))
    return out


def stale_allowlist() -> List[str]:
    """Allowlist entries that no longer match any broad-and-silent site."""
    live = {f"{relpath}::{qual}"
            for path in _iter_files()
            for relpath, _, qual in _scan_file(path)}
    return sorted(ALLOWLIST - live)


def main() -> int:
    violations = find_violations()
    stale = stale_allowlist()
    for relpath, lineno, qual in violations:
        print(f"{relpath}:{lineno}: silent broad except in {qual} — "
              f"log it, narrow it, or (rarely) allowlist "
              f"'{relpath}::{qual}' in tools/check_excepts.py")
    for entry in stale:
        print(f"stale allowlist entry (no matching handler): {entry}")
    return 1 if violations or stale else 0


if __name__ == "__main__":
    sys.exit(main())
