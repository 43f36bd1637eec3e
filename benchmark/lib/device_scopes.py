"""Device time by component, read from the profile the harness wrote.

``apex_tpu.obs.scopes.component`` opens ``jax.named_scope("apex.<name>")``
around each part of a served model; XLA keeps the scope path as an
instruction's ``op_name`` (a fused instruction carries its root's).  The
device's ``XLA Ops`` line names each event by its instruction, and the same
``.xplane.pb`` carries, on its ``/host:metadata`` plane, the optimised HLO
module of every program that ran (``<module>(<program id>)`` -> an
``HloProto``), with every instruction's ``op_name`` in it.  This module joins
the two: each op event gets its instruction's component, its module execution
(by containment in the ``XLA Modules`` line) and its **self time** (duration
less the events nested in it on the same line: a ``while`` and the ops of its
body do not count twice).

``lib/trace.py`` keeps ``(name, start, dur)`` and drops everything else, and
the reducers' context carries no path, so, as ``lib/program_spans.py`` does,
:func:`of` finds the newest ``apexbench-trace-*`` directory itself and parses
it once a process.  ``lib/trace.load_xplane`` hands out the two lines'
events; the metadata plane has no line, so its protobuf is read here on the
wire format (five message types, field numbers from ``xplane.proto`` and
``hlo.proto``) - no other package has to be imported.

The component of an instruction, :func:`resolve`:

1. the innermost ``apex.<name>`` of its ``op_name``;
2. a fusion or call whose own ``op_name`` has none: that of the root of the
   computation it calls;
3. an instruction XLA made itself - no ``op_name``, or a parameter's name for
   one (``copy-start`` / ``slice-start`` / ``-done``: the prefetch of the
   next matrices, a layout copy of a weight) - belongs to what consumes it:
   the component of its first user that has one, a few hops on;
4. else ``_unscoped_``: an instruction traced from the program (``jit(...)/``
   in its ``op_name``) under no scope stays that, so that a missing scope
   shows in ``scope_coverage.serve`` and is not papered over.

Everything below :func:`parse_xplane` works on the plain :class:`ScopeTrace`,
so the arithmetic is tested on hand-made events and on a fixture cut from a
chip run (``fixtures/serve_scope_trace.json``, ``tools/cut_scope_fixture.py``).
A trace without a device plane (the CPU rehearsal), a profile without the
modules' protobufs and a run without a trace all read as None, and the metric
is left out of the line.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import json
import re
import statistics

from benchmark.lib import program_spans as ps
from benchmark.lib import trace as tr

UNSCOPED = "_unscoped_"
PROGRAMS = r"^jit__(decode|prefill)"
# a scope's own path element, or one a transform wraps: ``vmap(apex.sample)``
_SCOPE = re.compile(r"(?<![\w.])apex\.([A-Za-z0-9_]+)(?![\w.])")
_CALLS = ("fusion", "call", "async-start", "async-update", "async-done")
_HOPS = 6                       # users followed from an instruction XLA made


@dataclasses.dataclass
class ScopeTrace:
    """``modules``: ``(name, start_ns, dur_ns)`` of the device's ``XLA
    Modules`` line.  ``ops``: ``(instruction, start_ns, dur_ns, component)``
    of its ``XLA Ops`` line, by start."""

    modules: list
    ops: list
    device: str = ""

    @functools.cached_property
    def self_ops(self) -> list:
        """``ops`` with self times for durations (every reducer of a line
        reads them: worked out once)."""
        return self_times(self.ops)

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, obj: dict) -> "ScopeTrace":
        return cls(modules=[tuple(e) for e in obj["modules"]],
                   ops=[tuple(e) for e in obj["ops"]],
                   device=obj.get("device", ""))


def load_fixture(path: str) -> ScopeTrace:
    with open(path) as f:
        return ScopeTrace.from_json(json.load(f))


# ---- an instruction's component ---------------------------------------------


def innermost(op_name: str) -> str | None:
    """The innermost ``apex.<name>`` of a scope path, None without one."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else None


@dataclasses.dataclass
class Instruction:
    name: str
    opcode: str = ""
    op_name: str = ""
    operands: tuple = ()        # names, in this instruction's computation
    calls: tuple = ()           # names of the computations it calls


def resolve(computations: dict) -> dict:
    """``{instruction name: component}`` of one module; ``computations`` =
    ``{computation name: ([Instruction, ...], root instruction name)}``.
    The four rules of the module's docstring, in order."""
    out: dict = {}
    roots = {name: root for name, (_, root) in computations.items()}
    by_name = {i.name: i for instrs, _ in computations.values()
               for i in instrs}

    def own(instr, depth=0):
        found = innermost(instr.op_name)
        if found is None and instr.opcode in _CALLS and depth < 4:
            for comp in instr.calls:
                root = by_name.get(roots.get(comp))
                if root is not None:
                    found = own(root, depth + 1)
                    if found is not None:
                        break
        return found

    for instrs, _ in computations.values():
        users: dict = {}
        for i in instrs:
            out[i.name] = own(i)
            for o in i.operands:
                users.setdefault(o, []).append(i.name)
        for i in instrs:
            if out[i.name] is not None or "jit(" in i.op_name:
                continue
            frontier, seen = [i.name], {i.name}
            for _ in range(_HOPS):
                frontier = [u for n in frontier for u in users.get(n, ())
                            if u not in seen and not seen.add(u)]
                found = next((out[u] for u in frontier
                              if out.get(u) is not None), None)
                if found is not None or not frontier:
                    out[i.name] = found
                    break
    return {name: comp or UNSCOPED for name, comp in out.items()}


# ---- the modules a profile carries: protobuf on the wire -------------------


def _varint(buf, at: int):
    value = shift = 0
    while True:
        b = buf[at]
        at += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, at
        shift += 7


def wire_fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint or a fixed field, a ``memoryview`` for a length-delimited one."""
    buf = memoryview(buf)
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        field, kind = key >> 3, key & 7
        if kind == 0:
            value, at = _varint(buf, at)
        elif kind == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, at = int.from_bytes(buf[at:at + size], "little"), at + size
        else:
            raise ValueError(f"wire type {kind} at byte {at}")
        yield field, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _ids(value) -> list:
    """A repeated int64 field's values: packed, or one a key."""
    if isinstance(value, int):
        return [value]
    out, at = [], 0
    while at < len(value):
        v, at = _varint(value, at)
        out.append(v)
    return out


def hlo_computations(hlo_proto) -> tuple:
    """``(module name, {computation: ([Instruction], root name)})`` of a
    serialized ``xla.HloProto``: names, opcodes, ``metadata.op_name``,
    operands and called computations, nothing else of it."""
    module = next((v for f, v in wire_fields(hlo_proto) if f == 1), None)
    if module is None:
        return "", {}
    name, raw = "", []
    for f, v in wire_fields(module):                # HloModuleProto
        if f == 1:
            name = _text(v)
        elif f == 3:
            raw.append(v)
    parsed, comp_names = [], {}
    for comp in raw:                                # HloComputationProto
        cname, cid, root_id, instrs = "", None, None, []
        for f, v in wire_fields(comp):
            if f == 1:
                cname = _text(v)
            elif f == 5:
                cid = v
            elif f == 6:
                root_id = v
            elif f == 2:                            # HloInstructionProto
                i = {"name": "", "opcode": "", "op_name": "", "id": None,
                     "operands": [], "calls": []}
                for g, w in wire_fields(v):
                    if g == 1:
                        i["name"] = _text(w)
                    elif g == 2:
                        i["opcode"] = _text(w)
                    elif g == 7:                    # OpMetadata.op_name
                        i["op_name"] = next(
                            (_text(x) for h, x in wire_fields(w) if h == 2),
                            "")
                    elif g == 35:
                        i["id"] = w
                    elif g == 36:
                        i["operands"] += _ids(w)
                    elif g == 38:
                        i["calls"] += _ids(w)
                instrs.append(i)
        comp_names[cid] = cname
        parsed.append((cname, root_id, instrs))
    computations = {}
    for cname, root_id, instrs in parsed:
        names = {i["id"]: i["name"] for i in instrs}
        computations[cname] = (
            [Instruction(i["name"], i["opcode"], i["op_name"],
                         tuple(names.get(o, "") for o in i["operands"]),
                         tuple(comp_names.get(c, "") for c in i["calls"]))
             for i in instrs], names.get(root_id, ""))
    return name, computations


def profile_modules(xspace) -> dict:
    """``{"<module>(<program id>)": serialized HloProto}`` from the planes
    of a serialized ``XSpace`` that carry them (``/host:metadata``: event
    metadata whose ``Hlo Proto`` stat holds the module)."""
    out = {}
    for f, plane in wire_fields(xspace):
        if f != 1:
            continue
        fields = list(wire_fields(plane))
        if not any(g == 2 and _text(v).endswith("metadata")
                   for g, v in fields):
            continue
        for g, entry in fields:
            if g != 4:                              # event_metadata map
                continue
            meta = next((v for h, v in wire_fields(entry) if h == 2), None)
            if meta is None:
                continue
            name, proto = "", None
            for h, v in wire_fields(meta):          # XEventMetadata
                if h == 2:
                    name = _text(v)
                elif h == 5:                        # XStat.bytes_value
                    proto = next((x for k, x in wire_fields(v) if k == 6),
                                 proto)
            if proto is not None:
                out[name] = proto
    return out


# ---- the same table from a compiled program's text --------------------------

_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INSTR = re.compile(r"^(ROOT )?%?([\w.\-]+) = ")
_OPCODE = re.compile(r"\s*([\w\-]+)\(")
_REF = re.compile(r"%([\w.\-]+)")
_CALLED = re.compile(
    r"\b(?:calls|to_apply|body|condition|branch_computations|"
    r"called_computations)=\{?((?:%?[\w.\-]+(?:, )?)+)\}?")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _balanced(text: str, at: int) -> int:
    """The index past the parenthesis that closes the one at ``at``."""
    depth = 0
    for i in range(at, len(text)):
        depth += (text[i] == "(") - (text[i] == ")")
        if depth == 0:
            return i + 1
    return len(text)


def text_computations(text: str) -> dict:
    """:func:`hlo_computations`' table from ``compiled.as_text()``: the
    off-line route (``tools/lowered_programs.py`` compiles the engines'
    programs for a described chip) and the tests' (a CPU compile)."""
    computations, instrs, root, name = {}, None, "", ""
    for line in text.splitlines():
        line = line.strip()
        if instrs is None:
            m = _HEADER.match(line)
            if m:
                name, instrs, root = m.group(1), [], ""
            continue
        if line == "}":
            computations[name] = (instrs, root)
            instrs = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        rest = line[m.end():]
        if rest.startswith("("):                    # a tuple's shape
            rest = rest[_balanced(rest, 0):]
        else:
            rest = rest[rest.index(" "):] if " " in rest else ""
        op = _OPCODE.match(rest)
        if not op:
            continue
        close = _balanced(rest, op.end() - 1)
        found = _OP_NAME.search(rest, close)
        calls = tuple(c.lstrip("%") for group in _CALLED.findall(rest, close)
                      for c in group.split(", "))
        instrs.append(Instruction(
            m.group(2), op.group(1), found.group(1) if found else "",
            tuple(_REF.findall(rest[op.end():close])), calls))
        if m.group(1):
            root = m.group(2)
    return computations


# ---- the profile -> ScopeTrace ----------------------------------------------

_TEXT_NAME = re.compile(r"^%?([^\s=(]+)")


def instruction_name(event_name: str) -> str:
    """``%fusion.12 = bf16[..] fusion(..)`` (the TPU's event name, the bare
    HLO text) or ``fusion.12`` -> ``fusion.12``."""
    return _TEXT_NAME.match(event_name.strip()).group(1)


def join(modules: list, ops: list, components: dict, device: str = ""
         ) -> ScopeTrace:
    """Each op event with the component of its instruction in the program
    whose execution holds it; ``components`` = ``{program key: {instruction:
    component}}``.  An event outside every execution, or of a program the
    profile carries no module for, is ``_unscoped_``."""
    spans = sorted((s, s + d, n) for n, s, d in modules)
    starts = [s for s, _, _ in spans]
    out = []
    for name, s, d in sorted(ops, key=lambda e: (e[1], -e[2])):
        i = bisect.bisect_right(starts, s) - 1
        table = components.get(spans[i][2], {}) \
            if i >= 0 and s < spans[i][1] else {}
        instr = instruction_name(name)
        out.append((instr, s, d, table.get(instr, UNSCOPED)))
    return ScopeTrace(modules=sorted(modules, key=lambda e: e[1]), ops=out,
                      device=device)


def parse_xplane(path: str, device_index: int = 0) -> ScopeTrace | None:
    plain = tr.load_xplane(path, (), device_index)
    if not plain.modules or not plain.ops:
        return None
    with open(path, "rb") as f:
        protos = profile_modules(f.read())
    ran = {n for n, _, _ in plain.modules}
    components = {key: resolve(hlo_computations(proto)[1])
                  for key, proto in protos.items() if key in ran}
    if not components:
        return None
    return join(plain.modules, plain.ops, components, plain.device)


@functools.lru_cache(maxsize=1)
def load() -> ScopeTrace | None:
    trace_dir = ps.find_trace_dir()
    path = tr.find_xplane(trace_dir) if trace_dir else None
    return parse_xplane(path) if path else None


def of(rc) -> ScopeTrace | None:
    """The scoped op events of the run that ``rc`` (a ``ReduceContext``)
    reduces; None when that run made no trace."""
    return load() if rc.trace is not None else None


# ---- the arithmetic ---------------------------------------------------------


def self_times(ops: list) -> list:
    """``ops`` (by start, an enclosing event before what it encloses) with
    each duration replaced by the event's self time: its duration less the
    events directly nested in it."""
    out = [list(e) for e in ops]
    stack = []                                      # (end, index)
    for i, (_, s, d, _) in enumerate(ops):
        while stack and s >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][2] -= d
        stack.append((s + d, i))
    return [tuple(e) for e in out]


def executions(st: ScopeTrace, module: str = PROGRAMS,
               key=lambda instr, comp: comp) -> list:
    """``[(program, start, dur, {component: self ns}), ...]``: the
    executions of the programs matching ``module`` with the self time of
    the ops each holds by component (by ``key(instruction, component)``),
    by start.  The first and the last execution of the slice - whatever
    their program - are left out once it holds three or more: a slice that
    starts or stops while the device is busy holds only part of their ops."""
    mods = sorted(st.modules, key=lambda e: e[1])
    if len(mods) >= 3:
        mods = mods[1:-1]
    starts = [s for _, s, _ in mods]
    sums = [dict() for _ in mods]
    for instr, s, d, comp in st.self_ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < mods[i][1] + mods[i][2]:
            k = key(instr, comp)
            sums[i][k] = sums[i].get(k, 0.0) + d
    rx = re.compile(module)
    return [(n, s, d, by) for (n, s, d), by in zip(mods, sums)
            if rx.search(n)]


def unscoped_ops(st: ScopeTrace, module: str = PROGRAMS) -> dict:
    """``{short op name: self ns}`` of what no scope covers inside the whole
    executions of the programs matching ``module``, largest first: the
    remainder ``scope_coverage.serve`` leaves."""
    sums: dict = {}
    for _, _, _, by in executions(
            st, module, lambda instr, comp: tr.short_name(instr)
            if comp == UNSCOPED else None):
        for op, ns in by.items():
            if op is not None:
                sums[op] = sums.get(op, 0.0) + ns
    return dict(sorted(sums.items(), key=lambda kv: -kv[1]))


def largest_program(st: ScopeTrace, module: str) -> tuple:
    """``(program, median ns)`` as ``module_median``'s ``pick="largest"``
    reads them: of the distinct programs matching ``module``, the one with
    the largest median duration over all its executions; ``(None, 0.0)``
    where none matches."""
    groups = tr.module_groups(tr.Trace(modules=st.modules, ops=[], spans=[]),
                              module)
    return max(((n, statistics.median(d)) for n, d in groups.items()),
               key=lambda nm: nm[1], default=(None, 0.0))


def scope_time_ms(st: ScopeTrace, scopes: str, module: str,
                  pick: str = "all") -> float | None:
    """Self time, ms a module execution, in components matching ``scopes``
    inside the whole executions of the programs matching ``module``: their
    mean.  ``pick="largest"``: of :func:`largest_program` alone, and of it
    the one whole execution that is the median ``module_median`` reads, or
    the nearest shorter one - a chunk's work follows its offset, so its
    parts are read off one chunk, and they add up to no more than that
    median.  None where no such execution is whole, or none holds such a
    component."""
    runs = executions(st, module)
    if pick == "largest":
        program, median = largest_program(st, module)
        runs = sorted((r for r in runs if r[0] == program),
                      key=lambda r: r[2])
        runs = [r for r in runs if r[2] <= median][-1:] or runs[:1]
    rx = re.compile(scopes)
    found = [ns for _, _, _, by in runs for comp, ns in by.items()
             if rx.search(comp)]
    return sum(found) / len(runs) / 1e6 if found else None


def coverage_pct(st: ScopeTrace, module: str = PROGRAMS) -> float | None:
    """% of the self time inside executions of the programs matching
    ``module`` that has a component; None where none has (a program from
    before the scopes, or one a compile cache kept from then)."""
    by: dict = {}
    for _, _, _, parts in executions(st, module):
        for comp, ns in parts.items():
            by[comp] = by.get(comp, 0.0) + ns
    scoped = sum(ns for comp, ns in by.items() if comp != UNSCOPED)
    return 100.0 * scoped / sum(by.values()) if scoped else None


def table(st: ScopeTrace, module: str = r"^jit_") -> list:
    """Program x component over the whole executions: ``[{"program",
    "executions", "mean_ms" (their duration), "busy_ms", "components":
    {name: ms an execution}}, ...]``, the longest program first."""
    groups: dict = {}
    for name, _, d, by in executions(st, module):
        groups.setdefault(name, []).append((d, by))
    rows = []
    for name, runs in groups.items():
        comps: dict = {}
        for _, by in runs:
            for comp, ns in by.items():
                comps[comp] = comps.get(comp, 0.0) + ns
        comps = {c: ns / len(runs) / 1e6 for c, ns in sorted(
            comps.items(), key=lambda kv: -kv[1])}
        rows.append({"program": name, "executions": len(runs),
                     "mean_ms": statistics.fmean(d for d, _ in runs) / 1e6,
                     "busy_ms": sum(comps.values()), "components": comps})
    return sorted(rows, key=lambda r: -r["mean_ms"])
