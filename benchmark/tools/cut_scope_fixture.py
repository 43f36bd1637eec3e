"""A small fixture of scoped device ops, cut from one traced run of a
serving cell on the chip - or from a capture of one that was kept.

    python3 benchmark/tools/cut_scope_fixture.py --seed 5 \\
        --out benchmark/fixtures/serve_scope_trace.json
    python3 benchmark/tools/cut_scope_fixture.py --capture <xplane.pb> --out ...

``run.py`` removes its trace directory when it ends, so, as
``tools/cut_span_fixture.py`` does, the run is made in this process
(``benchmark.run.main``) and the joined events are taken from
``lib/device_scopes.py``'s cache afterwards.  The fixture holds
``--executions`` consecutive executions of the engine's programs
(``jit__decode`` / ``jit__prefill``) around the first execution of the
largest ``jit__prefill`` program past the slice's middle, what ran between
them, one execution more on either side (the reducers leave a slice's first
and last out), and every op event inside them with its component.  The line printed last
is the cut's program x component table.
"""

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import device_scopes as ds  # noqa: E402

PREFILL = r"^jit__prefill"


def _whole(x: float):
    return int(x) if float(x).is_integer() else x


def cut(st: ds.ScopeTrace, count: int, skip: int = None) -> ds.ScopeTrace:
    """``count`` consecutive executions of the engine's programs after the
    first ``skip`` (around the largest prefill program's first execution
    past the middle when None), what ran between them and one execution
    more on either side, with the ops inside them, as recorded."""
    mods = sorted(st.modules, key=lambda e: e[1])
    rx = re.compile(ds.PROGRAMS)
    engine = [i for i, m in enumerate(mods)
              if rx.search(m[0]) and 0 < i < len(mods) - 1]
    program, _ = ds.largest_program(st, PREFILL)
    mid = next((k for k, i in enumerate(engine)
                if mods[i][0] == program and i >= len(mods) // 2),
               len(engine) // 2)
    first = max(mid - count // 3, 0) if skip is None else skip
    picked = engine[first:first + count]
    chosen = mods[picked[0] - 1:picked[-1] + 2]
    t0, t1 = chosen[0][1], chosen[-1][1] + chosen[-1][2]
    return ds.ScopeTrace(
        modules=[(n, _whole(s), _whole(d)) for n, s, d in chosen],
        ops=[(n, _whole(s), _whole(d), c) for n, s, d, c in st.ops
             if t0 <= s < t1],
        device=st.device)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="mistral-7b-l16.chat-closed")
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--executions", type=int, default=6)
    ap.add_argument("--skip", type=int,
                    help="engine executions to pass over before the cut "
                         "(default: cut around the largest prefill)")
    ap.add_argument("--capture",
                    help="cut from this kept .xplane.pb and run "
                         "nothing")
    ap.add_argument("--out")
    args, extra = ap.parse_known_args()

    if args.capture:
        st = ds.parse_xplane(args.capture)
    else:
        from benchmark import run

        rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                       "--seconds", str(args.seconds), "--trace", "1",
                       *extra])
        if rc != 0:
            sys.exit(rc)
        st = ds.load()
    if st is None:
        sys.exit("the trace holds no device plane or no module of the "
                 "programs that ran")
    small = cut(st, args.executions, args.skip)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(small.to_json(), f, separators=(",", ":"))
        print(f"{len(small.modules)} executions, {len(small.ops)} ops -> "
              f"{os.path.getsize(args.out)} bytes", file=sys.stderr)
    print(json.dumps({"coverage_pct": ds.coverage_pct(small),
                      "programs": ds.table(small)}))


if __name__ == "__main__":
    main()
