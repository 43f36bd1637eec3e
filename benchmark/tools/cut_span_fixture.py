"""One traced run of a serving cell on the chip, the split of its idle
step printed, and a small fixture of the program's spans cut from it.

    python3 benchmark/tools/cut_span_fixture.py --seed 5 \
        --out benchmark/fixtures/serve_program_trace.json

``run.py`` removes its trace directory when it ends and ``--keep-trace``
keeps only the benchmark's two spans, so the run is made in this process
(``benchmark.run.main``) and the parsed events are taken from
``lib/program_spans.py``'s cache afterwards.  The fixture holds ``--steps``
whole ``serving.step`` spans from the middle of the slice with every
program execution from the one before the first of them to the one after
the last, and the program spans, stats included, that overlap those.
The line printed last gives, in ms a step, the gaps between programs by
the span they fell under — the two ``idle_*`` metrics and the remainder
under no program span, which is the caller's loop and has no metric.
"""

import argparse
import bisect
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import program_spans as ps  # noqa: E402


def cut(pt: ps.ProgramTrace, steps: int, skip: int = None) -> ps.ProgramTrace:
    """``steps`` whole scheduler steps after the first ``skip`` (from the
    middle when None), as recorded: the device's clock is left as it is."""
    whole = ps.whole(pt)
    first = max((len(whole) - steps) // 2, 0) if skip is None else skip
    chosen = whole[first:first + steps]
    t0, t1 = chosen[0][1], chosen[-1][1] + chosen[-1][2]
    lead = ps.device_lead_ns(pt)
    mods = sorted(pt.modules, key=lambda e: e[1])
    starts = [s + lead for _, s, _ in mods]
    mods = mods[max(bisect.bisect_left(starts, t0) - 1, 0):
                bisect.bisect_left(starts, t1) + 1]
    lo, hi = mods[0][1] + lead, mods[-1][1] + mods[-1][2] + lead
    spans = [e for e in pt.spans if e[1] < hi and e[1] + e[2] > lo]
    return ps.ProgramTrace(modules=mods, spans=spans, device=pt.device)


def idle_split(pt: ps.ProgramTrace) -> dict:
    by_name, steps = ps.step_gaps(pt)
    per_step = {k: v / steps / 1e6 for k, v in sorted(
        by_name.items(), key=lambda kv: -kv[1])}
    return {"whole_steps": steps,
            "device_clock_lead_ms": ps.device_lead_ns(pt) / 1e6,
            "gaps_between_programs_ms_per_step": sum(per_step.values()),
            "idle_no_span_ms": per_step.get(ps.NO_SPAN, 0.0),
            "by_innermost_span_ms_per_step": per_step,
            "host_ms_per_step_by_span": {
                name: ps.span_ms(pt, f"^{re.escape(name)}$")
                for name in sorted({e[0] for e in pt.spans})}}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="mistral-7b-l16.chat-closed")
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--skip", type=int,
                    help="whole steps to pass over before the cut "
                         "(default: cut from the middle)")
    ap.add_argument("--out")
    args, extra = ap.parse_known_args()

    from benchmark import run

    rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                   "--seconds", str(args.seconds), "--trace", "1", *extra])
    if rc != 0:
        sys.exit(rc)
    pt = ps.load()
    if pt is None:
        sys.exit("the trace holds no device plane or no program span")
    if args.out:
        small = cut(pt, args.steps, args.skip)
        with open(args.out, "w") as f:
            json.dump(small.to_json(), f, separators=(",", ":"))
        print(f"{len(small.modules)} modules, {len(small.spans)} spans -> "
              f"{os.path.getsize(args.out)} bytes", file=sys.stderr)
    print(json.dumps({"idle_split": idle_split(pt)}))


if __name__ == "__main__":
    main()
