"""Run one cell several times, one process per run, and report spreads.

    python3 benchmark/tools/measure.py --workload <cell> --seconds <s> \
        --seeds 11,12,13 --sets 2 [--trace-seed 5] --out chiprun_out/<tag>

The parent never touches JAX (a chip belongs to one process).  Every
run's output is kept under ``--out``; the summary gives, for each
end-to-end metric, each set's median and spread (distance between the
quartiles over the median) — the numbers the bounds are set from.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib.stats import iqr_share  # noqa: E402  (no JAX in there)


def one_run(workload, seed, seconds, trace, out, tag, extra=()):
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace), *extra]
    t0 = time.perf_counter()
    with open(os.path.join(out, f"{tag}.err"), "w") as err:
        proc = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                              stderr=err, text=True)
    wall = time.perf_counter() - t0
    with open(os.path.join(out, f"{tag}.out"), "w") as f:
        f.write(proc.stdout)
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1]) if proc.returncode == 0 and lines else None
    print(json.dumps({"tag": tag, "rc": proc.returncode,
                      "wall_s": round(wall, 1), "last": last}), flush=True)
    return last


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--cold-seed", type=int)
    ap.add_argument("--trace-seed", type=int)
    ap.add_argument("--keep-trace", action="store_true",
                    help="keep the traced run's reduced events in --out")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    if args.cold_seed is not None:
        first = one_run(args.workload, args.cold_seed, args.seconds, 0,
                        args.out, "first")
        if not first or not first["correct"]:
            sys.exit("the first run failed or was not correct: stopping")
    sets = []
    for k in range(args.sets):
        runs = [one_run(args.workload, s, args.seconds, 0, args.out,
                        f"set{k}-seed{s}") for s in seeds]
        sets.append([r for r in runs if r])
    if args.trace_seed is not None:
        one_run(args.workload, args.trace_seed, args.seconds, 1, args.out,
                "trace", ("--keep-trace", os.path.join(
                    args.out, "kept_trace.json.gz")) if args.keep_trace
                else ())
    summary = {}
    names = sorted({m for runs in sets for r in runs for m in r["metrics"]})
    for name in names:
        per_set = [[r["metrics"][name]["value"] for r in runs]
                   for runs in sets]
        summary[name] = {
            "medians": [statistics.median(v) for v in per_set if v],
            "spreads": [iqr_share(v) if len(v) > 1 else None
                        for v in per_set],
            "values": per_set}
    print(json.dumps({"summary": summary}), flush=True)
    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)


if __name__ == "__main__":
    main()
