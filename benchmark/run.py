"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Looks the cell up in the manifest, loads its configuration and traffic
files, imports the runner the traffic file names, and prints one JSON
object as the last line of standard output.  Without an accelerator (or
with fewer chips than the cell asks for) it exits non-zero and prints no
result; ``--rehearse`` is the tests' explicit CPU path and stamps its line.
"""

import time

_T0 = time.perf_counter()           # process start, for setup_s

import argparse                     # noqa: E402
import dataclasses                  # noqa: E402
import importlib                    # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@dataclasses.dataclass
class Context:
    """What a runner gets: the cell's files and arguments, the set-up
    clock, the compile counter and the tracer factory."""

    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    chips: int
    setup: object
    compiles: object

    def tracer(self, span_names):
        from benchmark.lib.harness import TraceSlice

        return TraceSlice(
            self.trace,
            self.traffic.get("trace_start_share", 0.3) * self.seconds,
            min(self.traffic.get("trace_slice_s", 3.0), self.seconds / 2),
            span_names)

    def device_report(self) -> dict:
        from benchmark.lib.harness import device_report

        return device_report(self.chips)


def _load(path: str) -> dict:
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def _for_cell(metrics: list, cell: str) -> list:
    return [m for m in metrics
            if "workloads" not in m or cell in m["workloads"]]


def _layer_metrics(manifest: dict, cell: str, rc) -> dict:
    """Evaluate each per-layer metric of the cell through its own reader:
    ``layer_metrics/<name>.json`` names a reducer module and its
    arguments.  A reader that finds nothing returns None and the metric is
    left out."""
    out = {}
    for m in _for_cell(manifest["per_layer"], cell):
        spec = _load(f"benchmark/layer_metrics/{m['name']}.json")
        reducer = importlib.import_module(
            f"benchmark.reducers.{spec['reducer']}")
        value = reducer.reduce(rc, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default="BENCHMARK.json",
                    help="tests only: another manifest, relative to the "
                         "checkout")
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: run on the CPU with interpreted "
                         "kernels; the line is stamped as a rehearsal")
    ap.add_argument("--keep-trace", metavar="FILE",
                    help="tools only: also write the reduced events of a "
                         "traced run (lib/trace.py's Trace, gzipped JSON) "
                         "there, e.g. to cut a test fixture from")
    args = ap.parse_args(argv)

    manifest = _load(args.manifest)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        print(f"no cell {args.workload!r} in {args.manifest}: "
              f"{sorted(cells)}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    config_entry = next(c for c in manifest["configs"]
                        if c["name"] == cell["config"])
    config = _load(config_entry["file"])
    traffic = _load(f"benchmark/traffic/{cell['traffic']}.json")

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["APEX_TPU_KERNELS"] = "interpret"
    import jax

    from benchmark.lib import harness

    setup = harness.SetupClock(_T0)
    setup.mark("imports")
    if not args.rehearse:
        from apex_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache(ROOT)
    devices = jax.devices()
    platform = devices[0].platform
    setup.mark("backend_start")
    if not args.rehearse and (platform != "tpu"
                              or len(devices) < cell["chips"]):
        print(f"cell {cell['name']} needs {cell['chips']} TPU chip(s); JAX "
              f"found {len(devices)} x {platform}.  No result.",
              file=sys.stderr)
        return 3

    ctx = Context(config=config, traffic=traffic, seed=args.seed,
                  seconds=args.seconds, trace=bool(args.trace),
                  chips=cell["chips"], setup=setup,
                  compiles=harness.CompileCounter())
    runner = importlib.import_module(
        f"benchmark.runners.{traffic['runner']}")
    res = runner.run(ctx)
    tracer = res["tracer"]
    try:
        line = {"correct": bool(res["correct"]),
                "attempted": int(res["attempted"]),
                "failed": int(res["failed"]), "metrics": {},
                "device": res["device"]}
        if args.trace:
            from benchmark.lib import trace as tr
            from benchmark.reducers import ReduceContext

            trace = tracer.load()
            if trace is not None and args.keep_trace:
                import gzip

                with gzip.open(args.keep_trace, "wt") as f:
                    json.dump(trace.to_json(), f)
            line["metrics"] = _layer_metrics(
                manifest, cell["name"],
                ReduceContext(trace, res["counters"], config, traffic,
                              devices[0].device_kind))
            if trace is not None and trace.ops:
                busy, window, _ = tr.busy_union(trace.ops)
                line["device"]["busy_s"] = busy / 1e9
                line["device"]["window_s"] = window / 1e9
                line["breakdown"] = {"device_ops": tr.top_ops(trace),
                                     "idle_gaps": tr.attribute_gaps(trace)}
        else:
            values = dict(res["end_to_end"], setup_s=setup.setup_s)
            for m in _for_cell(manifest["end_to_end"], cell["name"]):
                line["metrics"][m["name"]] = {
                    "value": float(values[m["name"]]), "unit": m["unit"]}
    finally:
        tracer.close()
    if args.rehearse:
        line["rehearsal"] = True
    print(json.dumps({"workload": cell["name"], "seed": args.seed,
                      "seconds": args.seconds, "notes": res["notes"]},
                     default=str))
    print(json.dumps({"jax_compile_events_s": {
        k: round(v, 3) for k, v in ctx.compiles.seconds.items()}}))
    print(setup.line())
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
