"""Device time by component of any ``jax.profiler`` capture of a running
engine, as program x component: ms an execution and share.

    python3 benchmark/tools/scope_table.py <xplane.pb | trace dir> [--module REGEX] [--json]

The capture is what ``apex_tpu.obs.start_jax_profiler(dir)`` ...
``stop_jax_profiler()`` or ``obs.profile_on_stall(dir)`` leaves under
``dir`` (``plugins/profile/<time>/<host>.xplane.pb``), or a benchmark run's
(``benchmark/run.py --trace 1`` removes its own when it ends).  Each op of
the device's ``XLA Ops`` line goes to the innermost ``apex.<name>`` scope of
its instruction (``apex_tpu/obs/scopes.py``; the profile carries the compiled
modules), with its self time; the first and the last execution of the slice
are left out.  ``_unscoped_`` is what no scope covers; the last lines give
it by short op name inside the decode and prefill programs.  Needs ``jax`` to read the file, no device.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import device_scopes as ds  # noqa: E402
from benchmark.lib import trace as tr  # noqa: E402


def render(rows: list) -> str:
    lines = []
    for row in rows:
        lines.append(f"{row['program']}: {row['executions']} executions, "
                     f"{row['mean_ms']:.3f} ms each, busy "
                     f"{row['busy_ms']:.3f} ms")
        for comp, ms in row["components"].items():
            share = 100.0 * ms / row["busy_ms"] if row["busy_ms"] else 0.0
            lines.append(f"    {comp:<14}{ms:10.3f} ms {share:6.1f} %")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("capture")
    ap.add_argument("--module", default=r"^jit_",
                    help="programs to list (a regular expression on the "
                         "module's name)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    path = args.capture
    if os.path.isdir(path):
        path = tr.find_xplane(path)
        if path is None:
            print(f"no plugins/profile/*/*.xplane.pb under {args.capture}",
                  file=sys.stderr)
            return 2
    st = ds.parse_xplane(path)
    if st is None:
        print("the capture holds no TPU plane with XLA Modules and XLA Ops "
              "lines, or none of the modules that ran", file=sys.stderr)
        return 3
    rows = ds.table(st, args.module)
    out = {"device": st.device, "coverage_pct": ds.coverage_pct(st),
           "programs": rows,
           "unscoped_ms_by_op": {op: ns / 1e6 for op, ns in list(
               ds.unscoped_ops(st).items())[:10]}}
    if args.json:
        print(json.dumps(out))
        return 0
    print(render(rows))
    cover = out["coverage_pct"]
    print(f"scoped share of decode and prefill executions: "
          f"{'none' if cover is None else f'{cover:.2f} %'}")
    for name, ms in out["unscoped_ms_by_op"].items():
        print(f"    _unscoped_ {name:<40}{ms:10.3f} ms in the slice")
    return 0


if __name__ == "__main__":
    sys.exit(main())
