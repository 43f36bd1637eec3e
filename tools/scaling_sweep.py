"""Scaling-sweep harness: one table across mesh shapes and problem sizes.

TPU analog of the reference's
``tests/L0/run_transformer/gpt_scaling_test.py`` (sweep sizes / GPU counts,
record per-step times).  Two sweep axes, matching what this environment can
actually measure honestly:

- ``--mode tp`` (default off-chip): compile the full GPT-1.3B TP training
  step at tp ∈ {1,2,4,8} on the virtual CPU mesh (``bench.tp_dryrun``) and
  tabulate what the compiler proves — params/shard, per-chip memory, and
  the collective plan.  Step *times* on the CPU mesh say nothing about TPU
  and are deliberately not reported (see memory: CPU microbench ranks
  diverge from TPU).
- ``--mode batch`` (on the real chip): sweep batch × seq on a single-chip
  config with ``bench.run_config``'s marginal-timing protocol and tabulate
  tokens/s + MFU.

Usage (``--mode tp`` needs the CPU handle: ``bench.tp_dryrun`` raises when
fewer than ``tp`` devices are visible, it no longer re-executes itself):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python tools/scaling_sweep.py --mode tp
  python tools/scaling_sweep.py --mode batch --model medium \
      --batches 2,4,8 --seqs 512,1024
  python tools/scaling_sweep.py --mode both --json sweep.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


def sweep_tp(tps) -> list[dict]:
    return [bench.tp_dryrun(tp) for tp in tps]


def print_tp_table(rows) -> None:
    print("\n== TP scaling (GPT-2 1.3B, compile-proven; CPU-mesh memory "
          "numbers are layout approximations) ==")
    hdr = (f"{'tp':>3} {'params/shard':>13} {'per-chip GB':>12} "
           f"{'AG':>4} {'RS':>4} {'AR':>4} {'fits 16GB':>10}")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        c = r["collective_plan"]
        print(f"{r['config']['tp']:>3} "
              f"{r['params_per_shard_b']:>12.3f}B "
              f"{r['per_chip_gb']['total']:>12.2f} "
              f"{c['all-gather']:>4} {c['reduce-scatter']:>4} "
              f"{c['all-reduce']:>4} "
              f"{str(r['fits_v5e_16gb']):>10}")


def sweep_batch(model: str, batches, seqs, steps: int | None) -> list[dict]:
    rows = []
    for seq in seqs:
        for b in batches:
            try:
                r = bench.run_config(model, batch=b, seq=seq, steps=steps)
            except Exception as e:  # noqa: BLE001 — record, keep sweeping
                r = {"config": {"model": model, "batch": b, "seq": seq},
                     "error": f"{type(e).__name__}: {e}"[:200]}
            rows.append(r)
    return rows


def print_batch_table(rows) -> None:
    print("\n== batch x seq scaling (measured, marginal timing) ==")
    hdr = (f"{'model':>8} {'batch':>6} {'seq':>6} {'step ms':>9} "
           f"{'tokens/s':>10} {'MFU':>7}")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        c = r["config"]
        if "error" in r:
            print(f"{c['model']:>8} {c['batch']:>6} {c['seq']:>6} "
                  f"  {r['error']}")
            continue
        print(f"{c['model']:>8} {c['batch']:>6} {c['seq']:>6} "
              f"{r['step_time_ms']:>9.1f} {r['value']:>10.0f} "
              f"{r.get('mfu', 0.0):>7.4f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["tp", "batch", "both"], default="tp")
    ap.add_argument("--tps", default="1,2,4,8")
    ap.add_argument("--model", default="medium",
                    help="bench model card for --mode batch")
    ap.add_argument("--batches", default="2,4,8")
    ap.add_argument("--seqs", default="512,1024")
    ap.add_argument("--steps", type=int, default=0,
                    help="timing steps per point (default: model card)")
    ap.add_argument("--json", default=None,
                    help="also dump all rows to this file")
    args = ap.parse_args()

    results = {}
    if args.mode in ("tp", "both"):
        rows = sweep_tp([int(t) for t in args.tps.split(",")])
        print_tp_table(rows)
        results["tp"] = rows
    if args.mode in ("batch", "both"):
        rows = sweep_batch(args.model,
                           [int(b) for b in args.batches.split(",")],
                           [int(s) for s in args.seqs.split(",")],
                           args.steps or None)
        print_batch_table(rows)
        results["batch"] = rows
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
        print(f"\nwrote {args.json}")


if __name__ == "__main__":
    main()
