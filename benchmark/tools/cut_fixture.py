"""Cut a small test fixture from the events a traced run kept
(``run.py --keep-trace FILE``): the first ``--modules`` program executions,
every op of at least ``--min-us`` inside them plus every op whose short
name matches ``--keep``, names shortened to ``--name-chars`` characters,
and the host spans that overlap.

    python3 benchmark/tools/cut_fixture.py kept.json.gz fixture.json
"""

import argparse
import gzip
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import trace as tr  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("kept")
    ap.add_argument("out")
    ap.add_argument("--modules", type=int, default=3)
    ap.add_argument("--min-us", type=float, default=100.0)
    ap.add_argument("--keep", default="^(flash_attention|fused_lm_head)_")
    ap.add_argument("--name-chars", type=int, default=96)
    args = ap.parse_args()
    with gzip.open(args.kept, "rt") as f:
        full = tr.Trace.from_json(json.load(f))
    modules = sorted(full.modules, key=lambda e: e[1])[:args.modules]
    t0, t1 = modules[0][1], modules[-1][1] + modules[-1][2]
    keep = re.compile(args.keep)
    ops = [(n[:args.name_chars], s, d) for n, s, d in full.ops
           if t0 <= s < t1 and (d >= args.min_us * 1e3
                                or keep.search(tr.short_name(n)))]
    spans = [e for e in full.spans if e[1] < t1 and e[1] + e[2] > t0]
    cut = tr.Trace(modules=modules, ops=ops, spans=spans,
                   device=full.device)
    with open(args.out, "w") as f:
        json.dump(cut.to_json(), f, separators=(",", ":"))
    print(f"{len(modules)} modules, {len(ops)} ops, {len(spans)} spans -> "
          f"{os.path.getsize(args.out)} bytes")


if __name__ == "__main__":
    main()
