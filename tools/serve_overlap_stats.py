"""One run of a benchmark cell with the scheduler's ``overlap_stats()``
printed when the runner closes it (ISSUE 36): how many decode steps were
enqueued ahead of the host's reads, what settled early and why, how many
lanes were computed for a stream that had ended.

    chiprun -- python3 tools/serve_overlap_stats.py --workload <cell> \
        --seed <n> --seconds 50 --trace 1

Arguments are ``benchmark/run.py``'s; its lines go to stdout untouched, the
stats to stderr as ``OVERLAP_STATS {...}``.  The benchmark's manifest has
no entry for them (a ``benchmark`` PR's to add), so this is how a chip run
reads them.
"""

import json
import os
import runpy
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    sys.path.insert(0, ROOT)
    from apex_tpu.serving import ContinuousBatchingScheduler

    close = ContinuousBatchingScheduler.close

    def close_and_report(self):
        close(self)
        print("OVERLAP_STATS " + json.dumps(self.overlap_stats()),
              file=sys.stderr, flush=True)

    ContinuousBatchingScheduler.close = close_and_report
    sys.argv = ["benchmark/run.py"] + sys.argv[1:]
    runpy.run_path(os.path.join(ROOT, "benchmark", "run.py"),
                   run_name="__main__")


if __name__ == "__main__":
    main()
