"""Share of the HBM roofline a decode step of a Nemotron-H model reaches,
in percent: the bytes the step must move (``lib/hybrid_bytes.py``: the
matrices outside the experts once, the held experts' matrices times the
run's mean touched share from ``runners/serve_hybrid.py``'s ``moe_*``
counters, the recurrent state of the ``lanes`` its ``engine.decode`` span
counted, read and written, and the cached rows of its ``kv_tokens``) over
the chip's bytes per second, over the execution's device time; median over
the executions.  Memory bounds the step: 64 tokens through 12 G active
parameters' worth of matrices.  None without the counters (a dense
model's run, whose spans carry the same attributes) or the spans.  Over 100 % means the byte count is
wrong, not the chip fast."""

import statistics

from benchmark.lib import harness
from benchmark.lib import hybrid_bytes
from benchmark.lib import program_spans as ps


def reduce(rc, *, module: str, span: str):
    touched = hybrid_bytes.touched_share(rc.counters, rc.config)
    pt = ps.of(rc)
    if pt is None or touched is None:
        return None
    shares = [hybrid_bytes.nemotron_h_decode_step(
                  rc.config, lanes=sp[3]["lanes"],
                  kv_tokens=sp[3]["kv_tokens"], touched_share=touched)
              / (mod[2] / 1e9)
              for mod, sp in ps.paired(pt, module, span)]
    if not shares:
        return None
    peak = harness.load_peaks(rc.device_kind)["hbm_bytes_per_s"]
    return 100.0 * statistics.median(shares) / peak
