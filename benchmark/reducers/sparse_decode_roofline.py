"""Share of the HBM roofline a decode step of a dots3-note model reaches, in
percent: the bytes the step must move (``lib/sparse_bytes.py``: the matrices
outside the experts once, the held experts' matrices times the run's mean
touched share from the runner's ``moe_*`` counters, and the selector keys,
selected latent rows and window rows that its ``engine.decode`` span
counted, with the rows it appends) over the chip's bytes per second, over
the execution's device time; median over the executions.  None without the
counters, the spans or the spans' row counts (any other model's run, and a
commit before the latent cache).  Over 100 % means the byte count is wrong,
not the chip fast."""

import statistics

from benchmark.lib import harness, hybrid_bytes, sparse_bytes
from benchmark.lib import program_spans as ps

ROWS = ("index_rows", "attended_rows", "window_rows")


def reduce(rc, *, module: str, span: str):
    touched = hybrid_bytes.touched_share(rc.counters, rc.config)
    pt = ps.of(rc)
    if pt is None or touched is None or "layer_types" not in rc.config:
        return None
    shares = [sparse_bytes.dots3_decode_step(
                  rc.config, lanes=sp[3]["lanes"], touched_share=touched,
                  **{k: sp[3][k] for k in ROWS}) / (mod[2] / 1e9)
              for mod, sp in ps.paired(pt, module, span)
              if all(k in sp[3] for k in ROWS)]
    if not shares:
        return None
    peak = harness.load_peaks(rc.device_kind)["hbm_bytes_per_s"]
    return 100.0 * statistics.median(shares) / peak
