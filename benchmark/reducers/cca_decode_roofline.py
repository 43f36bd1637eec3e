"""Share of the HBM roofline a decode step of a ZAYA1 model reaches, in
percent: the bytes the step must move (``lib/cca_bytes.py``: the matrices
outside the experts once, the held experts' matrices times the run's mean
touched share from the runner's ``moe_*`` counters, the K/V rows of the
``kv_tokens`` its ``engine.decode`` span counted and the rows it appends, the
active lanes' convolution tails read and written) over the chip's bytes per
second, over the execution's device time; median over the executions.  None
without the counters or the spans, and for a configuration of another
family.  Over 100 % means the byte count is wrong, not the chip fast."""

import statistics

from benchmark.lib import cca_bytes, harness
from benchmark.lib import program_spans as ps

ATTRS = ("lanes", "kv_tokens")


def reduce(rc, *, module: str, span: str):
    pt = ps.of(rc)
    if pt is None or not cca_bytes.reads(rc.config):
        return None
    touched = cca_bytes.touched_share(rc.counters, rc.config)
    if touched is None:
        return None
    shares = [cca_bytes.zaya_decode_step(
                  rc.config, touched_share=touched,
                  **{k: sp[3][k] for k in ATTRS}) / (mod[2] / 1e9)
              for mod, sp in ps.paired(pt, module, span)
              if all(k in sp[3] for k in ATTRS)]
    if not shares:
        return None
    peak = harness.load_peaks(rc.device_kind)["hbm_bytes_per_s"]
    return 100.0 * statistics.median(shares) / peak
