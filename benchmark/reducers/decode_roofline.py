"""Share of the HBM roofline a decode step reaches, in percent: the bytes
the step must read (``lib/bytes.py``: the weights once, plus the cached
rows of the ``kv_tokens`` its ``engine.decode`` span counted) over the
chip's bytes per second, over the execution's device time; median over the
executions.  Memory bounds a decode step (16 tokens through 3.6 G
parameters: 0.12 Tflop against 7.3 GB).  Over 100 % means the byte count
is wrong, not the chip fast."""

import statistics

from benchmark.lib import bytes as by
from benchmark.lib import harness
from benchmark.lib import program_spans as ps


def reduce(rc, *, module: str, span: str, bytes_fn: str):
    pt = ps.of(rc)
    if pt is None:
        return None
    need = getattr(by, bytes_fn)
    shares = [need(rc.config, sp[3]["kv_tokens"]) / (mod[2] / 1e9)
              for mod, sp in ps.paired(pt, module, span)
              if "kv_tokens" in sp[3]]
    if not shares:
        return None
    peak = harness.load_peaks(rc.device_kind)["hbm_bytes_per_s"]
    return 100.0 * statistics.median(shares) / peak
