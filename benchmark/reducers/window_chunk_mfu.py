"""Share of the chip's bf16 peak that the prompt chunks of the largest
bucket of a Mellum model reach, in percent: the model's operations for each
traced chunk (``lib/window_flops.py`` at the ``tokens`` and ``offset`` its
``engine.prefill_chunk`` span carries) over the device time of the
``jit__prefill`` execution it dispatched, over the peak; median over the
chunks.  The count is the model's, whatever implements it, so the share
cannot pass 100 %: it is the cell's share of a whole step.  None without the
spans or their ``offset``, and for a configuration of another family."""

import statistics

from benchmark.lib import harness, window_bytes, window_flops
from benchmark.lib import program_spans as ps


def reduce(rc, *, module: str, span: str):
    pt = ps.of(rc)
    if pt is None or not window_bytes.reads(rc.config):
        return None
    largest = rc.traffic["engine"]["prefill_len"]
    rates = [window_flops.mellum_prefill_chunk(
                 rc.config, tokens=sp[3]["tokens"], offset=sp[3]["offset"])
             / (mod[2] / 1e9)
             for mod, sp in ps.paired(pt, module, span)
             if sp[3].get("bucket") == largest and "offset" in sp[3]]
    if not rates:
        return None
    peak = harness.load_peaks(rc.device_kind)["bf16_flops_per_s"]
    return 100.0 * statistics.median(rates) / peak
