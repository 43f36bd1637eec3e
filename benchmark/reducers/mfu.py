"""Model FLOPs of a step over (device time of the step x the chip's peak),
in percent.  The FLOPs function lives in ``benchmark/lib/flops.py``."""

from benchmark.lib import flops, harness
from benchmark.lib import trace as tr


def reduce(rc, *, module: str, flops_fn: str):
    if rc.trace is None:
        return None
    step_ms = tr.module_median_ms(rc.trace, module)
    if step_ms is None:
        return None
    peak = harness.load_peaks(rc.device_kind)["bf16_flops_per_s"]
    need = getattr(flops, flops_fn)(rc.config, rc.traffic)
    return 100.0 * need / (step_ms / 1e3 * peak)
