"""Share of the HBM roofline the grouped products of a decode step reach, in
percent, for any family: the bytes of the touched held experts' matrices -
``bytes_fn(config, touched_share)``, a function of the module
``benchmark.lib.<bytes_module>``, at the run's mean touched share from that
module's ``touched_share(counters, config)`` - over the chip's bytes per
second, over the device time a decode execution spends in the ops matching
``ops`` (the ``gmm`` kernel that ships with jax).  The family is the metric
file's arguments, so the next family is a data file
(``moe_gmm_roofline.serve`` and ``gated_gmm_roofline.serve`` each name one
family in code).  None without the counters or the kernel
(``lax.ragged_dot`` shows as no such op), and for a configuration the byte
module cannot read."""

import importlib

from benchmark.lib import harness
from benchmark.lib import trace as tr


def reduce(rc, *, ops: str, module: str, bytes_module: str, bytes_fn: str):
    if rc.trace is None:
        return None
    lib = importlib.import_module(f"benchmark.lib.{bytes_module}")
    try:
        touched = lib.touched_share(rc.counters, rc.config)
        need = (None if touched is None
                else getattr(lib, bytes_fn)(rc.config, touched))
    except KeyError:            # another family's configuration
        return None
    ms = tr.op_time_per_module_ms(rc.trace, ops, module)
    if need is None or not ms:
        return None
    peak = harness.load_peaks(rc.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / peak / (ms / 1e3)
