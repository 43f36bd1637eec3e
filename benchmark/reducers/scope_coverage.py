"""% of the device's self time inside executions of the programs matching
``module`` that has a component (an ``apex.<name>`` scope on its
instruction, or on what consumes an instruction XLA made itself).  Reads
``lib/device_scopes.py``."""

from benchmark.lib import device_scopes as ds


def reduce(rc, *, module: str = ds.PROGRAMS):
    st = ds.of(rc)
    return None if st is None else ds.coverage_pct(st, module)
