"""Median device duration (ms) of a compiled program on ``XLA Modules``."""

from benchmark.lib import trace as tr


def reduce(rc, *, module: str, pick: str = "all"):
    if rc.trace is None:
        return None
    return tr.module_median_ms(rc.trace, module, pick)
