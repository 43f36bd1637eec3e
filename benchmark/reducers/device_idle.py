"""Share of the traced window (first device op to last) with no op
running, in percent."""

from benchmark.lib import trace as tr


def reduce(rc):
    if rc.trace is None or not rc.trace.ops:
        return None
    busy, window, _ = tr.busy_union(rc.trace.ops)
    return 100.0 * (1.0 - busy / window)
