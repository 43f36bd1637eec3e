"""Device time (ms per execution of a program) in ops matched by name."""

from benchmark.lib import trace as tr


def reduce(rc, *, ops: str, module: str):
    if rc.trace is None:
        return None
    return tr.op_time_per_module_ms(rc.trace, ops, module)
