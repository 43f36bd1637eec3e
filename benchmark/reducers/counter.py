"""A count the runner kept over the whole window, times ``scale``."""


def reduce(rc, *, key: str, scale: float = 1.0):
    value = rc.counters.get(key)
    return None if value is None else value * scale
