"""Device self time (ms a module execution) in the components matching
``scopes`` - the innermost ``apex.<name>`` scope of each op's instruction,
``_unscoped_`` without one - inside executions of the programs matching
``module``; ``pick="largest"`` reads the one program ``module_median`` picks,
so that a chunk's parts add up to ``prefill_chunk_ms.*`` of the same line.
Reads ``lib/device_scopes.py``."""

from benchmark.lib import device_scopes as ds


def reduce(rc, *, scopes: str, module: str, pick: str = "all"):
    st = ds.of(rc)
    return None if st is None else ds.scope_time_ms(st, scopes, module, pick)
