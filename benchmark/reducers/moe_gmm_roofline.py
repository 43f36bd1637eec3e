"""Share of the HBM roofline the grouped products of a decode step reach,
in percent: the bytes of the touched held experts' matrices
(``lib/hybrid_bytes.py::held_expert_matrices`` at the run's mean touched
share, from ``runners/serve_hybrid.py``'s ``moe_*`` counters) over the
chip's bytes per second, over the device time a decode execution spends in
the ops matching ``ops`` (``op_time``'s reading: the ``gmm`` kernel that
ships with jax, ten calls a step).  Memory bounds them: ~3 token-expert
pairs an expert against 11 MB of matrices.  None without the counters or the
kernel (``lax.ragged_dot`` shows as no such op)."""

from benchmark.lib import harness, hybrid_bytes
from benchmark.lib import trace as tr


def reduce(rc, *, ops: str, module: str):
    touched = hybrid_bytes.touched_share(rc.counters, rc.config)
    if rc.trace is None or touched is None:
        return None
    ms = tr.op_time_per_module_ms(rc.trace, ops, module)
    if not ms:
        return None
    peak = harness.load_peaks(rc.device_kind)["hbm_bytes_per_s"]
    need = hybrid_bytes.held_expert_matrices(rc.config, touched)
    return 100.0 * need / peak / (ms / 1e3)
