"""Share of the HBM roofline the grouped products of a decode step of a
gated-expert layer reach, in percent: the bytes of the three matrices of the
touched held experts (``lib/sparse_bytes.py::held_expert_matrices`` at the
run's mean touched share, from the runner's ``moe_*`` counters) over the
chip's bytes per second, over the device time a decode execution spends in
the ops matching ``ops`` (the ``gmm`` kernel that ships with jax, three
calls an expert layer).  Memory bounds them: half a token-expert pair an
expert against 47 MB of matrices.  None without the counters or the kernel
(``lax.ragged_dot`` shows as no such op), and for a configuration whose
experts are not gated."""

from benchmark.lib import harness, hybrid_bytes, sparse_bytes
from benchmark.lib import trace as tr


def reduce(rc, *, ops: str, module: str):
    touched = hybrid_bytes.touched_share(rc.counters, rc.config)
    if rc.trace is None or touched is None or "layer_types" not in rc.config:
        return None
    ms = tr.op_time_per_module_ms(rc.trace, ops, module)
    if not ms:
        return None
    peak = harness.load_peaks(rc.device_kind)["hbm_bytes_per_s"]
    need = sparse_bytes.held_expert_matrices(rc.config, touched)
    return 100.0 * need / peak / (ms / 1e3)
