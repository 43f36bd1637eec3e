"""The single import site for the jax APIs this codebase has seen move.

Installed, and the only target: jax / jaxlib 0.9.0.  ``shard_map`` lives
at the top level there and its replication check is ``check_vma``; the
jit cache probe is ``_cache_size()``.  Every call site imports these from
here, so the next rename is a one-line change in this module:

    from apex_tpu.utils.compat import NO_REP_CHECK, shard_map
    f = shard_map(fn, mesh=mesh, in_specs=..., out_specs=...,
                  **NO_REP_CHECK)
"""

from __future__ import annotations

from jax import shard_map


def compile_count(fn) -> int:
    """Number of compiled variants a ``jax.jit``-wrapped function holds.

    The serving contract ("the decode step compiles exactly ONCE",
    "prefill compiles are bounded by the bucket table") is asserted in
    tier-1 through jit cache statistics, and the probe is private API.
    This helper is the ONE place that knows its spelling — every
    compile-count assertion (``DecodeEngine.decode_compiles()`` /
    ``prefill_compiles()``, bench regression guards, tests) goes
    through it.
    """
    return int(fn._cache_size())


# Disabling the replication checker is the repo-wide default for
# shard_map: the collective helpers mix per-leaf specs and produce
# outputs made replicated by explicit psum/all_gather, which the
# checker rejects conservatively.
NO_REP_CHECK = {"check_vma": False}

#: Mesh axis name of the serving tensor-parallel mesh.  Deliberately
#: the same spelling as ``parallel_state.TENSOR_PARALLEL_AXIS`` so the
#: tensor_parallel layers' ``tp_world_size(axis_name)`` probe binds to
#: it inside the serving shard_map exactly as it does under the
#: training mesh — without importing the training-side global mesh
#: state into a serving process.
SERVING_TP_AXIS = "tp"


def devices_available(n: int) -> bool:
    """Whether ``n`` devices are visible to jax (the serving-tp
    device-count guard; pair with :func:`device_count_skip_reason` for
    the human-readable skip message)."""
    import jax

    return len(jax.devices()) >= int(n)


def device_count_skip_reason(n: int) -> str:
    """One clear sentence for a skipped multi-device test/bench site."""
    import jax

    return (f"needs {int(n)} devices, found {len(jax.devices())} — on "
            f"CPU export XLA_FLAGS=--xla_force_host_platform_device_"
            f"count={int(n)} before jax initializes (tests/conftest.py "
            f"does this for the suite)")


def serving_mesh(size: int):
    """The 1-D tensor-parallel serving mesh over the first ``size``
    visible devices, axis-named :data:`SERVING_TP_AXIS`.

    The ONE place the ``Mesh(np.array(devices), ("tp",))`` dance is
    spelled (engine construction, weights-onto-mesh restore,
    tests and bench all call this), so a future Mesh-API rename lands
    here only.  Raises :class:`RuntimeError` with the
    ``--xla_force_host_platform_device_count`` recipe when the host
    exposes fewer devices than ``size``.
    """
    import jax
    import numpy as np

    size = int(size)
    if size < 1:
        raise ValueError(f"mesh size must be >= 1, got {size}")
    if not devices_available(size):
        raise RuntimeError(f"serving_mesh({size}): "
                           + device_count_skip_reason(size))
    return jax.sharding.Mesh(np.array(jax.devices()[:size]),
                             (SERVING_TP_AXIS,))


__all__ = ["NO_REP_CHECK", "SERVING_TP_AXIS", "compile_count",
           "device_count_skip_reason", "devices_available",
           "serving_mesh", "shard_map"]
