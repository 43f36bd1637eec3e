"""Kernel-availability dispatch (the TPU analog of extension import guards).

The reference gates every fused path twice: once on "was the extension built"
(lazy ``import amp_C`` etc.) and once on shape/dtype predicates
(``FusedScaleMaskSoftmax.is_kernel_available``,
apex/transformer/functional/fused_softmax.py:164-275).  Here the analogs are:

- :func:`on_tpu` — Pallas TPU kernels only lower on a TPU backend.  A
  backend that fails to initialize (e.g. the chip is held by another
  process) raises here: answering "not a TPU" would send the whole model
  to the jnp references and report a slow, green run.
- ``APEX_TPU_KERNELS`` env var — ``"0"`` disables Pallas everywhere
  (pure-jnp fallbacks, still jitted/fused by XLA), ``"interpret"`` runs
  Pallas kernels in interpreter mode so CPU tests exercise the kernel code
  path itself.  On a TPU backend ``"interpret"`` is an error, never a
  quiet interpreter run.
- per-op shape predicates live next to each kernel; with kernels enabled,
  each call site reports which side it took through :func:`record_dispatch`.
"""

from __future__ import annotations

import functools
import os

import jax

from apex_tpu._logging import emit_event

_ENV = "APEX_TPU_KERNELS"


@functools.lru_cache(maxsize=None)
def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_interpret() -> bool:
    """Run Pallas kernels in interpret mode (CPU testing of kernel code)."""
    if os.environ.get(_ENV, "").lower() != "interpret":
        return False
    if on_tpu():
        raise RuntimeError(
            f"{_ENV}=interpret on a TPU backend: the Pallas interpreter "
            f"is the CPU test handle; unset {_ENV} to compile the "
            f"kernels with Mosaic")
    return True


def kernels_enabled() -> bool:
    """Whether Pallas kernels should be used at all."""
    mode = os.environ.get(_ENV, "").lower()
    if mode == "0":
        return False
    if mode == "interpret":
        return use_interpret()
    return on_tpu()


def record_dispatch(op: str, shape_ok: bool, **shape) -> bool:
    """``kernels_enabled() and shape_ok``, reported.

    With kernels enabled, a call site whose shape predicate fails runs
    its jnp reference instead: numerically the same, an order of
    magnitude slower, and otherwise invisible.  Each such decision is
    emitted once per trace as a ``kernel_dispatch`` event (``path`` is
    ``"pallas"`` or ``"reference"``) so a caller — ``chip_smoke.py`` —
    can state which path every call site took.  With kernels disabled
    there is no decision to report and nothing is emitted."""
    if not kernels_enabled():
        return False
    emit_event("kernel_dispatch", op=op,
               path="pallas" if shape_ok else "reference", **shape)
    return bool(shape_ok)


def record_choice(op: str, path: str, **shape) -> None:
    """A call site's choice between two reads that are both plain
    ``jax.numpy``, decided from the shapes in hand (``path`` names the one
    taken): a ``read_dispatch`` event, emitted where and when
    :func:`record_dispatch` emits its own - once per trace, and only with
    kernels enabled, so that a default CPU run's event stream is what it
    was."""
    if kernels_enabled():
        emit_event("read_dispatch", op=op, path=path, **shape)


def lane_aligned(*dims: int, lane: int = 128) -> bool:
    """TPU kernels want the trailing dim to be a multiple of the lane width."""
    return all(d % lane == 0 for d in dims)
