"""Device time by component: the one vocabulary of named scopes.

A served model's work on the device is a dozen components - the projections
around attention, the cache's write and its read, a key selector, a recurrent
state, the dense MLP, a router, its experts, the head - and every one of them
is ``fusion.N`` to XLA.  A ``jax.named_scope`` is kept by XLA as the head of
each instruction's ``op_name`` (fused instructions carry their root's), and a
``jax.profiler`` capture carries the compiled module with it, so the device's
own op line can be read back by component.  This module is that vocabulary and
the one way to open a scope of it; ``benchmark/lib/device_scopes.py`` is the
reader (``benchmark/tools/scope_table.py <capture>`` prints any capture of a
running engine as program x component).

A scope is metadata: it is paid when a program is traced, never when it
runs, and the lowered text of a program is the same with and without it.  So
nothing turns it on or off.  Scopes nest under flax's module path and the
innermost ``apex.<name>`` of an instruction is its component:
``apex.cache_read`` opened by the cache's seam inside an attention module
that is ``apex.attn_proj`` reads as the cache's.

==================  ======================================================
scope               what is inside it
==================  ======================================================
``apex.embed``      token embedding
``apex.norm``       a decoder layer's input / post-attention norms
``apex.attn_proj``  q, k, v (or the latent down / up projections, a
                    selector's or a Mamba mixer's projections), rope /
                    YaRN, gates, the output projection
``apex.cache_write``  append / chunk-write, ring writes, a recurrent
                    state's store, a slot's length
``apex.cache_read``   the read whichever path was chosen: the Pallas call
                    with its glue (cuts, casts, expansion) or the
                    ``jax.numpy`` read
``apex.select``     a key selector's scores, thresholds / ``top_k`` and the
                    gather of the selected rows
``apex.state``      Mamba-2: convolution tail, chunked scan, one-token
                    state update, a slot's state read
``apex.mlp``        dense MLP, a shared expert
``apex.router``     router logits, top-k routing, the dispatch sort, the
                    call's counts
``apex.experts``    the grouped products, activation, weighting, combine
``apex.head``       final norm, the LM-head product, the logits handed back
``apex.sample``     the sampler
==================  ======================================================
"""

from __future__ import annotations

import functools

import jax

__all__ = ["PREFIX", "VOCABULARY", "component", "EMBED", "NORM", "ATTN_PROJ",
           "CACHE_WRITE", "CACHE_READ", "SELECT", "STATE", "MLP", "ROUTER",
           "EXPERTS", "HEAD", "SAMPLE"]

PREFIX = "apex."

EMBED = "embed"
NORM = "norm"
ATTN_PROJ = "attn_proj"
CACHE_WRITE = "cache_write"
CACHE_READ = "cache_read"
SELECT = "select"
STATE = "state"
MLP = "mlp"
ROUTER = "router"
EXPERTS = "experts"
HEAD = "head"
SAMPLE = "sample"

VOCABULARY = (EMBED, NORM, ATTN_PROJ, CACHE_WRITE, CACHE_READ, SELECT, STATE,
              MLP, ROUTER, EXPERTS, HEAD, SAMPLE)


def _open(name: str):
    """The one place a scope is made."""
    return jax.named_scope(name)


class _Component:
    """``jax.named_scope(name)`` made anew at each entry: as a decorator it
    holds the name and opens the scope when the function is called (under a
    trace), so one object serves every call and every thread."""

    def __init__(self, name: str):
        self._name = name
        self._scope = None

    def __enter__(self):
        self._scope = _open(self._name)
        return self._scope.__enter__()

    def __exit__(self, *exc):
        return self._scope.__exit__(*exc)

    def __call__(self, fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with _open(self._name):
                return fn(*args, **kwargs)

        return scoped


def component(name: str) -> _Component:
    """The scope ``apex.<name>``: a context manager, and a decorator of a
    function or of a flax module's ``__call__`` (under ``nn.compact``).  A
    name outside :data:`VOCABULARY` raises: a private name is a second
    system beside the first, and no reader knows it."""
    if name not in VOCABULARY:
        raise ValueError(
            f"{name!r} is no component of the vocabulary {VOCABULARY}: "
            f"device time is read back by these names "
            f"(apex_tpu/obs/scopes.py)")
    return _Component(PREFIX + name)
