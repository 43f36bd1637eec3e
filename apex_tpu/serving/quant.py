"""Quantized serving: int8 weights, int8 KV cache, quantized tp psum.

Opt-in (``DecodeEngine(..., quant=QuantConfig(...))``, default off —
an engine without ``quant=`` is byte-for-byte the fp engine: same
traces, same event stream, same token bytes).  Three independently
switchable levers, all built on the one int8 spelling site
(:mod:`apex_tpu.amp.quant` — symmetric, per-group fp32 scales):

- **weights** — the seven projection kernels (q/k/v/o/gate/up/down)
  and the LM head are stored as :class:`QTensor` leaves (int8 payload
  + one fp32 scale per output channel) by :func:`quantize_params` at
  load/boot time; embedding and norm scales stay high-precision (they
  are tiny, and norm scales multiply *activations* — quantizing them
  buys nothing and costs accuracy).  Dequantization happens *inside*
  the existing five jitted program families (prefill / decode / verify
  / restore / region read keep their bounded compile counts — no new
  program family), so XLA fuses the ``int8 * scale`` expansion into
  the surrounding matmul's operand read and the weights live in HBM at
  ~4x density.
- **kv** — the decode cache stores int8 K/V with one fp32 scale per
  (position, kv head) (:class:`~apex_tpu.serving.kv_cache.QuantKVCache`
  dense, :class:`~apex_tpu.serving.paged_kv_cache.QuantPagedKVCache`
  paged — scale pools indexed by the SAME block ids, so aliasing,
  copy-on-write, fork, and release move payload and scales together by
  construction).  Every attention read dequantizes through the scales;
  capture (:meth:`DecodeEngine.read_region` / ``capture_slot``) hands
  out **dequantized fp32** rows so every host consumer — prefix-cache
  spans, preemption snapshots, fleet stream exports — stays
  quantization-oblivious, and restore requantizes in-program (the
  group amax element always requantizes to exactly ±127, so the int8
  payload survives a capture→restore roundtrip bit-for-bit).
- **allreduce** — the per-layer tp psum pair (attention ``o_proj`` +
  MLP ``down_proj``) runs as a grouped-scale int8 exchange
  (:func:`quantized_allreduce`, the EQuARX shape: quantize per token
  group, all-gather payloads + scales, dequantize-sum in fp32): the
  wire moves ~1/4 the bytes per psum.  Scoped by construction to the
  ``kind="row_linear"`` call sites via
  :func:`~apex_tpu.transformer.tensor_parallel.mappings.
  override_forward_allreduce`; the embedding and logits reductions
  stay exact.  Requires ``tp=``.

Acceptance is **agreement-tier**, not bit-tier: pinned greedy streams
must agree with the fp32 engine at a high rate with bounded
per-position logit error (``tests/test_serving_quant.py`` pins the
bars; the ``serving_quant`` bench block tracks them release over
release together with bytes/token and streams-per-GB).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu._logging import emit_event, get_logger
from apex_tpu.amp.quant import dequantize_int8, quantize_int8
from apex_tpu.utils.compat import SERVING_TP_AXIS

__all__ = [
    "QuantConfig",
    "QTensor",
    "quantize_params",
    "dequant_params",
    "is_quantized",
    "serving_param_spec",
    "quantized_allreduce",
    "stream_agreement",
    "max_logit_error",
    "kv_bytes_per_token",
    "param_bytes",
    "evaluate_quant",
]

logger = get_logger("serving.quant")


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """Which quantization levers a :class:`DecodeEngine` turns on.

    ``weights``: store projection kernels + LM head int8 (per-output-
    channel scales).  ``kv``: store the decode cache int8 (per-
    (position, head) scales).  ``allreduce``: run the per-layer tp psum
    pair as a grouped-scale int8 exchange (requires ``tp=``; the
    engine rejects the combination at construction otherwise).
    """

    weights: bool = True
    kv: bool = True
    allreduce: bool = False

    def __post_init__(self):
        if not (self.weights or self.kv or self.allreduce):
            raise ValueError(
                "QuantConfig with every lever off — pass quant=None "
                "instead (the default-off path is the fp engine)")


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("q", "scale"),
                   meta_fields=("axis", "dtype_name"))
@dataclasses.dataclass(frozen=True)
class QTensor:
    """One int8-quantized weight: payload + per-output-channel scales.

    ``q``: int8, the original kernel's shape.  ``scale``: fp32, the
    kernel's shape with ``axis`` (the reduction/input axis) removed —
    one scale per output channel, so quantization error never mixes
    across channels.  ``axis``/``dtype_name`` are pytree *meta* (hash
    into the jit cache key, never traced).  A QTensor flattens to its
    two arrays, so ``device_put``, sharding trees, and the engine's
    swap-time shape/dtype checks all see plain leaves.
    """

    q: jax.Array
    scale: jax.Array
    axis: int = 0
    dtype_name: str = "float32"

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return jnp.dtype(self.dtype_name)

    @property
    def nbytes(self) -> int:
        return int(getattr(self.q, "nbytes", 0)) + int(
            getattr(self.scale, "nbytes", 0))

    def dequantize(self) -> jax.Array:
        return dequantize_int8(self.q, self.scale, axis=self.axis,
                               dtype=self.dtype)


# the weight leaves quantize_params touches: the per-layer projection
# kernels (per-output-channel = reduce over the INPUT axis 0 of the
# [in, out] flax kernel) and the [vocab, h] LM head (output channel =
# vocab row, reduce over axis 1).  Embedding and norm scales stay fp
# on purpose: they are a rounding error of the byte budget, and the
# embedding gather has no matmul to fuse a dequant into.
_WEIGHT_QUANT_MODULES = ("q_proj", "k_proj", "v_proj", "o_proj",
                         "gate_proj", "up_proj", "down_proj")


def _weight_quant_axis(ks: str) -> Optional[int]:
    """Reduce axis of a leaf's per-output-channel scales, or ``None``
    when the leaf stays high-precision."""
    if "lm_head" in ks:
        return 1
    if "kernel" in ks and any(m in ks for m in _WEIGHT_QUANT_MODULES):
        return 0
    return None


def quantize_params(params):
    """Replace every weight-quantizable fp leaf with a :class:`QTensor`
    (int8 payload + per-output-channel fp32 scales); everything else —
    embedding, norm scales, already-quantized leaves — passes through
    untouched.  Idempotent: QTensor nodes are treated as leaves and
    passed through whole (descending into one would meet its fp32
    ``.scale`` under the kernel path and re-wrap it)."""

    def one(path, leaf):
        if isinstance(leaf, QTensor):
            return leaf
        ks = jax.tree_util.keystr(path)
        ax = _weight_quant_axis(ks)
        if (ax is None or not hasattr(leaf, "dtype")
                or not jnp.issubdtype(leaf.dtype, jnp.floating)):
            return leaf
        q, scale = quantize_int8(leaf, axis=ax)
        return QTensor(q=q, scale=scale, axis=ax,
                       dtype_name=jnp.dtype(leaf.dtype).name)

    return jax.tree_util.tree_map_with_path(
        one, params, is_leaf=lambda x: isinstance(x, QTensor))


def _is_qtensor(x) -> bool:
    return isinstance(x, QTensor)


def is_quantized(params) -> bool:
    """True when the tree carries any :class:`QTensor` leaf (the
    swap/rollback detection: an already-quantized candidate must pass
    through :func:`quantize_params` untouched)."""
    return any(_is_qtensor(l)
               for l in jax.tree.leaves(params, is_leaf=_is_qtensor))


def dequant_params(params):
    """Expand every :class:`QTensor` back to its fp array (the in-
    program dequant the engine fuses into its jitted bodies); an
    unquantized tree maps through unchanged."""
    return jax.tree.map(
        lambda l: l.dequantize() if _is_qtensor(l) else l,
        params, is_leaf=_is_qtensor)


def serving_param_spec(path, axis_name: str = SERVING_TP_AXIS):
    """Quant-aware tp ``PartitionSpec`` for one serving-params leaf.

    Plain leaves delegate to
    :func:`apex_tpu.models.llama.tp_param_spec` (the model owns its
    column/row layout).  A :class:`QTensor`'s ``.q`` payload shards
    exactly like the kernel it replaced; its per-output-channel
    ``.scale`` follows the OUTPUT dimension — sharded for column
    kernels and the lm_head (their output dim is the tp-split one),
    replicated for row kernels (their output dim survives the psum
    whole on every rank).  ``.q``/``.scale`` suffixes only ever come
    from QTensor attribute keys — dict-keyed params (e.g. a norm's
    ``['scale']``) render as ``['scale']``, not ``.scale``.
    """
    from jax.sharding import PartitionSpec as P

    from apex_tpu.models.llama import tp_param_spec

    ks = path if isinstance(path, str) else jax.tree_util.keystr(path)
    if ks.endswith(".q"):
        return tp_param_spec(ks[:-len(".q")], axis_name)
    if ks.endswith(".scale"):
        base = ks[:-len(".scale")]
        if ("lm_head" in base
                or any(m in base for m in ("q_proj", "k_proj", "v_proj",
                                           "gate_proj", "up_proj"))):
            return P(axis_name)
        return P()   # row kernels: whole-output scales, replicated
    return tp_param_spec(ks, axis_name)


def quantized_allreduce(x, axis_name: str = SERVING_TP_AXIS):
    """Grouped-scale int8 allreduce (the EQuARX shape): quantize each
    rank's partial sum per last-dim group, exchange int8 payloads +
    fp32 scales, dequantize-accumulate in fp32, cast back.

    The wire cost per psum drops to ``(1 + 4/group) / dtype_bytes`` of
    the exact collective (~¼ at fp32 activations).  Error is bounded
    per group by ``world * amax / 254`` — the reason this is installed
    ONLY for the ``kind="row_linear"`` psum pair (residual-stream
    deltas), never the logits/embedding reductions.
    """
    q, scale = quantize_int8(x, axis=-1)
    qg = lax.all_gather(q, axis_name)            # [world, ..., group]
    sg = lax.all_gather(scale, axis_name)        # [world, ...]
    out = jnp.sum(dequantize_int8(qg, sg, axis=-1), axis=0)
    return out.astype(x.dtype)


# ---- acceptance accounting -----------------------------------------------


def stream_agreement(ref_tokens, got_tokens) -> float:
    """Positionwise agreement rate of two greedy token streams over
    their common length (1.0 == identical streams)."""
    n = min(len(ref_tokens), len(got_tokens))
    if n == 0:
        return 1.0
    same = sum(1 for a, b in zip(ref_tokens, got_tokens)
               if int(a) == int(b))
    return same / n


def max_logit_error(ref_logits, got_logits) -> float:
    """Largest absolute per-position logit deviation between two
    ``[steps, vocab]`` stacks (compared over the common prefix)."""
    import numpy as np

    r = np.asarray(ref_logits, np.float32)
    g = np.asarray(got_logits, np.float32)
    n = min(r.shape[0], g.shape[0])
    if n == 0:
        return 0.0
    return float(np.max(np.abs(r[:n] - g[:n])))


def kv_bytes_per_token(cache) -> float:
    """Device bytes one cached token costs across every layer — payload
    plus scales, fp and quant caches alike (total pool bytes / total
    token capacity).  The capacity half of the streams-per-GB
    acceptance bar: ``fp_bytes / quant_bytes`` is exactly the
    concurrent-streams multiplier at a fixed byte budget."""
    total = sum(int(getattr(cache, name).nbytes) for name in cache.stored)
    # dense: [L, slots, max_len, ...]; paged: [L, blocks, block_size, ...]
    tokens = int(cache.k.shape[1]) * int(cache.k.shape[2])
    return total / tokens


def param_bytes(params) -> int:
    """Total leaf bytes of a params tree (QTensor leaves flatten to
    payload + scales, so the quantized footprint is counted honestly)."""
    return sum(int(getattr(l, "nbytes", 0)) for l in jax.tree.leaves(params))


def evaluate_quant(ref_tokens, quant_tokens, *, ref_logits=None,
                   quant_logits=None, bytes_per_token=None,
                   fp_bytes_per_token=None) -> dict:
    """Score a quantized stream against its fp32 reference and publish
    the ``serving_quant_eval`` event the obs bridge turns into the
    ``apex_serving_quant_*`` agreement/logit-error/bytes metrics.

    Returns the scored dict: ``agreement`` (positionwise rate),
    ``tokens`` (compared length), ``max_logit_error`` (when both logit
    stacks are given), ``bytes_per_token`` / ``capacity_ratio`` (when
    the byte accounting is given).
    """
    out: dict = {
        "agreement": stream_agreement(ref_tokens, quant_tokens),
        "tokens": min(len(ref_tokens), len(quant_tokens)),
    }
    if ref_logits is not None and quant_logits is not None:
        out["max_logit_error"] = max_logit_error(ref_logits, quant_logits)
    if bytes_per_token is not None:
        out["bytes_per_token"] = float(bytes_per_token)
        if fp_bytes_per_token:
            out["capacity_ratio"] = float(fp_bytes_per_token) / float(
                bytes_per_token)
    emit_event("serving_quant_eval", **out)
    logger.debug("serving_quant_eval: %s", out)
    return out
