"""Tensor-parallel layers: Column/Row linear + vocab-parallel embedding.

Parity target: ``apex.transformer.tensor_parallel.layers``
(layers.py:174-813): ``VocabParallelEmbedding``, ``ColumnParallelLinear``,
``RowParallelLinear`` built on ``LinearWithGradAccumulationAndAsyncCommunication``
(layers.py:279-438).

TPU-native design: the layers are flax modules meant to run **inside
shard_map over the tp axis** — each rank holds its weight shard and the
forward/backward collectives are the explicit custom-vjp mappings
(:mod:`.mappings`), giving exactly Megatron's communication schedule:

- column fwd: identity (or SP all-gather, layers.py:311-325); bwd: grad-input
  all-reduce (or SP reduce-scatter, layers.py:379-412).
- row fwd: all-reduce (or SP reduce-scatter); bwd: identity.

What does NOT carry over, by design (SURVEY.md §7 "wgrad accumulation"):

- ``gradient_accumulation_fusion`` / ``main_grad`` (layers.py:413-425): JAX
  grads are functional; accumulation into a persistent fp32 buffer is the
  optimizer/accumulator's job and XLA fuses the wgrad GEMM with the add when
  the buffer is donated.  The flag is accepted and ignored.
- async-communication overlap (layers.py:345-376): XLA's latency-hiding
  scheduler overlaps the all-gather/reduce-scatter with the wgrad GEMMs; the
  ``no_async_tensor_model_parallel_allreduce`` knob is accepted and ignored.

Neither claim is taken on faith: ``tests/test_hlo_comm_plan.py`` compiles
this MLP fwd+bwd and asserts, on the optimized HLO, the exact Megatron
collective plan (SP: 2 all-gather + 2 reduce-scatter, zero all-reduce;
plain TP: 2 all-reduce) and that the wgrads survive as single dot
contractions (bf16-operand on TPU).

Weight shards are initialized with a rank-folded RNG so the full (gathered)
weight matches a single full-size initialization draw pattern
(_initialize_affine_weight_gpu's per-rank seed, random.py:124-235 semantics).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

import flax.linen as nn

from apex_tpu.transformer.parallel_state import TENSOR_PARALLEL_AXIS
from apex_tpu.transformer.tensor_parallel.mappings import (
    copy_to_tensor_model_parallel_region,
    gather_from_sequence_parallel_region,
    gather_from_tensor_model_parallel_region,
    reduce_from_tensor_model_parallel_region,
    reduce_scatter_to_sequence_parallel_region,
    scatter_to_tensor_model_parallel_region,
)
from apex_tpu.transformer.tensor_parallel.utils import VocabUtility, divide

__all__ = [
    "ColumnParallelLinear",
    "RowParallelLinear",
    "VocabParallelEmbedding",
]


def maybe_axis_index(axis_name: str):
    """axis_index if inside a mapped context over ``axis_name``, else None."""
    try:
        return jax.lax.axis_index(axis_name)
    except NameError:
        return None


def _tp_size(axis_name: str) -> int:
    """Static tp world size: the mapped axis size when inside shard_map over
    ``axis_name``, else 1 (single-chip semantics, even when a global mesh
    exists — binding, not mesh presence, decides)."""
    if maybe_axis_index(axis_name) is None:
        return 1
    return int(jax.lax.axis_size(axis_name))


def _shard_init(init_fn: Callable, axis_name: str) -> Callable:
    """Fold the tp rank into the RNG so shards draw independent values."""

    def wrapped(key, shape, dtype):
        idx = maybe_axis_index(axis_name)
        if idx is not None:
            key = jax.random.fold_in(key, idx)
        return init_fn(key, shape, dtype)

    return wrapped


def _matmul(x, kernel):
    precision = (jax.lax.Precision.HIGHEST
                 if x.dtype == jnp.float32 else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(
        x, kernel, (((x.ndim - 1,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32).astype(x.dtype)


class ColumnParallelLinear(nn.Module):
    """Y = XA + b with A sharded along its output (column) dim
    (layers.py:460-640).

    Input is replicated across tp ranks (or sequence-sharded when
    ``sequence_parallel_enabled``); output is the rank's column shard unless
    ``gather_output``.
    """

    input_size: int
    output_size: int
    use_bias: bool = True
    gather_output: bool = True
    init_method: Callable = nn.initializers.lecun_normal()
    skip_bias_add: bool = False
    sequence_parallel_enabled: bool = False
    no_async_tensor_model_parallel_allreduce: bool = False  # accepted, unused
    gradient_accumulation_fusion: bool = False  # accepted, unused (see module doc)
    params_dtype: Any = jnp.float32
    axis_name: str = TENSOR_PARALLEL_AXIS

    @nn.compact
    def __call__(self, x):
        world = _tp_size(self.axis_name)
        out_per_rank = divide(self.output_size, world)
        kernel = self.param(
            "kernel", _shard_init(self.init_method, self.axis_name),
            (self.input_size, out_per_rank), self.params_dtype)
        bias = (self.param("bias", nn.initializers.zeros, (out_per_rank,),
                           self.params_dtype) if self.use_bias else None)

        if self.sequence_parallel_enabled:
            if world > 1:
                x = gather_from_sequence_parallel_region(
                    x, self.axis_name, True)
        elif world > 1:
            x = copy_to_tensor_model_parallel_region(x, self.axis_name)

        y = _matmul(x, kernel.astype(x.dtype))
        if bias is not None and not self.skip_bias_add:
            y = y + bias.astype(y.dtype)

        if self.gather_output:
            if self.sequence_parallel_enabled:
                raise RuntimeError(
                    "gather_output is incompatible with sequence parallelism"
                )  # layers.py:520 same constraint
            if world > 1:
                y = gather_from_tensor_model_parallel_region(y, self.axis_name)

        if self.skip_bias_add:
            return y, bias
        return y


class RowParallelLinear(nn.Module):
    """Y = XA + b with A sharded along its input (row) dim (layers.py:660-813).

    Input is expected already split along its last dim across tp ranks
    (``input_is_parallel``, the usual case after a column-parallel layer);
    output is all-reduced (or reduce-scattered under sequence parallelism).
    """

    input_size: int
    output_size: int
    use_bias: bool = True
    input_is_parallel: bool = False
    init_method: Callable = nn.initializers.lecun_normal()
    skip_bias_add: bool = False
    sequence_parallel_enabled: bool = False
    gradient_accumulation_fusion: bool = False  # accepted, unused
    params_dtype: Any = jnp.float32
    axis_name: str = TENSOR_PARALLEL_AXIS

    @nn.compact
    def __call__(self, x):
        world = _tp_size(self.axis_name)
        in_per_rank = divide(self.input_size, world)
        kernel = self.param(
            "kernel", _shard_init(self.init_method, self.axis_name),
            (in_per_rank, self.output_size), self.params_dtype)
        bias = (self.param("bias", nn.initializers.zeros, (self.output_size,),
                           self.params_dtype) if self.use_bias else None)

        if not self.input_is_parallel:
            if self.sequence_parallel_enabled:
                raise RuntimeError(
                    "To enable `sequence_parallel_enabled`, "
                    "`input_is_parallel` must be `True`")  # layers.py:720
            if world > 1:
                x = scatter_to_tensor_model_parallel_region(x, self.axis_name)

        y = _matmul(x, kernel.astype(x.dtype))
        if world > 1:
            if self.sequence_parallel_enabled:
                y = reduce_scatter_to_sequence_parallel_region(y, self.axis_name)
            else:
                # tagged "row_linear": this is the per-layer psum pair
                # (attention o_proj + MLP down_proj) the serving quant
                # subsystem may override with a grouped-scale int8
                # allreduce; the embedding/logits reduces stay "generic"
                # and therefore always exact
                y = reduce_from_tensor_model_parallel_region(
                    y, self.axis_name, kind="row_linear")

        if self.skip_bias_add:
            return y, bias
        if bias is not None:
            y = y + bias.astype(y.dtype)
        return y


class VocabParallelEmbedding(nn.Module):
    """Embedding with the vocab dim sharded across tp ranks
    (layers.py:174-278): masked local lookup + all-reduce.
    """

    num_embeddings: int
    embedding_dim: int
    # Megatron's init_method_normal(0.02) default (arguments.py init-method-std)
    init_method: Callable = nn.initializers.normal(stddev=0.02)
    params_dtype: Any = jnp.float32
    axis_name: str = TENSOR_PARALLEL_AXIS

    @nn.compact
    def __call__(self, ids):
        world = _tp_size(self.axis_name)
        per_rank = divide(self.num_embeddings, world)
        weight = self.param(
            "embedding", _shard_init(self.init_method, self.axis_name),
            (per_rank, self.embedding_dim), self.params_dtype)

        if world == 1:
            return jnp.take(weight, ids, axis=0)

        rank = jax.lax.axis_index(self.axis_name)
        first, last = VocabUtility.vocab_range_from_per_partition_vocab_size(
            per_rank, rank, world)
        in_range = jnp.logical_and(ids >= first, ids < last)
        masked = jnp.where(in_range, ids - first, 0)
        out = jnp.take(weight, masked, axis=0)
        out = jnp.where(in_range[..., None], out, 0.0)
        return reduce_from_tensor_model_parallel_region(out, self.axis_name)


def parallel_lm_logits(hidden, word_embeddings, axis_name: str = TENSOR_PARALLEL_AXIS,
                       sequence_parallel_enabled: bool = False,
                       gather_output: bool = False):
    """Logits = H @ E^T with E vocab-sharded (the reference's
    parallel_lm_logits): output is [s, b, vocab/tp] unless gathered."""
    from apex_tpu.transformer.tensor_parallel.mappings import (
        copy_to_tensor_model_parallel_region,
        gather_from_sequence_parallel_region,
        gather_from_tensor_model_parallel_region,
    )

    if sequence_parallel_enabled:
        hidden = gather_from_sequence_parallel_region(hidden, axis_name, True)
    else:
        hidden = copy_to_tensor_model_parallel_region(hidden, axis_name)
    logits = jax.lax.dot_general(
        hidden, word_embeddings,
        (((hidden.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if gather_output:
        logits = gather_from_tensor_model_parallel_region(logits, axis_name)
    return logits


# public names for model composition (apex_tpu.models builds on these)
tp_world_size = _tp_size
shard_init = _shard_init
