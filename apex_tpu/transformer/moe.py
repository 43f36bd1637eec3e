"""Mixture-of-Experts with expert parallelism over a mesh axis.

Capability target: expert parallelism ("ep") as a first-class sharding —
experts live sharded across ranks and tokens travel to their expert via
``all_to_all``, the standard TPU MoE dataflow (GShard/Switch): gate →
capacity-bounded dispatch einsum → all_to_all over ``ep`` → batched
expert FFN on the MXU → all_to_all back → weighted combine.  (NVIDIA
Apex predates MoE and has no counterpart; this rounds out the dp/tp/pp/
sp/ep sharding set the framework targets.)

Design notes:
- dispatch/combine are dense einsums against a [tokens, experts,
  capacity] one-hot — no dynamic shapes, so XLA can tile everything;
  tokens over capacity are dropped and their outputs pass through as
  zeros scaled into the residual (Switch semantics).
- the router computes in fp32 regardless of activation dtype; an
  auxiliary load-balancing loss (Switch eq. 4) is returned alongside.
- with ``axis_name=None`` the same module runs single-rank (all experts
  local) — the parity oracle for the sharded path *while capacity does
  not bind*.  When it binds, drops differ by design: the sharded path
  cuts each rank's local queue (capacity slots per rank per expert, the
  GShard dataflow), the local path cuts one global queue.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

import flax.linen as nn

from apex_tpu.obs.scopes import EXPERTS, MLP, ROUTER, component
from apex_tpu.ops._dispatch import (
    lane_aligned,
    record_dispatch,
    use_interpret,
)

__all__ = ["ExpertParallelMLP", "top1_dispatch", "LatentMoE", "GatedMoE",
           "topk_sigmoid_route", "topk_softmax_route", "grouped_matmul",
           "held_pairs",
           "MOE_COUNTERS"]


def top1_dispatch(logits32, capacity: int):
    """Switch-style top-1 routing with position-in-expert capacity.

    logits32: [tokens, experts] fp32.  Returns (dispatch [t, e, c] float,
    combine [t, e, c] float, aux_loss scalar).
    """
    t, e = logits32.shape
    probs = jax.nn.softmax(logits32, axis=-1)
    expert = jnp.argmax(probs, axis=-1)                    # [t]
    onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)  # [t, e]

    # position of each token within its chosen expert's queue
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0        # [t, e]
    in_cap = (pos >= 0) & (pos < capacity)
    dispatch = onehot[..., None] * jax.nn.one_hot(
        jnp.maximum(pos, 0.0).astype(jnp.int32), capacity,
        dtype=jnp.float32) * in_cap[..., None]             # [t, e, c]
    gate = jnp.sum(probs * onehot, axis=-1)                # [t]
    combine = dispatch * gate[:, None, None]

    # Switch load-balancing loss: e * sum_e(frac_tokens_e * frac_prob_e)
    frac_tokens = jnp.mean(onehot, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return dispatch, combine, aux


class ExpertParallelMLP(nn.Module):
    """Top-1 MoE FFN; experts sharded over ``axis_name`` when set.

    Input ``[tokens, hidden]`` (flatten batch/sequence first); returns
    ``(output [tokens, hidden], aux_loss)``.  Under shard_map each rank
    holds ``num_experts / ep`` experts and its own token shard.
    """

    num_experts: int
    hidden_size: int
    ffn_hidden_size: Optional[int] = None
    capacity_factor: float = 1.25
    axis_name: Optional[str] = None
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x) -> Tuple[jax.Array, jax.Array]:
        t, h = x.shape
        ffn = self.ffn_hidden_size or 4 * h
        ep = (jax.lax.psum(1, self.axis_name)  # static; no axis_size in 0.4.x
              if self.axis_name is not None else 1)
        if self.num_experts % ep:
            raise ValueError(f"num_experts ({self.num_experts}) must divide "
                             f"by the ep axis size ({ep})")
        local_e = self.num_experts // ep
        # per-rank slots per expert: the GShard/Switch bound — each expert
        # receives ep * capacity = cf * t_global / num_experts slots total,
        # so per-expert compute and all_to_all bytes stay flat as ep grows
        capacity = max(1, int(self.capacity_factor * t / self.num_experts))

        router = self.param("router", nn.initializers.lecun_normal(),
                            (h, self.num_experts), jnp.float32)
        # local experts only: [local_e, h, ffn] / [local_e, ffn, h]
        w_in = self.param("w_in", nn.initializers.lecun_normal(),
                          (local_e, h, ffn), self.param_dtype)
        w_out = self.param("w_out", nn.initializers.lecun_normal(),
                           (local_e, ffn, h), self.param_dtype)

        logits = x.astype(jnp.float32) @ router
        dispatch, combine, aux = top1_dispatch(logits, capacity)

        # [t, e, c] x [t, h] -> [e, c, h]: the dispatch einsum
        expert_in = jnp.einsum("tec,th->ech", dispatch.astype(x.dtype), x)

        if self.axis_name is not None:
            # rows [e, ...] regroup so each rank receives ITS experts'
            # slots from every rank: [e, c, h] -> [local_e, ep*c, h]
            expert_in = expert_in.reshape(ep, local_e, capacity, h)
            expert_in = jax.lax.all_to_all(
                expert_in, self.axis_name, split_axis=0, concat_axis=0,
                tiled=False)
            expert_in = expert_in.transpose(1, 0, 2, 3).reshape(
                local_e, ep * capacity, h)
        else:
            expert_in = expert_in.reshape(local_e, capacity, h)

        # batched expert FFN: one [local_e] batched MXU matmul pair
        hmid = jax.nn.gelu(jnp.einsum("ech,ehf->ecf", expert_in,
                                      w_in.astype(x.dtype)))
        expert_out = jnp.einsum("ecf,efh->ech", hmid, w_out.astype(x.dtype))

        if self.axis_name is not None:
            expert_out = expert_out.reshape(local_e, ep, capacity, h)
            expert_out = expert_out.transpose(1, 0, 2, 3)
            expert_out = jax.lax.all_to_all(
                expert_out, self.axis_name, split_axis=0, concat_axis=0,
                tiled=False)
            expert_out = expert_out.reshape(self.num_experts, capacity, h)
        else:
            expert_out = expert_out.reshape(self.num_experts, capacity, h)

        out = jnp.einsum("tec,ech->th", combine.astype(x.dtype), expert_out)
        return out, aux


# what LatentMoE counts a call, in this order (int32): calls, tokens routed
# (valid rows), token-expert pairs computed here, held experts with a pair,
# largest number of pairs on one held expert
MOE_COUNTERS = ("steps", "tokens", "pairs", "touched", "max_load")

# gmm's tile: up to 1024 x 1024 of an expert's matrix a grid step, so that
# a step's ~0.35 us is paid once per 2 MB read and not once per 32 KB, and 32
# rows of pairs a step where a call has few (a decode step: ~3 pairs an
# expert), 128 where it has many (a prefill chunk).  On one v5e at this
# model's shapes (128 held experts, 1024 -> 2688 -> 1024, both products;
# tools/grouped_matmul_bench.py, PERF.md section 6, PR 27): 64 tokens x 22
# pairs 2.0 ms against jax.lax.ragged_dot's 4.9 and the default 128^3
# tile's 13.7; 512 x 22 pairs 3.1 ms against 9.2 and 17.3
_GMM_TILE_KN = 1024
_GMM_TILE_M, _GMM_TILE_M_FEW, _GMM_FEW_ROWS = 128, 32, 2048


def topk_sigmoid_route(x, kernel, bias, top_k: int, scale: float):
    """Sigmoid-scored top-k routing with a selection bias (DeepSeek-V3 /
    Nemotron-H): scores ``sigmoid(x W_r)`` in float32 over every published
    expert, the ``top_k`` largest of ``score + bias`` chosen, weights the
    chosen *scores* normalised to sum 1 and multiplied by ``scale``.
    Returns ``(chosen [t, top_k] int32, weights [t, top_k] float32)``."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), kernel.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = scale * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), weights


def topk_softmax_route(x, kernel, top_k: int):
    """Softmax-scored top-k routing without a selection bias (Mixtral /
    Qwen-MoE with ``norm_topk_prob``): ``p = softmax(x W_r)`` in float32 over
    every published expert, the ``top_k`` largest ``p`` chosen, weights the
    chosen ``p`` normalised to sum 1.  Returns ``(chosen [t, top_k] int32,
    weights [t, top_k] float32)``."""
    probs = jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), kernel.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    picked, chosen = jax.lax.top_k(probs, top_k)
    return chosen.astype(jnp.int32), picked / picked.sum(-1, keepdims=True)


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs[rows of group g] @ rhs[g]`` for the ``rhs.shape[0]`` groups
    held; ``group_sizes`` has one more entry, the rows at the end that
    belong to no group held here, whose output rows the caller masks.
    ``lhs [m, k]`` sorted by group, ``rhs [g, k, n]``; float32 out.

    On the chip, at tile-aligned shapes, the ``gmm`` kernel that ships with
    jax (it visits only the tiles of non-empty groups, and reads a touched
    expert's matrix once a row tile); everywhere else
    ``jax.lax.ragged_dot``."""
    m, k = lhs.shape
    n = rhs.shape[2]
    tm = _GMM_TILE_M_FEW if m <= _GMM_FEW_ROWS else _GMM_TILE_M
    if record_dispatch("moe_gmm", lane_aligned(k, n) and m % tm == 0,
                       m=m, k=k, n=n):
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        def tile(dim):
            # the largest multiple of 128 up to _GMM_TILE_KN that divides dim
            return max(t for t in range(128, min(_GMM_TILE_KN, dim) + 1, 128)
                       if dim % t == 0)

        return gmm(lhs, rhs, group_sizes,
                   preferred_element_type=jnp.float32,
                   tiling=(tm, tile(k), tile(n)), interpret=use_interpret())
    return jax.lax.ragged_dot(lhs, rhs, group_sizes[:rhs.shape[0]],
                              preferred_element_type=jnp.float32)


def _held_inside(experts_held, num_experts: int) -> int:
    """The count of ``experts_held = (start, count)``, a range inside the
    ``num_experts`` routed experts."""
    lo, held = experts_held
    if not 0 <= lo <= lo + held <= num_experts or held < 1:
        raise ValueError(
            f"experts_held {tuple(experts_held)}: a range (start, count) "
            f"inside the {num_experts} routed experts")
    return held


@dataclasses.dataclass(frozen=True)
class HeldPairs:
    """The token-expert pairs of one call, sorted by the expert held here
    they land on (:func:`held_pairs`): ``token_of [t k]`` the token of each
    sorted row, ``sizes [held + 1]`` the rows of each held expert and, last,
    of the pairs that land nowhere here, ``order`` the sort, ``key`` each
    pair's group before it, ``counts`` the call's :data:`MOE_COUNTERS`."""

    key: jax.Array
    order: jax.Array
    sizes: jax.Array
    token_of: jax.Array
    counts: jax.Array

    def combine(self, out, weights):
        """Sorted rows ``out [t k, width]`` back to tokens: each real pair
        times its weight (``weights [t, k]``), summed over a token's
        ``k``; a row past the held groups is whatever the product left
        there and counts as zero."""
        t, k = weights.shape
        held = self.sizes.shape[0] - 1
        out = jnp.where((self.key[self.order] < held)[:, None],
                        out * weights.reshape(t * k)[self.order][:, None],
                        0.0)
        # pair (token, j) sits at sorted row inverse[.]
        inverse = jnp.zeros((t * k,), jnp.int32).at[self.order].set(
            jnp.arange(t * k, dtype=jnp.int32))
        return out[inverse].reshape(t, k, out.shape[-1]).sum(1)


def held_pairs(chosen, experts_held, valid=None) -> HeldPairs:
    """What every routed-expert layer that holds a share of the experts
    does before and after its grouped products: of the pairs ``chosen [t,
    k]`` (each token's experts) those on the experts ``[start, start +
    count)`` of rows that are ``valid`` are sorted by expert, the rest sort
    to the end as group ``count``, which no matrix is multiplied for."""
    t, k = chosen.shape
    lo, held = experts_held
    here = (chosen >= lo) & (chosen < lo + held)
    if valid is not None:
        here &= valid[:, None]
    key = jnp.where(here, chosen - lo, held).reshape(t * k)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)
    load = sizes[:held]
    tokens = t if valid is None else valid.sum()
    counts = jnp.stack([jnp.int32(1), jnp.asarray(tokens, jnp.int32),
                        load.sum(), (load > 0).sum().astype(jnp.int32),
                        load.max()])
    return HeldPairs(key=key, order=order, sizes=sizes, token_of=order // k,
                     counts=counts)


class LatentMoE(nn.Module):
    """One chip's share of a routed-expert layer whose experts live in a
    latent space (Nemotron-H ``E`` layers), plus the shared expert.

    The router is as wide as the model's ``num_experts`` and keeps the
    published ``top_k`` and weights; this layer holds the experts
    ``[experts_held[0], experts_held[0] + experts_held[1])`` and computes
    their part of the result for the token-expert pairs that land on them:
    ``W_up (sum over chosen e held here of w_e W2_e relu(W1_e W_down x)^2)``.
    Nothing is dropped: the pairs are sorted by expert and each projection
    is one grouped matrix product over the experts held
    (:func:`grouped_matmul`) with room for every pair.  What experts held
    elsewhere would add is left out; in an expert-parallel deployment the
    tokens are exchanged over the chips before and after this layer, and on
    one chip it runs without that exchange.  The shared expert reads the
    hidden vector and is whole here.

    ``x [tokens, hidden]``; ``valid [tokens]`` bool marks the rows that are
    real (a prefill bucket's padding and a decode step's inactive lanes are
    not): their pairs are computed nowhere and counted nowhere.  Returns
    ``(out [tokens, hidden], counts [len(MOE_COUNTERS)] int32)``.
    """

    num_experts: int
    experts_held: Tuple[int, int]
    top_k: int
    hidden_size: int
    latent_size: int
    expert_width: int
    shared_width: int
    routed_scaling_factor: float = 1.0
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, valid=None) -> Tuple[jax.Array, jax.Array]:
        h = x.shape[1]
        held = _held_inside(self.experts_held, self.num_experts)
        k = self.top_k
        normal = nn.initializers.normal(0.02)

        def dense(name, features):
            return nn.Dense(features, use_bias=False, dtype=x.dtype,
                            param_dtype=self.param_dtype, kernel_init=normal,
                            name=name)

        router_kernel = self.param("router_kernel", normal,
                                   (h, self.num_experts), jnp.float32)
        router_bias = self.param("router_bias", nn.initializers.zeros,
                                 (self.num_experts,), jnp.float32)
        w1 = self.param("experts_w1", normal,
                        (held, self.latent_size, self.expert_width),
                        self.param_dtype)
        w2 = self.param("experts_w2", normal,
                        (held, self.expert_width, self.latent_size),
                        self.param_dtype)

        with component(ROUTER):
            chosen, weights = topk_sigmoid_route(
                x, router_kernel, router_bias, k, self.routed_scaling_factor)
            pairs = held_pairs(chosen, self.experts_held, valid)
        with component(EXPERTS):
            latent = dense("latent_down", self.latent_size)(x)
            hid = grouped_matmul(latent[pairs.token_of], w1.astype(x.dtype),
                                 pairs.sizes)
            hid = jnp.square(jax.nn.relu(hid)).astype(x.dtype)
            out = grouped_matmul(hid, w2.astype(x.dtype), pairs.sizes)
            routed = pairs.combine(out, weights)
            routed = dense("latent_up", h)(routed.astype(x.dtype))
        with component(MLP):
            shared = jnp.square(jax.nn.relu(
                dense("shared_up", self.shared_width)(x)))
            shared = dense("shared_down", h)(shared)
            return routed + shared, pairs.counts


class GatedMoE(nn.Module):
    """One chip's share of a routed-expert layer of gated (SwiGLU) experts
    that read the hidden vector (DeepSeek-V3's form), plus the shared expert
    of the same shape where ``shared_width`` is not 0.

    As :class:`LatentMoE`: the router is ``num_experts`` wide and keeps the
    published ``top_k`` and weights - by ``scoring``, ``"sigmoid"`` with a
    selection bias (:func:`topk_sigmoid_route`: the chosen scores normalised
    over all ``top_k``, times ``routed_scaling_factor``) or ``"softmax"``
    without one (:func:`topk_softmax_route`), or ``"given"``: the model
    routes itself (a router that is more than one linear map) and hands
    ``route = (chosen [tokens, top_k] int32, weights [tokens, top_k]
    float32)`` in, and this layer declares no router parameter; this layer
    holds the experts ``[experts_held[0], experts_held[0] +
    experts_held[1])`` and computes ``sum over chosen e held here of w_e
    W_down_e (silu(W_gate_e x) * W_up_e x)`` through :func:`held_pairs` and
    three grouped products; what experts held elsewhere would add is left
    out.  ``x [tokens, hidden]``, ``valid [tokens]``; returns ``(out,
    counts)``."""

    num_experts: int
    experts_held: Tuple[int, int]
    top_k: int
    hidden_size: int
    expert_width: int
    shared_width: int
    routed_scaling_factor: float = 1.0
    param_dtype: Any = jnp.float32
    scoring: str = "sigmoid"

    @nn.compact
    def __call__(self, x, valid=None,
                 route=None) -> Tuple[jax.Array, jax.Array]:
        h = x.shape[1]
        held = _held_inside(self.experts_held, self.num_experts)
        normal = nn.initializers.normal(0.02)

        def dense(name, features):
            return nn.Dense(features, use_bias=False, dtype=x.dtype,
                            param_dtype=self.param_dtype, kernel_init=normal,
                            name=name)

        if self.scoring not in ("sigmoid", "softmax", "given"):
            raise ValueError(f"scoring {self.scoring!r}: 'sigmoid', "
                             f"'softmax' or 'given'")
        if (route is None) != (self.scoring != "given"):
            raise ValueError("route= is handed in with scoring 'given' and "
                             "with no other")
        if self.scoring != "given":
            router_kernel = self.param("router_kernel", normal,
                                       (h, self.num_experts), jnp.float32)
        if self.scoring == "sigmoid":
            router_bias = self.param("router_bias", nn.initializers.zeros,
                                     (self.num_experts,), jnp.float32)
        w_gate, w_up = (self.param(name, normal, (held, h, self.expert_width),
                                   self.param_dtype)
                        for name in ("experts_gate", "experts_up"))
        w_down = self.param("experts_down", normal,
                            (held, self.expert_width, h), self.param_dtype)

        with component(ROUTER):
            if self.scoring == "sigmoid":
                chosen, weights = topk_sigmoid_route(
                    x, router_kernel, router_bias, self.top_k,
                    self.routed_scaling_factor)
            elif self.scoring == "softmax":
                chosen, weights = topk_softmax_route(x, router_kernel,
                                                     self.top_k)
            else:
                chosen, weights = route
            pairs = held_pairs(chosen, self.experts_held, valid)
        with component(EXPERTS):
            rows = x[pairs.token_of]                          # [t k, hidden]
            gate = grouped_matmul(rows, w_gate.astype(x.dtype), pairs.sizes)
            up = grouped_matmul(rows, w_up.astype(x.dtype), pairs.sizes)
            hid = (jax.nn.silu(gate) * up).astype(x.dtype)
            out = grouped_matmul(hid, w_down.astype(x.dtype), pairs.sizes)
            routed = pairs.combine(out, weights).astype(x.dtype)
        if not self.shared_width:
            return routed, pairs.counts

        with component(MLP):
            shared = (jax.nn.silu(dense("shared_gate", self.shared_width)(x))
                      * dense("shared_up", self.shared_width)(x))
            return routed + dense("shared_down", h)(shared), pairs.counts
