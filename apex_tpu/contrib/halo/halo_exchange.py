"""Halo exchange for spatial parallelism (split-H/W convolutions).

Parity targets:
- ``apex.contrib.peer_memory.PeerHaloExchanger1d``
  (peer_halo_exchanger_1d.py:5-60): exchange ``half_halo`` rows with the
  two neighbors on a 1-D rank line; edge ranks zero-fill.
- ``apex.contrib.bottleneck.halo_exchangers`` (halo_exchangers.py:11-126):
  the same contract over four transports (NoComm / AllGather / SendRecv /
  Peer).

TPU design: all four reference transports exist because CUDA has four ways
to move a tensor to a neighbor; on TPU the one right answer is
``lax.ppermute`` over the spatial mesh axis — XLA lowers it to
neighbor-to-neighbor ICI sends, and *non-wrapping* permutations zero-fill
the missing edge inputs, which is exactly the reference's
``low_zero``/``high_zero`` behavior.  The functional shape also differs on
purpose: the reference mutates halo regions of a pre-padded NCHW tensor,
while here :func:`halo_exchange_1d` takes the unpadded local shard and
returns it with halos attached — the JAX-native dataflow form.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["HaloExchanger1d", "halo_exchange_1d", "left_right_halo_exchange",
           "spatial_conv2d"]


def left_right_halo_exchange(left_output_halo, right_output_halo,
                             axis_name: str):
    """Swap halos with the line neighbors (halo_exchangers.py:30-126).

    Rank i sends ``left_output_halo`` to rank i-1 and ``right_output_halo``
    to rank i+1; returns ``(left_input_halo, right_input_halo)`` — what
    arrived from the left and right neighbors — zero-filled at the ends of
    the line (non-periodic, the reference's low_zero/high_zero).
    """
    n = jax.lax.axis_size(axis_name)
    # y[i].right_input comes from x[i+1].left_output: perm (i+1 -> i)
    right_input = jax.lax.ppermute(
        left_output_halo, axis_name, [(i + 1, i) for i in range(n - 1)])
    left_input = jax.lax.ppermute(
        right_output_halo, axis_name, [(i, i + 1) for i in range(n - 1)])
    return left_input, right_input


def halo_exchange_1d(y, half_halo: int, axis_name: str, spatial_dim: int = 1):
    """Attach ``half_halo`` neighbor rows to a spatially-sharded tensor.

    ``y`` is the *unpadded* local shard ([N, H_local, W, C] for the default
    ``spatial_dim=1``, the reference's H_split=True over NHWC); returns the
    shard extended to ``H_local + 2*half_halo`` with neighbor data (zeros
    at the line edges).
    """
    if half_halo <= 0:
        return y
    size = y.shape[spatial_dim]
    if size < half_halo:
        raise ValueError(
            f"local spatial extent ({size}) smaller than half_halo "
            f"({half_halo}) — shard too thin to donate a halo")
    low_edge = jax.lax.slice_in_dim(y, 0, half_halo, axis=spatial_dim)
    high_edge = jax.lax.slice_in_dim(y, size - half_halo, size,
                                     axis=spatial_dim)
    low_halo, high_halo = left_right_halo_exchange(low_edge, high_edge,
                                                   axis_name)
    return jnp.concatenate([low_halo, y, high_halo], axis=spatial_dim)


class HaloExchanger1d:
    """Object form mirroring PeerHaloExchanger1d's call shape.

    The CUDA resource knobs (peer pool, numSM, diagnostics) have no TPU
    meaning and are absent; ranks/rank_in_group collapse into the named
    mesh axis.
    """

    def __init__(self, axis_name: str, half_halo: int):
        self.axis_name = axis_name
        self.half_halo = half_halo

    def __call__(self, y, H_split: bool = True):
        return halo_exchange_1d(y, self.half_halo, self.axis_name,
                                spatial_dim=1 if H_split else 2)


def spatial_conv2d(x, weight, axis_name: str, bias=None, stride: int = 1,
                   spatial_dim: int = 1):
    """2-D conv over an H-sharded NHWC tensor via halo exchange.

    Equivalent to running the conv on the gathered tensor with SAME
    padding, then re-sharding: interior halos come from the neighbors, the
    line edges get the zero padding.  ``weight`` is HWIO; the kernel's
    spatial extent fixes ``half_halo = (k - 1) // 2``.
    """
    kh, kw = weight.shape[0], weight.shape[1]
    if kh % 2 == 0 or kw % 2 == 0:
        raise ValueError("spatial_conv2d needs odd kernel extents")
    if stride != 1:
        # XLA's SAME padding is asymmetric for stride > 1 (left pad
        # total//2), so a symmetric halo lands the windows off the global
        # stride grid — silently wrong values, not just a shape issue
        raise NotImplementedError(
            "stride > 1 needs stride-grid-aligned asymmetric halos; shard "
            "the batch or the non-convolved spatial dim instead")
    half_halo = (kh - 1) // 2 if spatial_dim == 1 else (kw - 1) // 2
    padded = halo_exchange_1d(x, half_halo, axis_name, spatial_dim)
    # the halo'd dim is VALID-convolved (neighbors supplied the padding);
    # the other dim keeps SAME padding
    pad_h = (0, 0) if spatial_dim == 1 else ((kh - 1) // 2,) * 2
    pad_w = ((kw - 1) // 2,) * 2 if spatial_dim == 1 else (0, 0)
    out = jax.lax.conv_general_dilated(
        padded, weight, window_strides=(stride, stride),
        padding=[pad_h, pad_w],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if bias is not None:
        out = out + bias
    return out
