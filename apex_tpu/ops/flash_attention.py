"""Flash attention — Pallas TPU kernels + jnp fallback.

Parity targets (SURVEY.md §2.2): the ``fmhalib`` fused attention extension
(apex/contrib/csrc/fmha/, fixed seq {128,256,384,512}, head dim 64, fp16
tile kernels) and the attention core of ``fast_multihead_attn``
(apex/contrib/csrc/multihead_attn/, CUTLASS batched GEMM + fused
softmax).  Per the SURVEY design map, one Pallas flash-attention kernel with
online softmax supersedes both: it handles arbitrary sequence lengths
(no 512 cap), causal masking, and varlen packing via segment ids, and never
materializes the [b, h, sq, sk] score matrix.

Design (TPU-first, not a translation):

- Grid ``(b*h, num_q_blocks, num_k_blocks)`` with the k axis innermost.
  Scratch accumulators (running max ``m``, running sum ``l``, output
  accumulator) persist across the sequential k steps of one q block —
  the canonical TPU online-softmax layout.  Block sizes default to 128
  (MXU-shaped); both matmuls per step hit the MXU in fp32 accumulation.
- Causal masking is generated from iota (never loaded); whole k blocks
  strictly above the diagonal are skipped with ``pl.when``.
- Varlen ("THD"/packed) sequences use segment ids: query i attends to key j
  iff ``q_seg[i] == kv_seg[j]``.  A padding mask is the special case of
  giving pad positions segment id 0 and real tokens id 1.
- Backward recomputes attention probabilities blockwise from the saved
  logsumexp (no O(s^2) residual): a dq kernel (k innermost) and a dk/dv
  kernel (q innermost), plus a cheap jnp precompute of
  ``delta = rowsum(do * o)``.
- Fully-masked query rows produce zeros, matching the fused-softmax
  extensions' convention (and their gradient is exactly zero).
- Attention dropout runs *in kernel* (parity: the reference's fused
  softmax+dropout with Philox RNG, apex/contrib/csrc/multihead_attn/,
  setup.py:647).  Like Philox, the RNG is *counter-based*: the keep bit
  for score element (bh, qpos, kpos) is a stateless integer hash of
  ``(seed, bh, qpos, kpos)`` (murmur3-finalizer avalanche), so the exact
  mask is regenerated — never stored — in the forward and both backward
  kernels, on every platform (plain jnp integer ops; no TPU-only PRNG
  primitive, so interpret-mode CPU tests cover the real code path).  The
  softmax denominator accumulates the *undropped* probabilities (dropout
  applies to the normalized matrix), and the flash backward identity
  ``delta = rowsum(do*o) = rowsum(p_kept * dp_kept)`` still holds, so the
  delta precompute is unchanged.

The jnp fallback implements identical semantics for unsupported
shapes/backends and is what the parity tests diff against.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.ops._dispatch import record_dispatch, use_interpret

_NEG_INF = -1e30
# Large default tiles: at head dims of 64-128 a (128, d) step is too little
# work to amortize grid overhead (measured 5 TF/s at 128x128 vs ~90 TF/s at
# 1024x1024 on v5e, b8 h16 s1024 d64).  VMEM at 1024x1024: the fp32 p tile is
# 4 MiB + q/k/v/do/acc tiles ≈ 7 MiB total — comfortably under the ~16 MiB
# budget for d ≤ 128.  Longer sequences keep wide tiles and grid over the
# rest (causal whole-block skip then prunes the upper triangle).
# (an isolated block sweep suggested block_q=512 wins fwd+bwd, but the full
# training step measured WORSE at 512 — 220.5 vs 213 ms/step; in-model
# measurement is authoritative, so both defaults stay 1024)
_DEFAULT_BLOCK_Q = 1024
_DEFAULT_BLOCK = 1024


# ---------------------------------------------------------------------------
# jnp reference path (also the fallback — fully differentiable)
# ---------------------------------------------------------------------------


def mha_reference(q, k, v, *, causal=False, q_segment_ids=None,
                  kv_segment_ids=None, scale=None, dropout_rate=0.0,
                  dropout_seed=None):
    """Materialized attention with flash-identical masking semantics.

    q: [b, h, sq, d]; k/v: [b, h, sk, d]; segment ids: [b, s].  Dropout
    applies to the normalized probabilities and draws the SAME counter
    hash as the Pallas kernels — per (seed, coordinates) the two paths
    realize bit-identical keep masks (pinned by
    test_kernel_and_fallback_share_dropout_stream)."""
    d = q.shape[-1]
    scale = (1.0 / d ** 0.5) if scale is None else scale
    s = jax.lax.dot_general(
        q.astype(jnp.float32) * scale, k.astype(jnp.float32),
        (((3,), (3,)), ((0, 1), (0, 1))))  # [b, h, sq, sk]
    valid = None
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        row = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        valid = (col <= row + (sk - sq))[None, None]
    if q_segment_ids is not None:
        seg = (q_segment_ids[:, None, :, None] ==
               kv_segment_ids[:, None, None, :])
        valid = seg if valid is None else jnp.logical_and(valid, seg)
    if valid is not None:
        s = jnp.where(valid, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    l = jnp.sum(e, axis=-1, keepdims=True)
    p = e / l
    if valid is not None:
        any_valid = jnp.any(valid, axis=-1, keepdims=True)
        p = jnp.where(any_valid, p, 0.0)
    if dropout_rate > 0.0:
        # the SAME counter hash as the Pallas kernels, evaluated densely:
        # a shape-driven kernel/fallback routing change cannot silently
        # change the dropout stream (r3 advisor finding), and parity tests
        # compare realizations bit-for-bit
        bb, hh, sq_, sk_ = p.shape
        g = jnp.arange(bb * hh, dtype=jnp.uint32).reshape(bb, hh, 1, 1)
        qpos = jnp.arange(sq_, dtype=jnp.uint32).reshape(1, 1, sq_, 1)
        kpos = jnp.arange(sk_, dtype=jnp.uint32).reshape(1, 1, 1, sk_)
        keep = _hash_keep(jnp.asarray(dropout_seed, jnp.uint32), g, qpos,
                          kpos, dropout_rate)
        p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
    out = jax.lax.dot_general(
        p, v.astype(jnp.float32), (((3,), (2,)), ((0, 1), (0, 1))))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas forward
# ---------------------------------------------------------------------------


def _fmix32(h):
    """murmur3's 32-bit finalizer: full avalanche (every input bit flips
    each output bit with ~1/2 probability)."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def _hash_keep(seed, g, qpos, kpos, rate):
    """Counter-based dropout keep decision for coordinates (g, qpos, kpos).

    Each coordinate is folded through the full finalizer in sequence
    (h = fmix(h ^ c)), not XOR-combined before one finalizer round: a
    single shared round would give distinct (qpos, kpos, g) triples with
    colliding pre-mix XORs identical keep bits — structured cross-position
    correlation (r3 advisor finding).  Chaining makes each coordinate
    avalanche independently, the property the reference gets from Philox
    key/counter separation.  All operands broadcast, so the same function
    serves the Pallas tiles and the dense jnp fallback — the two paths
    are bit-identical per (seed, coordinates).
    """
    h = _fmix32(seed ^ qpos)
    h = _fmix32(h ^ kpos)
    h = _fmix32(h ^ g)
    # P(h < T) = rate for T = rate * 2^32 (h uniform over uint32)
    threshold = jnp.uint32(min(int(rate * 4294967296.0), 4294967295))
    return h >= threshold


def _keep_mask(seed, g, i, j, bq, bk, rate):
    """Keep mask for tile (g, i, j).  Stateless, so the forward and both
    backward kernels regenerate the identical mask from the same
    coordinates (the Philox property the reference relies on)."""
    qpos = (i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            ).astype(jnp.uint32)
    kpos = (j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            ).astype(jnp.uint32)
    return _hash_keep(seed.astype(jnp.uint32), g.astype(jnp.uint32),
                      qpos, kpos, rate)


def _block_mask(i, j, bq, bk, sq, sk, causal, has_seg, qseg, kseg):
    """(bq, bk) bool validity for q block i vs k block j; None if all-valid."""
    valid = None
    if causal:
        row = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        col = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = col <= row + (sk - sq)
    if has_seg:
        # segment refs are lane-tiled (rows, 128); column 0 holds the ids
        seg = qseg[:, :1] == kseg[:, :1].reshape(1, bk)
        valid = seg if valid is None else jnp.logical_and(valid, seg)
    return valid


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, qseg_ref, kseg_ref, o_ref,
                lse_ref, m_scr, l_scr, acc_scr, *, scale, causal, has_seg,
                sq, sk, dropout_rate):
    g, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nj = pl.num_programs(2)
    bq, d = q_ref.shape[1], q_ref.shape[2]
    bk = k_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Whole block strictly above the causal diagonal → nothing to do.
    live = (j * bk <= i * bq + bq - 1 + (sk - sq)) if causal else True

    @pl.when(live)
    def _step():
        # matmul operands stay in the input dtype: bf16 hits the MXU at
        # native rate with fp32 accumulation; scale applies to the fp32
        # product (an fp32 upcast of q/k forces the slow multi-pass path)
        s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        valid = _block_mask(i, j, bq, bk, sq, sk, causal, has_seg,
                            qseg_ref[0] if has_seg else None,
                            kseg_ref[0] if has_seg else None)
        if valid is not None:
            s = jnp.where(valid, s, _NEG_INF)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), m_prev)
        # exp(-inf - -inf) is nan; a still-empty row keeps correction 1
        corr = jnp.where(m_prev == -jnp.inf, 0.0, jnp.exp(m_prev - m_cur))
        if has_seg or (causal and sq > sk):
            # fully-masked rows (m_cur = -inf, or finite but all-_NEG_INF)
            # exist with segment padding and with causal sq > sk (leading
            # queries see no keys); square causal always keeps the diagonal
            corr = jnp.where(m_cur == -jnp.inf, 1.0, corr)
            p = jnp.exp(jnp.where(m_cur == -jnp.inf, 0.0, s - m_cur))
            p = jnp.where(valid, p, 0.0)  # fully-masked rows stay zero
        else:
            p = jnp.exp(s - m_cur)  # masked entries: exp(-1e30 - m) == 0
        l_cur = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        if dropout_rate > 0.0:
            keep = _keep_mask(seed_ref[0], g, i, j, bq, bk, dropout_rate)
            p = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
        v = v_ref[0]
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = jnp.broadcast_to(m_cur, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_cur, l_scr.shape)

    @pl.when(j == nj - 1)
    def _finish():
        l = l_scr[:, :1]
        m = m_scr[:, :1]
        # fully-masked rows (l == 0) emit zeros; lse=+inf makes their
        # backward recomputed p exactly 0 as well
        o = jnp.where(l > 0, acc_scr[...] / jnp.where(l > 0, l, 1.0), 0.0)
        o_ref[0] = o.astype(o_ref.dtype)
        lse = jnp.where(l > 0, m + jnp.log(jnp.where(l > 0, l, 1.0)),
                        jnp.inf)
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _seg_specs(b, h, bq, bk, has_seg):
    """Block specs for [b, s]-shaped segment-id inputs (dummy if absent)."""
    if has_seg:
        qspec = pl.BlockSpec((1, bq, 128), lambda g, i, j: (g // h, i, 0))
        kspec = pl.BlockSpec((1, bk, 128), lambda g, i, j: (g // h, j, 0))
    else:
        qspec = pl.BlockSpec((1, 1, 128), lambda g, i, j: (0, 0, 0))
        kspec = pl.BlockSpec((1, 1, 128), lambda g, i, j: (0, 0, 0))
    return qspec, kspec


def _expand_seg(seg):
    """[b, s] → [b, s, 128] so segment ids tile cleanly in VMEM."""
    return jnp.broadcast_to(seg[:, :, None], (*seg.shape, 128))


def _pallas_fwd(q, k, v, qseg, kseg, seed, causal, scale, block_q, block_k,
                dropout_rate):
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq, bk = min(block_q, sq), min(block_k, sk)
    has_seg = qseg is not None
    grid = (b * h, sq // bq, sk // bk)
    qseg3 = _expand_seg(qseg) if has_seg else jnp.zeros((1, 1, 128), jnp.int32)
    kseg3 = _expand_seg(kseg) if has_seg else jnp.zeros((1, 1, 128), jnp.int32)
    sqspec, skspec = _seg_specs(b, h, bq, bk, has_seg)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          has_seg=has_seg, sq=sq, sk=sk,
                          dropout_rate=dropout_rate),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
            sqspec, skspec,
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda g, i, j: (g, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=use_interpret(),
        name="flash_attention_fwd",
    )(seed, q.reshape(b * h, sq, d), k.reshape(b * h, sk, d),
      v.reshape(b * h, sk, d), qseg3, kseg3)
    return (o.reshape(b, h, sq, d), lse[:, :, 0].reshape(b, h, sq))


# ---------------------------------------------------------------------------
# Pallas backward
# ---------------------------------------------------------------------------


def _dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               qseg_ref, kseg_ref, dq_ref, dq_scr,
               *, scale, causal, has_seg, sq, sk, dropout_rate):
    g, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nj = pl.num_programs(2)
    bq, d = q_ref.shape[1], q_ref.shape[2]
    bk = k_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    live = (j * bk <= i * bq + bq - 1 + (sk - sq)) if causal else True

    @pl.when(live)
    def _step():
        k = k_ref[0]
        s = jax.lax.dot_general(q_ref[0], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        valid = _block_mask(i, j, bq, bk, sq, sk, causal, has_seg,
                            qseg_ref[0] if has_seg else None,
                            kseg_ref[0] if has_seg else None)
        if valid is not None:
            s = jnp.where(valid, s, _NEG_INF)
        lse = lse_ref[0][:, :1]
        p = jnp.exp(s - lse)  # lse=+inf on dead rows → p = 0
        dp = jax.lax.dot_general(do_ref[0], v_ref[0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            # d(softmax) sees the dropout-masked upstream cotangent
            keep = _keep_mask(seed_ref[0], g, i, j, bq, bk, dropout_rate)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_rate)), 0.0)
        delta = delta_ref[0][:, :1]
        ds = p * (dp - delta)
        dq_scr[...] += scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                qseg_ref, kseg_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale, causal, has_seg, sq, sk, dropout_rate):
    g = pl.program_id(0)
    j, i = pl.program_id(1), pl.program_id(2)  # k block outer, q block inner
    ni = pl.num_programs(2)
    bq, d = q_ref.shape[1], q_ref.shape[2]
    bk = k_ref.shape[1]

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    live = (j * bk <= i * bq + bq - 1 + (sk - sq)) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(q, k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        valid = _block_mask(i, j, bq, bk, sq, sk, causal, has_seg,
                            qseg_ref[0] if has_seg else None,
                            kseg_ref[0] if has_seg else None)
        if valid is not None:
            s = jnp.where(valid, s, _NEG_INF)
        lse = lse_ref[0][:, :1]
        p = jnp.exp(s - lse)
        if dropout_rate > 0.0:
            keep = _keep_mask(seed_ref[0], g, i, j, bq, bk, dropout_rate)
            inv = 1.0 / (1.0 - dropout_rate)
            p_kept = jnp.where(keep, p * inv, 0.0)
        else:
            p_kept = p
        # dv sees the dropped-and-rescaled probabilities (O = P_kept V)
        dv_scr[...] += jax.lax.dot_general(
            p_kept.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            dp = jnp.where(keep, dp * inv, 0.0)
        delta = delta_ref[0][:, :1]
        ds = p * (dp - delta)
        dk_scr[...] += scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == ni - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _pallas_bwd(q, k, v, o, lse, do, qseg, kseg, seed, causal, scale,
                block_q, block_k, dropout_rate):
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq, bk = min(block_q, sq), min(block_k, sk)
    has_seg = qseg is not None
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    # [b*h, s, 128] lane-tiled copies of the per-row scalars
    lse3 = jnp.broadcast_to(lse.reshape(b * h, sq)[:, :, None],
                            (b * h, sq, 128))
    delta3 = jnp.broadcast_to(delta.reshape(b * h, sq)[:, :, None],
                              (b * h, sq, 128))
    qseg3 = _expand_seg(qseg) if has_seg else jnp.zeros((1, 1, 128), jnp.int32)
    kseg3 = _expand_seg(kseg) if has_seg else jnp.zeros((1, 1, 128), jnp.int32)
    q3 = q.reshape(b * h, sq, d)
    k3 = k.reshape(b * h, sk, d)
    v3 = v.reshape(b * h, sk, d)
    do3 = do.reshape(b * h, sq, d)

    sqspec, skspec = _seg_specs(b, h, bq, bk, has_seg)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          has_seg=has_seg, sq=sq, sk=sk,
                          dropout_rate=dropout_rate),
        grid=(b * h, sq // bq, sk // bk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda g, i, j: (g, i, 0)),
            sqspec, skspec,
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=use_interpret(),
        name="flash_attention_dq",
    )(seed, q3, k3, v3, do3, lse3, delta3, qseg3, kseg3)

    sqspec2, skspec2 = _seg_specs(b, h, bq, bk, has_seg)
    # swap index maps: grid is (bh, k block, q block)
    if has_seg:
        sqspec2 = pl.BlockSpec((1, bq, 128), lambda g, j, i: (g // h, i, 0))
        skspec2 = pl.BlockSpec((1, bk, 128), lambda g, j, i: (g // h, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          has_seg=has_seg, sq=sq, sk=sk,
                          dropout_rate=dropout_rate),
        grid=(b * h, sk // bk, sq // bq),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, d), lambda g, j, i: (g, i, 0)),
            pl.BlockSpec((1, bk, d), lambda g, j, i: (g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda g, j, i: (g, j, 0)),
            pl.BlockSpec((1, bq, d), lambda g, j, i: (g, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda g, j, i: (g, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda g, j, i: (g, i, 0)),
            sqspec2, skspec2,
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda g, j, i: (g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda g, j, i: (g, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=use_interpret(),
        name="flash_attention_dkv",
    )(seed, q3, k3, v3, do3, lse3, delta3, qseg3, kseg3)
    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


# ---------------------------------------------------------------------------
# custom_vjp + dispatch
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _flash(q, k, v, qseg, kseg, seed, causal, scale, block_q, block_k,
           dropout_rate):
    o, _ = _pallas_fwd(q, k, v, qseg, kseg, seed, causal, scale, block_q,
                       block_k, dropout_rate)
    return o


def _flash_fwd(q, k, v, qseg, kseg, seed, causal, scale, block_q, block_k,
               dropout_rate):
    o, lse = _pallas_fwd(q, k, v, qseg, kseg, seed, causal, scale, block_q,
                         block_k, dropout_rate)
    return o, (q, k, v, o, lse, qseg, kseg, seed)


def _flash_bwd(causal, scale, block_q, block_k, dropout_rate, res, do):
    q, k, v, o, lse, qseg, kseg, seed = res
    dq, dk, dv = _pallas_bwd(q, k, v, o, lse, do, qseg, kseg, seed, causal,
                             scale, block_q, block_k, dropout_rate)
    return dq, dk, dv, None, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def _kernel_ok(q, k, block_q, block_k) -> bool:
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq, bk = min(block_q, sq), min(block_k, sk)
    return record_dispatch(
        "flash_attention",
        d % 64 == 0 and sq % bq == 0 and sk % bk == 0
        and bq % 8 == 0 and bk % 8 == 0,
        sq=sq, sk=sk, d=d)


def flash_attention(q, k, v, *, causal: bool = False,
                    segment_ids=None,
                    scale: Optional[float] = None,
                    dropout_rate: float = 0.0,
                    dropout_seed=None,
                    block_q: int = _DEFAULT_BLOCK_Q,
                    block_k: int = _DEFAULT_BLOCK):
    """Fused attention: softmax(q kᵀ · scale [+ masks]) [dropout] v, never
    materializing the score matrix.

    Args:
      q: ``[b, h, sq, d]``; k, v: ``[b, h, sk, d]``.
      causal: apply a causal mask (aligned to the *last* query for sq < sk).
      segment_ids: ``None``, a single ``[b, s]`` int array (self-attention),
        or a ``(q_segment_ids, kv_segment_ids)`` pair.  Tokens attend only
        within their own segment — this is the varlen/"THD" packing story
        (reference fmha `fmha.py:33-109`) and also expresses padding masks.
      scale: logit scale; defaults to ``1/sqrt(d)``.
      dropout_rate: attention-probability dropout (kept values rescaled by
        ``1/(1-rate)``), regenerated counter-based in the backward — the
        reference's fused softmax+dropout (multihead_attn csrc).  Requires
        ``dropout_seed``.
      dropout_seed: int (or int32 scalar array) seeding the keep mask; the
        same seed reproduces the same mask exactly.
      block_q / block_k: kernel tile sizes (clamped to the sequence length).

    Returns ``[b, h, sq, d]`` in q's dtype.  Fully-masked rows give zeros.
    """
    if segment_ids is None:
        qseg = kseg = None
    elif isinstance(segment_ids, tuple):
        qseg, kseg = segment_ids
    else:
        qseg = kseg = segment_ids
    d = q.shape[-1]
    scale = (1.0 / d ** 0.5) if scale is None else float(scale)
    dropout_rate = float(dropout_rate)
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed")
    if not 0.0 <= dropout_rate < 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got {dropout_rate}")
    seed = jnp.atleast_1d(jnp.asarray(
        0 if dropout_seed is None else dropout_seed, jnp.int32))
    if _kernel_ok(q, k, block_q, block_k):
        return _flash(q, k, v, qseg, kseg, seed, causal, scale, block_q,
                      block_k, dropout_rate)
    return mha_reference(q, k, v, causal=causal, q_segment_ids=qseg,
                         kv_segment_ids=kseg, scale=scale,
                         dropout_rate=dropout_rate, dropout_seed=seed[0])
