"""A chunk's blocked read as a kernel (``ops/kv_chunk_attention.py``) against
the reads it stands for, with the kernel's own code run by the Pallas
interpreter on the CPU: alone against the loop ``_kv_chunk_read`` on the same
rows, then behind the seam - ``prefill_attend`` for a full layer,
``window_prefill_attend`` for a window layer - against the same call with
kernels off.

Blockwise sums in another order than the loop's, so kernel and reference are
compared to a tolerance: float32 ``F32_TOL`` = 5e-6 on results of O(1)
(measured <= 5e-7); bf16 ``BF16_TOL`` = 2e-2, absolute and relative (a bf16
result of magnitude 2-4 has an ulp of 1.6e-2, and the probabilities are
rounded to bf16 before the second product on both sides).  What the
comparisons guard - a row read past the chunk's end, outside the window,
another slot's or another layer's - is a NaN here, not a small error."""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import _logging
from apex_tpu.ops import kv_chunk_attention as kca
from apex_tpu.serving import kv_cache as kvc

F32_TOL, BF16_TOL = 5e-6, 2e-2
HD = 128
DTYPES = pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                                 ids=["float32", "bfloat16"])


def _close(got, want, dtype, msg=""):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all(), f"{msg}: the kernel read a NaN row"
    tol = F32_TOL if dtype == jnp.float32 else BF16_TOL
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol, err_msg=msg)


@pytest.fixture
def interpreted(monkeypatch):
    """Kernels on, run by the interpreter; yields the ``kernel_dispatch``
    events of ``kv_chunk_attention``."""
    monkeypatch.setenv("APEX_TPU_KERNELS", "interpret")
    seen = []

    def sink(event):
        if (event["event"] == "kernel_dispatch"
                and event["op"] == "kv_chunk_attention"):
            seen.append(event)

    _logging.add_event_sink(sink)
    yield seen
    _logging.remove_event_sink(sink)


def _rows(dtype, *shape, seed=0):
    return jax.random.normal(jax.random.key(seed), shape,
                             jnp.float32).astype(dtype)


@DTYPES
@pytest.mark.parametrize("m, offset", [
    (16, 0), (32, 100), (512, 300), (64, 960), (256, 128), (1024, 0)],
    ids=lambda v: str(v))
def test_kernel_matches_the_loop(monkeypatch, dtype, m, offset):
    """GQA 8 over 2, a slot of 1,024 rows in blocks of 128: chunks inside a
    block, across several, up to the last; one query tile and several; rows
    past the chunk's end are NaN."""
    monkeypatch.setenv("APEX_TPU_KERNELS", "interpret")
    heads, nkv, max_len, block = 8, 2, 1024, 128
    if offset + m > max_len:
        max_len = 2048
    q = _rows(dtype, m, heads, HD)
    k, v = (_rows(dtype, max_len, nkv, HD, seed=s) for s in (1, 2))
    live = (jnp.arange(max_len) < offset + m)[:, None, None]
    k, v = jnp.where(live, k, jnp.nan), jnp.where(live, v, jnp.nan)
    blocks = min((offset + m - 1) // block + 1, max_len // block)
    got = kca.kv_chunk_attention(q, k.transpose(1, 0, 2),
                                 v.transpose(1, 0, 2), offset, blocks,
                                 block=block)
    monkeypatch.setattr(kvc, "_key_block", lambda n: block)
    want = kvc._kv_chunk_read(q.transpose(1, 0, 2), k[None, None],
                              v[None, None], 0, 0, offset)
    assert got.shape == (m, heads, HD) and got.dtype == jnp.float32
    _close(got.transpose(1, 0, 2), want, dtype, f"m {m} offset {offset}")


def test_heads_a_step_follow_the_budget():
    """Four query heads a step at the cell's 1,024-row bucket, all under 14
    MiB; a group never spans two KV heads."""
    shape = dict(hd=128, block=512, item=2)
    assert kca.plan(1024, 8, **shape) == 4
    assert kca._vmem(4, m=1024, **shape) <= kca.VMEM_BUDGET < kca._vmem(
        8, m=1024, **shape)
    assert kca.plan(1024, 2, **shape) == 2 and kca.plan(64, 1, **shape) == 1
    assert kca.plan(1024, 6, **shape) == 3
    assert kca.kernel_takes(m=1024, hd=128, block=512, max_len=32768)
    assert kca.kernel_takes(m=16, hd=128, block=128, max_len=128)
    for bad in (dict(hd=64), dict(m=8), dict(block=96), dict(max_len=1000),
                dict(m=384)):
        assert not kca.kernel_takes(**{**dict(
            m=1024, hd=128, block=512, max_len=32768), **bad}), bad


def _short_cache(dtype, nkv, offset, hidden):
    """Two layers of two slots of 2,048 rows; slot 1 of layer 1 holds
    ``offset`` rows, everything else is ``hidden``."""
    mine = jnp.zeros((2, 2, 2048), bool).at[1, 1, :offset].set(True)
    k, v = (jnp.where(mine[..., None, None],
                      _rows(dtype, 2, 2, 2048, nkv, HD, seed=n), hidden)
            for n in (3, 4))
    return kvc.KVCache(k=k, v=v, lengths=jnp.zeros((2,), jnp.int32))


@DTYPES
@pytest.mark.parametrize("offset", [0, 40, 512, 1500],
                         ids=lambda o: f"offset{o}")
def test_full_layer_chunk_behind_the_seam(monkeypatch, interpreted, dtype,
                                          offset):
    """``prefill_attend`` on a cache whose full-extent scores pass the size
    the walk is chosen from: the kernel, against the same call with kernels
    off (the loop).  Other slots, the other layer and every row past the
    chunk are NaN in both."""
    monkeypatch.setattr(kvc, "_FULL_READ_BYTES", 0)
    s, heads, nkv = 64, 8, 2
    cache = _short_cache(dtype, nkv, offset, jnp.nan)
    q = _rows(dtype, s, 1, heads, HD, seed=5)
    k, v = (_rows(dtype, s, 1, nkv, HD, seed=n) for n in (6, 7))

    def call():
        # a function of its own each time: a second trace, not the first
        # one's program out of jit's cache
        return jax.jit(lambda *args: kvc.prefill_attend(cache, 1, *args))(
            jnp.int32(1), q, k, v, jnp.int32(offset))

    got, after = call()
    assert [(e["path"], e["m"], e["block"]) for e in interpreted] == [
        ("pallas", s, 512)]
    monkeypatch.setenv("APEX_TPU_KERNELS", "0")
    want, _ = call()
    assert got.shape == (1, heads, s, HD) and got.dtype == dtype
    _close(got, want, dtype, f"offset {offset}")
    # the chunk's rows went in where they belong, and nowhere else
    wrote = np.asarray(after.k[1, 1, offset:offset + s], np.float32)
    assert (wrote == np.asarray(k[:, 0], np.float32)).all()
    assert np.isnan(np.asarray(after.k[0], np.float32)).all()


@contextlib.contextmanager
def _dispatch_events():
    """The ``(op, path)`` of the dispatch events emitted inside."""
    seen = []

    def sink(event):
        if event["event"] in ("kernel_dispatch", "read_dispatch"):
            seen.append((event["op"], event["path"]))

    _logging.add_event_sink(sink)
    try:
        yield seen
    finally:
        _logging.remove_event_sink(sink)


@functools.cache
def _seam(kernels):
    """``prefill_attend`` on layer 1 under ``jit``, one function a setting of
    ``APEX_TPU_KERNELS`` (a trace is kept by shapes: the offsets of a bucket
    share a program, the two settings must not)."""
    return jax.jit(lambda cache, *args: kvc.prefill_attend(cache, 1, *args))


@DTYPES
@pytest.mark.parametrize("offset", [0, 512, 1536], ids=lambda o: f"offset{o}")
@pytest.mark.parametrize("m", [16, 32, 64, 128, 256, 512],
                         ids=lambda m: f"m{m}")
@pytest.mark.parametrize("nkv", [8, 2], ids=lambda n: f"kv{n}")
def test_short_cache_chunk_behind_the_seam(monkeypatch, dtype, nkv, m,
                                           offset):
    """The two short-cache cells' attention shapes - 32 query heads over 8
    KV heads (``chat-closed``) and over 2 (the hybrid cell), heads of 128,
    2,048 rows a slot - at every default bucket with one, two and four
    visible key blocks: ``prefill_attend`` takes the kernel whatever the
    extent, and agrees with :func:`cached_attention` over the whole masked
    extent (the same call with kernels off, which these shapes' 4-128 MiB
    of scores keep on ``full_extent``).  The file's tolerances for the loop
    hold against the full extent too: float32 differs by the order of its
    sums alone; bf16 rounds the probabilities to 8 bits on both sides, there
    after the division by the row's sum and here before it.  Other slots, the
    other layer and every row past the chunk are NaN for the kernel - which
    must not read them - and zeros for the masked read, whose ``0 * v`` has
    to stay finite."""
    heads = 32
    q = _rows(dtype, m, 1, heads, HD, seed=5)
    k, v = (_rows(dtype, m, 1, nkv, HD, seed=n) for n in (6, 7))

    def call(kernels, hidden, read):
        monkeypatch.setenv("APEX_TPU_KERNELS", kernels)
        cache = _short_cache(dtype, nkv, offset, hidden)
        assert kvc._prefill_read(cache, q) == read
        return _seam(kernels)(cache, jnp.int32(1), q, k, v,
                              jnp.int32(offset))

    got, after = call("interpret", jnp.nan, "kernel")
    want, _ = call("0", 0.0, "full_extent")
    assert got.shape == (1, heads, m, HD) and got.dtype == dtype
    _close(got, want, dtype, f"kv {nkv} m {m} offset {offset}")
    wrote = np.asarray(after.k[1, 1, offset:offset + m], np.float32)
    assert (wrote == np.asarray(k[:, 0], np.float32)).all()


@DTYPES
def test_a_verify_the_kernel_refuses_stays_on_the_full_extent(monkeypatch,
                                                              dtype):
    """Nine rows (a draft bucket of 8 and the token before it) are no whole
    sublane tile: with kernels on ``prefill_attend`` still takes
    ``full_extent``, says so, and returns bit for bit what it returns with
    kernels off - the parent's read."""
    m, heads, nkv, offset = 9, 32, 8, 700
    q = _rows(dtype, m, 1, heads, HD, seed=5)
    k, v = (_rows(dtype, m, 1, nkv, HD, seed=n) for n in (6, 7))
    cache = _short_cache(dtype, nkv, offset, 0.0)

    def call():
        return jax.jit(lambda *args: kvc.prefill_attend(cache, 1, *args))(
            jnp.int32(1), q, k, v, jnp.int32(offset))

    monkeypatch.setenv("APEX_TPU_KERNELS", "interpret")
    with _dispatch_events() as seen:
        got, after = call()
    assert seen == [("kv_chunk_attention", "reference"),
                    ("prefill_attend", "full_extent")]
    monkeypatch.setenv("APEX_TPU_KERNELS", "0")
    want, after_want = call()
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(np.asarray(after.k, np.float32),
                                  np.asarray(after_want.k, np.float32))


@DTYPES
@pytest.mark.parametrize("offset, s, length", [
    (0, 64, 64), (20, 64, 64), (500, 64, 40), (96, 256, 256), (1000, 16, 9)],
    ids=lambda v: str(v))
def test_window_layer_chunk_behind_the_seam(monkeypatch, interpreted, dtype,
                                            offset, s, length):
    """``window_prefill_attend`` with a window of 96 in a ring of 96: the
    kernel over the rows before the chunk and its own, under the window's
    two bounds, against the same call with kernels off (the masked read).
    Ring rows that hold no position before the chunk, the other slot and the
    other layer are NaN in both; the ring afterwards is the same."""
    window, heads, nkv = 96, 8, 2
    layer = kvc.KVWindowRows(nkv, HD, window)
    assert layer.rows == window
    held = jnp.arange(offset - window, offset)       # positions in the ring
    mine = jnp.zeros((2, 2, window), bool).at[
        1, 0, jnp.where(held >= 0, held % window, window)].set(
        True, mode="drop")
    ring_k, ring_v = (jnp.where(mine[..., None, None], _rows(
        dtype, 2, 2, window, nkv, HD, seed=n), jnp.nan) for n in (3, 4))
    cache = kvc.WindowKVCache(
        k=jnp.zeros((0, 2, 128, nkv, HD), dtype),
        v=jnp.zeros((0, 2, 128, nkv, HD), dtype),
        lengths=jnp.zeros((2,), jnp.int32), ring_k=ring_k, ring_v=ring_v,
        counters=jnp.zeros((0, 0), jnp.int32))
    q = _rows(dtype, s, 1, heads, HD, seed=5)
    k, v = (_rows(dtype, s, 1, nkv, HD, seed=n) for n in (6, 7))

    def call():
        # a function of its own each time, as above
        return jax.jit(lambda *args: kvc.window_prefill_attend(
            cache, 1, *args, window=window))(
            jnp.int32(0), q, k, v, jnp.int32(offset), jnp.int32(length))

    got, after = call()
    assert [(e["path"], e["m"], e["window"]) for e in interpreted] == [
        ("pallas", s, window)]
    monkeypatch.setenv("APEX_TPU_KERNELS", "0")
    want, after_want = call()
    assert got.shape == (1, heads, s, HD) and got.dtype == dtype
    _close(got, want, dtype, f"offset {offset} s {s}")
    for a, b in ((after.ring_k, after_want.ring_k),
                 (after.ring_v, after_want.ring_v)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
