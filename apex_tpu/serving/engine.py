"""DecodeEngine: bucketed chunked prefill + single-token decode +
speculative multi-token verify over a KV cache.

Wraps :class:`~apex_tpu.models.llama.LlamaForCausalLM` with a *bounded*
set of compiled programs — one **prefill chunk** program per bucket in
a small power-of-two bucket table (a short prompt costs a short
dispatch instead of a full ``prefill_len``-sized one), exactly one
**batched decode step** (one token per slot), and one **speculative
verify** program per entry in a small ``draft_buckets`` table (scores
a pending token plus up to ``max_draft`` drafted candidates in one
cached multi-token forward — see :meth:`DecodeEngine.verify_draft`) —
all shape-stable by construction: chunks and drafts are padded to the
smallest covering bucket, decode always runs all ``slots`` lanes, and
the cache is preallocated (:mod:`apex_tpu.serving.kv_cache`).  The
cross-request prefix cache adds two more bounded families: a
**prefix restore** program per prefill bucket (previously captured K/V
written back verbatim — :meth:`DecodeEngine.restore_prefix`) and a
fixed-extent **region read** for block capture
(:meth:`DecodeEngine.read_region`; one compile per span extent,
bounded by the blocks-per-chunk count).  After warmup the decode jit cache
holds exactly one entry and the prefill / verify / restore jit caches
at most one entry per bucket, no matter how requests arrive
(`tests/test_serving.py` / `tests/test_serving_spec.py` /
`tests/test_serving_prefix.py` assert them through
:func:`apex_tpu.utils.compat.compile_count`).

Prompts longer than ``prefill_len`` are served by **chunked cached
prefill**: the prompt is split into ``prefill_len``-sized chunks (tail
bucketed), and each chunk's causal block attends previously cached
tokens through the same masked fixed-extent read the decode step uses —
any prompt up to ``max_len`` serves, and splitting never changes a bit.
(That fixed extent is also the cost model: a chunk's attention reads
the full ``max_len`` axis — ``O(bucket * max_len)`` — while the
bucket-scaled projections/MLP/head dominate at transformer widths; see
``docs/api/serving.md`` for the honest accounting.)

Numerics contract (the acceptance bar): in a float32 engine prefill
*and* greedy incremental decode through the cache are **bit-identical**
— same f32 logits — to the *shape-stable* uncached full-context forward
(context padded to ``max_len``, the recompile-free form a TPU server
would actually run) at every length and under every chunk split, and
produce the identical greedy argmax stream as the unpadded forward,
including GQA configs.  Ingredients: rope applied at the true position
through ``_rope_freqs``'s offset paths, attention reads masked with the
flash kernels' exact ``-1e30`` (masked ``exp`` underflows to 0.0, so
same-extent reductions round identically; see
``serving.kv_cache.cached_attention``), and logits through the same
``parallel_lm_logits`` head matmul as the plain forward (the fused LM
*head-loss* kernel is training-only — serving has no labels).  The
cached read takes the cache as it is stored — query heads grouped over
their KV head, K/V neither repeated nor upcast, operands in the cache's
dtype with float32 accumulation and softmax — so a bf16 engine runs the
flash kernel's arithmetic (bf16 products, float32 sums), and decode,
chunked prefill and speculative verification share that one read and
so one arithmetic in either precision.

Sampling is a pure function of ``(logits, key, temperature, top_k)``
with explicit PRNG keys — no ambient state, so a replayed request
reproduces its exact token stream.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec

from apex_tpu._logging import emit_event, get_logger
from apex_tpu.obs import trace as obs_trace
from apex_tpu.obs.scopes import CACHE_WRITE, HEAD, SAMPLE, component
from apex_tpu.serving.kv_cache import (
    CallCounters,
    KVCache,
    RecurrentRows,
    commit_slot_length,
    gather_slot_rows,
    init_cache,
    other_state,
    release_slot,
    value_dtype,
    write_slot_region,
)
from apex_tpu.serving.paged_kv_cache import (
    PagedCacheConfig,
    PagedCacheManager,
    blocks_per_slot,
)
from apex_tpu.serving.quant import (
    QuantConfig,
    dequant_params,
    is_quantized,
    quantize_params,
    quantized_allreduce,
    serving_param_spec,
)
from apex_tpu.utils.compat import (
    NO_REP_CHECK,
    SERVING_TP_AXIS,
    compile_count,
    serving_mesh,
    shard_map,
)

__all__ = ["DecodeEngine", "TPConfig", "default_prefill_buckets",
           "default_draft_buckets", "sample_tokens", "request_key",
           "request_key_bits", "token_key", "tp_param_shardings"]

logger = get_logger("serving.engine")

#: dtypes of the decode program's ``[slots]`` operands behind params and
#: cache, in order: the kept last-sampled vector, the host's tokens, which
#: lanes take the kept one, which lanes are active (what a test or a tool
#: that lowers ``engine._decode`` for shapes hands it)
DECODE_VECTORS = (jnp.int32, jnp.int32, jnp.bool_, jnp.bool_)


@dataclasses.dataclass(frozen=True)
class TPConfig:
    """Opt-in tensor-parallel serving over a 1-D ``size``-chip mesh.

    ``DecodeEngine(..., tp=TPConfig(size=2))`` lays the serving params
    out with the Megatron column/row split the training forward already
    uses, shards the KV cache head-wise (dense ``[layers, slots,
    max_len, kv_heads/tp, head_dim]`` and the paged block pool alike),
    replicates slot lengths and block tables, and wraps every compiled
    program family in ``shard_map`` over the mesh — so the per-layer
    psum pair (attention o_proj + MLP down_proj) runs exactly as it
    does in training.  The default (``tp=None``) keeps the single-chip
    engine byte-for-byte untouched.
    """

    size: int

    def __post_init__(self):
        if int(self.size) < 1:
            raise ValueError(f"tp size must be >= 1, got {self.size}")


def tp_param_shardings(params, mesh) -> "jax.tree_util.PyTreeDef":
    """Per-leaf :class:`NamedSharding` tree for serving params on a tp
    mesh, derived from :func:`apex_tpu.models.llama.tp_param_spec` (the
    model owns its column/row layout).  Hand this to
    :func:`apex_tpu.serving.weights.load_serving_params` to restore a
    checkpoint *directly onto the serving mesh* — no host-replicated
    detour — or ``jax.device_put`` a host tree with it.  Quant-aware:
    a weight-quantized tree's QTensor payload/scale leaves get the
    layout :func:`apex_tpu.serving.quant.serving_param_spec` derives
    from the kernel they replaced (plain fp leaves keep the exact
    ``tp_param_spec`` layout as before)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: NamedSharding(mesh, serving_param_spec(
            path, SERVING_TP_AXIS)), params)


@component(SAMPLE)
def _sample_one(logits, base_key, index, temperature, top_k):
    """One token from one ``[vocab]`` logits row — fully traced, so the
    vmapped form never retraces on per-request sampling params.

    The per-token key is derived *inside* the jitted sampler
    (``fold_in(base_key, index)``, identical to :func:`token_key`): the
    host hands over one base key per stream plus an integer index, so a
    whole decode step's sampling is ONE dispatch — no per-slot fold_in
    ops or device->host syncs on the serving hot path.

    ``temperature <= 0`` is greedy (argmax).  ``top_k > 0`` keeps only
    the k highest logits (threshold from a descending sort — ``top_k``
    is a *traced* scalar, so mixed-k batches share one compile);
    ``top_k <= 0`` means no truncation.
    """
    key = jax.random.fold_in(base_key, index)
    vocab = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    thresh = jnp.sort(logits)[::-1][jnp.clip(top_k - 1, 0, vocab - 1)]
    masked = jnp.where((top_k > 0) & (logits < thresh), -jnp.inf, logits)
    temp = jnp.where(temperature > 0, temperature, 1.0)
    tok = jax.random.categorical(key, masked / temp).astype(jnp.int32)
    return jnp.where(temperature > 0, tok, greedy)


sample_tokens = jax.jit(jax.vmap(_sample_one))
"""Batched sampler: ``(logits [n, vocab], base_keys [n, 2], indices [n],
temperatures [n], top_ks [n]) -> tokens [n]`` — deterministic per
``(base_key, index)``; equals sampling with ``token_key(base, index)``."""


def default_prefill_buckets(prefill_len: int,
                            floor: int = 16) -> tuple:
    """Power-of-two chunk-size table ``(floor, 2*floor, ...,
    prefill_len)`` — the compile-count budget of the prefill path.

    A prompt (or prompt chunk) is padded to the smallest covering
    bucket, so a short prompt costs a short dispatch while the number
    of distinct compiled prefill programs stays ``len(buckets)`` —
    logarithmic in ``prefill_len``, bounded and asserted rather than
    hoped (``DecodeEngine.prefill_compiles()``).
    """
    if floor < 2:
        # floor <= 0 would loop forever below (0 * 2 == 0); 1-row
        # chunks are rejected by the engine anyway (decode ambiguity)
        raise ValueError(f"bucket floor must be >= 2, got {floor}")
    if prefill_len <= floor:
        return (prefill_len,)
    out, b = [], floor
    while b < prefill_len:
        out.append(b)
        b *= 2
    out.append(prefill_len)
    return tuple(out)


def default_draft_buckets(max_draft: int) -> tuple:
    """Power-of-two draft-length table ``(1, 2, 4, ..., max_draft)`` —
    the compile-count budget of the speculative verify path.

    A k-token draft is padded to the smallest covering bucket (the
    verify program's width is ``bucket + 1``: the pending token plus
    the padded draft), so the number of distinct compiled verify
    programs stays ``len(buckets)`` — logarithmic in ``max_draft``,
    bounded and asserted via :meth:`DecodeEngine.verify_compiles`
    exactly like the prefill buckets.
    """
    if max_draft < 1:
        raise ValueError(f"max_draft must be >= 1, got {max_draft}")
    out, b = [], 1
    while b < max_draft:
        out.append(b)
        b *= 2
    out.append(max_draft)
    return tuple(out)


def request_key(seed: int) -> jax.Array:
    """Base PRNG key for one request (explicit, replayable)."""
    return jax.random.PRNGKey(seed)


def request_key_bits(seed: int) -> np.ndarray:
    """:func:`request_key`'s two ``uint32`` words, made on the host: the same
    bits as ``np.asarray(request_key(seed))`` (the seed's high and low 32
    bits; the high word is 0 unless 64-bit mode is on) without the eager
    device program and the read that waits behind whatever the device has
    queued.  What the scheduler's admission calls.  Another default PRNG
    than threefry has another key layout and takes the device's path."""
    if jax.config.jax_default_prng_impl != "threefry2x32":
        return np.asarray(request_key(seed))
    seed = int(np.int64(seed))       # out of range raises, as PRNGKey does
    high = (seed >> 32) & 0xFFFFFFFF if jax.config.jax_enable_x64 else 0
    return np.array([high, seed & 0xFFFFFFFF], np.uint32)


def token_key(base: jax.Array, index) -> jax.Array:
    """Key for the ``index``-th generated token of a request."""
    return jax.random.fold_in(base, index)


class DecodeEngine:
    """KV-cached incremental decoding for a Llama-family model.

    >>> eng = DecodeEngine(model, params, slots=8, max_len=512,
    ...                    prefill_len=64)
    >>> first_logits = eng.prefill(slot=0, tokens=prompt_ids)
    >>> logits = eng.decode(tokens, active)       # one step, all slots
    >>> eng.release(0)                            # O(1) slot reuse

    The engine owns the cache functionally: every call swaps in the
    updated :class:`KVCache`.  ``slots``/``max_len``/``prefill_len``/
    ``prefill_buckets`` are compile-time constants — ``prefill_len`` is
    the *chunk-size* ceiling (prompts up to ``max_len`` serve; anything
    longer than ``prefill_len`` is split into chunks), and each chunk
    is padded to the smallest covering bucket (the padded K/V are
    written but never readable, because per-slot lengths mask them).
    """

    def __init__(self, model, params, *, slots: int = 8,
                 max_len: int = 512, prefill_len: int = 64,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 draft_buckets: Optional[Sequence[int]] = None,
                 cache_dtype=None,
                 paged: Optional[PagedCacheConfig] = None,
                 tp: Optional[TPConfig] = None,
                 quant: Optional[QuantConfig] = None):
        if prefill_len < 2:
            raise ValueError("prefill_len must be >= 2 (a length-1 "
                             "prefill is indistinguishable from a decode "
                             "step; pad the buffer)")
        if prefill_len > max_len:
            raise ValueError(f"prefill_len {prefill_len} > max_len "
                             f"{max_len}")
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if prefill_buckets is None:
            prefill_buckets = default_prefill_buckets(int(prefill_len))
        buckets = tuple(int(b) for b in prefill_buckets)
        if not buckets or list(buckets) != sorted(set(buckets)):
            raise ValueError(f"prefill_buckets must be non-empty, "
                             f"strictly ascending ints, got {buckets}")
        if buckets[0] < 2:
            raise ValueError(f"prefill buckets must be >= 2 (a 1-row "
                             f"chunk is indistinguishable from a decode "
                             f"step), got {buckets}")
        if buckets[-1] != int(prefill_len):
            raise ValueError(
                f"the largest prefill bucket must equal prefill_len "
                f"{prefill_len} (it is the full-chunk program), got "
                f"{buckets}")
        if draft_buckets is None:
            # a verify writes bucket+1 rows, so the widest default
            # draft must leave room in even the smallest cache
            draft_buckets = default_draft_buckets(min(8, int(max_len) - 1))
        dbuckets = tuple(int(b) for b in draft_buckets)
        if not dbuckets or list(dbuckets) != sorted(set(dbuckets)):
            raise ValueError(f"draft_buckets must be non-empty, strictly "
                             f"ascending ints, got {dbuckets}")
        if dbuckets[0] < 1:
            raise ValueError(f"draft buckets must be >= 1 (a 0-token "
                             f"draft has nothing to verify), got "
                             f"{dbuckets}")
        if dbuckets[-1] >= int(max_len):
            raise ValueError(
                f"largest draft bucket {dbuckets[-1]} must be < max_len "
                f"{max_len} (a verify writes bucket+1 rows into the "
                f"cache)")
        # opt-in quantized serving: validate the lever combination up
        # front (quant=None keeps every code path below byte-for-byte
        # untouched — same traces, same events, same token bytes)
        self._quant_cfg = quant
        if quant is not None:
            if quant.allreduce and tp is None:
                raise ValueError(
                    "QuantConfig(allreduce=True) without tp= — the "
                    "quantized collective replaces the per-layer tp "
                    "psum pair; a single-chip engine has no psum to "
                    "quantize")
            if quant.kv and cache_dtype is not None:
                raise ValueError(
                    "cache_dtype with QuantConfig(kv=True) — the KV-"
                    "int8 cache owns its storage dtype (int8 payload "
                    "+ fp32 scales); drop one of the two")
        self.model = model
        self.params = params
        self.slots = int(slots)
        # a model declares what each layer keeps a slot (K/V rows, a ring
        # of window K/V rows, a recurrent state, latent rows, a window ring,
        # counters) and the
        # cache is built from that.  What pages, shards, quantizes, copies or
        # rolls back K/V rows knows nothing of the others, so for a model
        # that keeps them each such mechanism is refused by name through
        # refuse_other_state: the options here, the methods, the scheduler's
        self._layers = tuple(model.cache_layers())
        self._other_state = other_state(self._layers)
        self._recurrent = any(isinstance(l, RecurrentRows)
                              for l in self._layers)
        for given, what in (
                (paged is not None, "paged= (a block table pages K/V rows)"),
                (tp is not None, "tp= (the model's mixers have no "
                 "tensor-parallel layout)"),
                (quant is not None and quant.kv, "QuantConfig(kv=True) (the "
                 "int8 format stores K/V rows)")):
            if given:
                self.refuse_other_state(what)
        # the layers that say what a decode step reads of their rows
        # (``rows_read``: a latent-attention model's, a window layer's), for
        # the engine.decode span's counts and their running sums
        self._reading = [l for l in self._layers if hasattr(l, "rows_read")]
        self._rows_read: dict = {}
        # opt-in tensor parallelism: validate the head/vocab split up
        # front (a bad divisor must fail at construction, not as an XLA
        # sharding error three calls later) and build the serving mesh.
        # tp=None (the default) leaves every code path below untouched.
        self._tp_cfg = tp
        self._mesh = None
        if tp is not None:
            from apex_tpu.models.llama import validate_tp_divisibility
            validate_tp_divisibility(model.config, tp.size)
            self._mesh = serving_mesh(tp.size)
        self.max_len = int(max_len)
        self.prefill_len = int(prefill_len)
        self.prefill_buckets = buckets
        self.draft_buckets = dbuckets
        if cache_dtype is None:
            # serve in the params' own precision (bf16 params -> bf16
            # cache); fall back to f32 for exotic all-int trees
            floats = [l.dtype for l in jax.tree.leaves(params)
                      if hasattr(l, "dtype")
                      and jnp.issubdtype(l.dtype, jnp.floating)]
            cache_dtype = floats[0] if floats else jnp.float32
        # weight-int8 at boot, AFTER the cache dtype inference (the
        # quantized tree's fp leaves are the scales — inferring from
        # them would serve a bf16 model with an f32 cache).  A pre-
        # quantized tree (load_serving_params(quantize=True), or a
        # rollback buffer) passes through untouched.
        if quant is not None and quant.weights and not is_quantized(params):
            params = quantize_params(params)
            self.params = params
        # opt-in paged layout: a global block pool + per-slot block
        # tables, host-managed by a PagedCacheManager (allocation,
        # refcounts, CoW planning).  None (the default) keeps the dense
        # per-slot cache byte-for-byte as before — every PR-4..9
        # guarantee stays provable side by side.
        self._paged_cfg = paged
        self._pager: Optional[PagedCacheManager] = None
        if paged is not None:
            bs = int(paged.block_size)
            if bs > max_len:
                raise ValueError(
                    f"paged block_size {bs} exceeds max_len {max_len}")
            self._pager = PagedCacheManager(
                slots=slots, max_len=max_len, block_size=bs,
                num_blocks=paged.pool_blocks(slots, max_len))
        # commit the fresh cache to its device up front: the first
        # prefill otherwise sees UNCOMMITTED zeros while every later
        # call sees the jit output's committed placement — same trace,
        # but pjit specializes a SECOND executable for the changed
        # placement, and the "compiles bounded by the bucket table"
        # contract would be off by one (environment-dependently)
        fresh = init_cache(self._layers, slots=slots, max_len=max_len,
                           dtype=cache_dtype, paged=paged,
                           int8=quant is not None and quant.kv)
        if self._pager is not None:
            self._pager.consume_dirty()     # device holds this snapshot
        if tp is None:
            # _host_target is where host-side snapshots (table flushes,
            # length mirrors, restore chunks) get committed before a
            # dispatch — the single local device here, a replicated
            # NamedSharding under tp.  Same committed-placement rule
            # either way.
            self._device = jax.local_devices()[0]
            self._host_target = self._device
            self._cache_specs = None
            self._cache = jax.device_put(fresh, self._device)
            # pin (commit) the params too: jit keys its executable
            # cache on input placement, so an uncommitted boot tree
            # followed by a committed checkpoint-restored swap
            # candidate would retrace every program family once —
            # the zero-compile hot-swap contract needs one placement
            # signature from boot onward
            self.params = jax.device_put(params, self._device)
        else:
            self._device = jax.local_devices()[0]
            P = PartitionSpec
            # head-wise cache split: dense [layers, slots, max_len,
            # kv_heads, head_dim] and the paged pool [layers, blocks,
            # block_size, kv_heads, head_dim] both carry kv_heads on
            # axis 3; lengths and block tables are replicated (every
            # rank needs them to mask/route identically)
            # no trailing None: jit outputs carry the canonical short
            # spec, and the init-time placement must hash identically
            # or the first post-decode prefill retraces
            # the KV-int8 scale arrays (dense [layers, slots, max_len,
            # kv_heads], paged pools [layers, blocks, block_size,
            # kv_heads]) carry kv_heads on axis 3 exactly like the
            # payload, so one spec covers all four fields
            kvspec = P(None, None, None, SERVING_TP_AXIS)
            self._cache_specs = jax.tree_util.tree_map_with_path(
                lambda path, _: (kvspec
                                 if jax.tree_util.keystr(path) in
                                 (".k", ".v", ".k_scale", ".v_scale")
                                 else P()), fresh)
            self._host_target = NamedSharding(self._mesh, P())
            # restore/read chunks are [layers, rows, kv_heads, head_dim]
            # — kv_heads on axis 2 outside the cache container
            self._kv_chunk_sharding = NamedSharding(
                self._mesh, P(None, None, SERVING_TP_AXIS))
            cache_shardings = jax.tree.map(
                lambda s: NamedSharding(self._mesh, s), self._cache_specs,
                is_leaf=lambda x: isinstance(x, PartitionSpec))
            self._cache = jax.device_put(fresh, cache_shardings)
            # lay the params out column/row-split on the mesh (a no-op
            # transfer when weights.load_serving_params already restored
            # them onto this very layout)
            self.params = jax.device_put(
                params, tp_param_shardings(params, self._mesh))
        # slots whose K/V arrived via restore_prefix (slot -> restored
        # token count): the ONLY slots prefill() accepts a nonzero
        # resume offset for — an arbitrary occupied slot is still
        # rejected loudly (the PR-4 clobber guard), but a slot the
        # engine itself verified and restored may legitimately resume
        # mid-prompt
        self._restored: dict[int, int] = {}
        # host mirror of per-slot lengths: lets every call validate slot
        # bounds and cache capacity WITHOUT a device->host sync on the
        # decode hot path (the cache's scatters drop out-of-range rows
        # silently — overflow must be an error, not a lost token)
        self._lengths_host = np.zeros((self.slots,), np.int64)
        # each slot's last sampled token, kept on the device (keep_sampled)
        # so that the next decode step can be enqueued before the host has
        # read it; committed like the cache, for the same one-program reason
        self._last = jax.device_put(np.zeros((self.slots,), np.int32),
                                    self._host_target)
        # monotonic weight-buffer generation: bumped by swap_params so
        # host layers (the prefix cache's version tags, the reloader's
        # rollback bookkeeping) can tell which weights produced a byte
        self._weights_version = 0

        # weight-int8: every program body expands QTensor leaves back
        # to fp INSIDE its jit (XLA fuses the int8*scale read into the
        # surrounding matmul; the HBM-resident tree stays int8).  The
        # off path binds the identity — the traced graph is the byte-
        # identical fp graph, so quant=None engines keep every compile
        # and numerics contract untouched.
        if quant is not None and quant.weights:
            dq = dequant_params
        else:
            def dq(p):
                return p

        def _prefill(params, cache, ids, slot, offset, length):
            # ids [1, B] (one bucket's shape — jit compiles one program
            # per bucket, never per prompt length); offset = tokens
            # already cached in the slot; length = REAL tokens in this
            # chunk.  Returns the logits at the chunk's last real
            # position (the next-token distribution after the final
            # chunk) + the filled cache.  The model is told the length
            # too: K/V rows of the padding are hidden afterwards by the
            # commit below, a recurrent state must not take them in at all
            logits, cache = model.apply(dq(params), ids, kv_cache=cache,
                                        slot=slot, position=offset,
                                        length=length)
            cache = commit_slot_length(cache, slot, offset + length)
            with component(HEAD):
                last = lax.dynamic_index_in_dim(logits[:, 0, :], length - 1,
                                                axis=0, keepdims=False)
                return last.astype(jnp.float32), cache

        def _decode(params, cache, last, tokens, on_device, active):
            # tokens [slots] int32 (last sampled per slot); active [slots]
            # bool — inactive lanes still compute (shape stability) but
            # never advance their length, so their writes are unreadable.
            # Where an idle lane's write goes is the layout's to say
            # (its own masked rows, or nowhere).  A lane in on_device
            # [slots] bool takes its token from last [slots] int32, the
            # sampled tokens kept on the device (keep_sampled), not from
            # the host's vector: the host may not have read it yet
            tokens = jnp.where(on_device, last, tokens)
            position = cache.decode_positions(active)
            # the model is told the active lanes too: an idle lane's K/V
            # write is hidden by its length, its recurrent state must not
            # move
            logits, cache = model.apply(dq(params), tokens[:, None],
                                        kv_cache=cache, position=position,
                                        active=active)
            with component(CACHE_WRITE):
                cache = dataclasses.replace(
                    cache,
                    lengths=cache.lengths + active.astype(jnp.int32))
            with component(HEAD):
                return logits[0].astype(jnp.float32), cache

        def _verify(params, cache, ids, slot, offset, length):
            # ids [1, W] where W = draft_bucket + 1: the slot's PENDING
            # token (sampled but not yet cached — decode's invariant)
            # followed by the (padded) draft.  Runs the chunked-prefill
            # machinery — rope at the true positions, K/V written at
            # offset.., per-row causal bounds over the whole masked
            # cache — but keeps EVERY row's logits instead of slicing
            # the last one: row i is the next-token distribution after
            # ids[0, :i+1], bit-identical to the single-token decode
            # logits at that depth (same fixed-extent reductions).
            # Acceptance runs on device so dispatch + rollback is ONE
            # program: a = longest prefix where the target's own argmax
            # agrees with the draft (only the length-1 REAL draft rows
            # count), and the length commit rolls the slot back to
            # offset + a + 1 — the rejected rows' K/V become unreadable
            # in the same program that wrote them.
            logits, cache = model.apply(dq(params), ids, kv_cache=cache,
                                        slot=slot, position=offset)
            rows = logits[:, 0, :].astype(jnp.float32)   # [W, vocab]
            if tp is not None:
                # under shard_map each rank holds only its vocab shard
                # of the rows; acceptance must argmax the FULL vocab
                # identically on every rank (a shard-local argmax would
                # diverge per rank and corrupt the replicated committed
                # length), so gather the shards back before deciding
                rows = lax.all_gather(rows, SERVING_TP_AXIS, axis=1,
                                      tiled=True)
            greedy = jnp.argmax(rows, axis=-1).astype(jnp.int32)
            w = ids.shape[1]
            real = jnp.arange(w - 1, dtype=jnp.int32) < (length - 1)
            match = (greedy[:-1] == ids[0, 1:]) & real
            accepted = jnp.sum(jnp.cumprod(match.astype(jnp.int32)))
            cache = commit_slot_length(cache, slot, offset + accepted + 1)
            return greedy, rows, accepted.astype(jnp.int32), cache

        def _restore(cache, k_blk, v_blk, slot, start, length):
            # k_blk / v_blk [layers, B, kvh, hd] (one restore bucket's
            # shape — compiles are bounded by the prefill bucket table,
            # never per prefix length); start = rows already restored,
            # length = REAL rows in this chunk (padding rows past it
            # land beyond the committed length: masked garbage, exactly
            # like a prefill chunk's bucket padding, and any overhang
            # past max_len is dropped by the per-row scatter)
            cache = write_slot_region(cache, slot, start, k_blk, v_blk)
            return commit_slot_length(cache, slot, start + length)

        def _keep(last, sampled, lanes):
            # sampled [slots], or [1] for the one lane of a prompt's first
            # token; lanes [slots] bool
            return jnp.where(lanes, sampled, last)

        def _cow(cache, src, dst):
            # copy-on-write block copy: pool block src -> dst across
            # every layer, ONE compiled program for every (src, dst)
            # pair (both traced scalars).  Runs BEFORE the write that
            # needed it, so the writer lands on a private copy while
            # the sharers keep the original bytes — bit-isolation by
            # construction.
            return cache.copy_block(src, dst)

        def _read(cache, slot, start, *, n):
            # the traced-start twin of kv_cache.read_slot_region (same
            # row gather; the module primitive takes host ints while a
            # capture wants ONE compiled program for every block offset
            # — static extent, traced start).  gather_slot_rows hands a
            # KV-int8 cache's rows back DEQUANTIZED fp32, so prefix
            # capture and preemption snapshots stay quant-oblivious.
            rows = jnp.asarray(start, jnp.int32) + jnp.arange(
                n, dtype=jnp.int32)
            return gather_slot_rows(cache, slot, rows)

        if quant is not None and quant.allreduce:
            # grouped-scale int8 psum: the override is TRACE-time state
            # (reduce_from consults it while the body's jaxpr is built),
            # and jit runs the python body exactly once per program
            # family/shape — so wrapping the bodies swaps the collective
            # into every traced program while the executed XLA keeps no
            # python in the loop.  Scoped to kind="row_linear": only the
            # per-layer o_proj/down_proj psum pair quantizes; embedding
            # and logits reductions stay exact.
            from apex_tpu.transformer.tensor_parallel.mappings import (
                override_forward_allreduce,
            )

            def _with_quant_psum(body):
                def wrapped(*args):
                    with override_forward_allreduce(quantized_allreduce):
                        return body(*args)
                return wrapped

            _prefill = _with_quant_psum(_prefill)
            _decode = _with_quant_psum(_decode)
            _verify = _with_quant_psum(_verify)

        # the cache argument is donated: the engine discards the old
        # functional copy on every call, and without aliasing each
        # one-token step would copy the whole preallocated k/v pair
        if tp is None:
            self._prefill = jax.jit(_prefill, donate_argnums=(1,))
            self._decode = jax.jit(_decode, donate_argnums=(1,))
            self._verify = jax.jit(_verify, donate_argnums=(1,))
            self._restore = jax.jit(_restore, donate_argnums=(0,))
            self._cow = jax.jit(_cow, donate_argnums=(0,))
            self._keep = jax.jit(_keep)
            # NOT donated: a region read must leave the cache intact,
            # and its outputs are fresh owned buffers the prefix cache
            # keeps alive across later (donating) engine calls
            self._read = jax.jit(_read, static_argnames=("n",))
        else:
            # tensor-parallel wiring: the SAME program bodies, wrapped
            # in shard_map over the serving mesh inside the same jit
            # (donation included).  The tensor_parallel layers probe
            # the mapped axis via tp_world_size("tp") — bound inside
            # the shard_map they shard automatically, so model code
            # needs no serving-specific branches, and each family still
            # compiles the same bounded program count (asserted in
            # tests/test_serving_tp.py via the same compile witnesses).
            P = PartitionSpec
            TP = SERVING_TP_AXIS
            mesh = self._mesh
            cspec = self._cache_specs
            # serving_param_spec == tp_param_spec on fp leaves; QTensor
            # payload/scale leaves get the layout derived from the
            # kernel they replaced
            pspec = jax.tree_util.tree_map_with_path(
                lambda path, _: serving_param_spec(path, TP), params)
            blk = P(None, None, TP, None)   # [layers, rows, kvh, hd]
            S = P()                         # replicated scalars/ids

            def smap(body, in_specs, out_specs):
                return shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, **NO_REP_CHECK)

            self._prefill = jax.jit(
                smap(_prefill, (pspec, cspec, S, S, S, S),
                     (P(TP), cspec)), donate_argnums=(1,))
            self._decode = jax.jit(
                smap(_decode, (pspec, cspec, S, S, S, S),
                     (P(None, TP), cspec)), donate_argnums=(1,))
            # replicated like the host's vectors, whatever the sampler's
            # output was laid out as: another placement of `last` would be
            # another decode program
            self._keep = jax.jit(_keep, out_shardings=self._host_target)
            # verify's greedy/rows/accepted leave replicated: the body
            # all_gathers the vocab shards before the argmax decides
            self._verify = jax.jit(
                smap(_verify, (pspec, cspec, S, S, S, S),
                     (S, S, S, cspec)), donate_argnums=(1,))
            self._restore = jax.jit(
                smap(_restore, (cspec, blk, blk, S, S, S), cspec),
                donate_argnums=(0,))
            self._cow = jax.jit(
                smap(_cow, (cspec, S, S), cspec), donate_argnums=(0,))

            def _read_tp(cache, slot, start, *, n):
                # shard_map takes no static args: bind the extent in a
                # closure and build the mapped program inside the jit —
                # one trace per distinct n, exactly like the plain
                # static_argnames form (and still NOT donated)
                def body(c, s, t):
                    return _read(c, s, t, n=n)
                return smap(body, (cspec, S, S), (blk, blk))(
                    cache, slot, start)

            self._read = jax.jit(_read_tp, static_argnames=("n",))
        logger.debug("DecodeEngine: slots=%d max_len=%d prefill_len=%d "
                     "buckets=%s cache_dtype=%s", self.slots,
                     self.max_len, self.prefill_len,
                     self.prefill_buckets, jnp.dtype(fresh.dtype).name)
        if quant is not None:
            # quant=None emits nothing: the default-off event stream is
            # byte-identical to the fp engine's
            emit_event("serving_quant_enabled",
                       weights=bool(quant.weights), kv=bool(quant.kv),
                       allreduce=bool(quant.allreduce), tp=self.tp_size,
                       paged=self._pager is not None)

    # ---- cache/slot state ------------------------------------------------
    @property
    def cache(self) -> KVCache:
        return self._cache

    @property
    def quant(self) -> Optional[QuantConfig]:
        """The quantization config, or ``None`` on an fp engine."""
        return self._quant_cfg

    @property
    def tp(self) -> Optional[TPConfig]:
        """The tensor-parallel config, or ``None`` on a single-chip
        engine."""
        return self._tp_cfg

    @property
    def tp_size(self) -> int:
        """Mesh width the serving programs run over (1 = single-chip)."""
        return 1 if self._tp_cfg is None else int(self._tp_cfg.size)

    @property
    def mesh(self):
        """The 1-D serving tp :class:`jax.sharding.Mesh`, or ``None``
        on a single-chip engine."""
        return self._mesh

    def lengths(self) -> np.ndarray:
        """Per-slot valid-token counts (0 = free), from the host mirror
        — no device sync."""
        return self._lengths_host.copy()

    def free_slots(self) -> list[int]:
        return [i for i, n in enumerate(self._lengths_host) if n == 0]

    def cache_utilization(self) -> float:
        """Filled cache positions / total capacity, in ``[0, 1]`` — from
        the host mirror, so sampling it every step costs no device sync.
        The number an admission controller actually wants: slot
        occupancy says how many streams are live, utilization says how
        much of the preallocated KV memory their tokens fill."""
        return float(self._lengths_host.sum()) / float(
            self.slots * self.max_len)

    def _check_slot(self, slot: int) -> None:
        if not 0 <= slot < self.slots:
            raise ValueError(f"slot {slot} out of range [0, {self.slots})")

    @property
    def recurrent_state(self) -> bool:
        """Whether some layer of the model keeps a recurrent state a slot
        (then nothing that copies, shares or rolls back K/V rows serves)."""
        return self._recurrent

    @property
    def other_state(self) -> list:
        """The kinds of per-layer state the model declares beside K/V rows
        (``["RecurrentRows: a recurrent state", ...]``; empty for a model
        that keeps K/V rows alone): nothing that pages, shards, quantizes,
        copies, shares or rolls back K/V rows serves a model that has
        any."""
        return list(self._other_state)

    def refuse_other_state(self, what: str) -> None:
        """The one refusal for every slot state that is not K/V rows, naming
        the declarations and the option or method ``what`` (the engine's
        and the scheduler's alike)."""
        if self._other_state:
            raise ValueError(
                f"{what} cannot serve {type(self.model).__name__}: it moves "
                f"K/V rows, and the model declares per-layer state other "
                f"than K/V rows that is not among them (cache_layers(): "
                f"{', '.join(self._other_state)})")

    def _count_rows(self, act) -> dict:
        """What a decode step reads over the active lanes, the row it appends
        among them, summed over the layers that declare it
        (``LatentRows.rows_read``: ``index_rows``, ``attended_rows``;
        ``RingRows.rows_read``: ``window_rows``; ``KVWindowRows.rows_read``:
        ``window_rows``, ``window_live_rows``), from the host mirror: no
        readback.  Empty for a model that keeps K/V rows alone."""
        out: dict = {}
        if self._reading:
            live = self._lengths_host[act] + 1
            for layer in self._reading:
                for name, rows in layer.rows_read(live).items():
                    out[name] = out.get(name, 0) + rows
        return out

    def rows_read(self) -> dict:
        """:meth:`_count_rows` summed over every decode step so far (what
        each ``engine.decode`` span carries as attributes, for a run that
        records no spans); empty for a model that keeps K/V rows alone."""
        return dict(self._rows_read)

    def moe_stats(self) -> dict:
        """What the counting layers (routed experts) added up over every
        decode step so far: ``{name: int64 [counting layers]}``, empty for
        a model that declares none.  ONE readback, when asked: the counts
        ride the cache pytree and cost a decode step no transfer."""
        names = next((l.names for l in self._layers
                      if isinstance(l, CallCounters)), ())
        counts = np.asarray(self._cache.counters, np.int64) if names else ()
        return {name: counts[:, i] for i, name in enumerate(names)}

    def release(self, slot: int) -> None:
        """Evict a slot (O(1)); its bytes stay masked until overwritten.
        Paged engines also drop the slot's block references — blocks
        shared with a prefix-cache entry or another slot survive; the
        rest return to the pool."""
        self._check_slot(slot)
        self._cache = release_slot(self._cache, slot)
        self._lengths_host[slot] = 0
        self._restored.pop(slot, None)
        if self._pager is not None:
            self._pager.release(slot)
            self._flush_tables()

    def reset(self) -> None:
        """Free every slot (keeps compiled programs and allocations)."""
        zeros = (jnp.zeros((self.slots,), jnp.int32)
                 if self._tp_cfg is None
                 # replicated committed placement, like _flush_tables
                 else jax.device_put(np.zeros((self.slots,), np.int32),
                                     self._host_target))
        self._cache = dataclasses.replace(self._cache, lengths=zeros)
        if self._other_state:
            # a recurrent state and counters start from zero (rows are hidden
            # by the lengths); committed like every jit output, or the next
            # call retraces
            self._cache = dataclasses.replace(
                self._cache, **jax.device_put(
                    {name: jax.tree.map(jnp.zeros_like,
                                        getattr(self._cache, name))
                     for name in ("state", "counters")
                     if hasattr(self._cache, name)}, self._device))
        self._lengths_host[:] = 0
        self._restored.clear()
        if self._pager is not None:
            for slot in range(self.slots):
                self._pager.release(slot)
            self._flush_tables()

    # ---- hot weight swap (serving/reload.py's engine surface) ------------
    @property
    def weights_version(self) -> int:
        """Monotonic generation counter of the served weight buffer
        (0 == the boot params; bumped by every :meth:`swap_params`,
        including rollbacks)."""
        return self._weights_version

    def swap_params(self, params) -> Any:
        """Replace the served params with ``params``; returns the old
        buffer (the caller's rollback copy).

        The replacement tree must match the current one exactly —
        structure, leaf shapes, leaf dtypes — because every compiled
        program family (prefill, decode, verify, restore, capture
        read, CoW) takes ``params`` as a *traced* argument: a
        same-spec tree re-dispatches the already-compiled executables
        with **zero** new compiles, while a mismatched one would
        silently retrace.  The check makes the retrace impossible, so
        a validated-but-wrong candidate (e.g. a different model's
        checkpoint that happens to restore) is refused here rather
        than served.  KV cache, block tables, and per-slot lengths are
        untouched: decode state is weight-independent, so in-flight
        streams continue under the new weights with no drop.

        Under tensor parallelism the new tree is laid out onto the tp
        mesh exactly like ``__init__`` did (a no-op transfer when
        ``weights.load_serving_params(shardings=...)`` already
        restored it there).  The swap itself is a host pointer write —
        the engine is between dispatches at every scheduler step
        boundary, which is the only place a reloader calls this.
        """
        if (self._quant_cfg is not None and self._quant_cfg.weights
                and not is_quantized(params)):
            # a reloader hands the engine a freshly restored fp tree;
            # quantize it the same way boot did so the structural check
            # below compares like with like.  An already-quantized
            # candidate (the rollback buffer swap_params itself
            # returned) passes through untouched.
            params = quantize_params(params)
        old_leaves, old_def = jax.tree_util.tree_flatten(self.params)
        new_leaves, new_def = jax.tree_util.tree_flatten(params)
        if new_def != old_def:
            raise ValueError(
                f"swap_params: candidate tree structure does not match "
                f"the served params ({new_def} != {old_def}) — the "
                f"compiled programs would retrace; refuse the swap")
        for i, (o, n) in enumerate(zip(old_leaves, new_leaves)):
            if (tuple(o.shape) != tuple(n.shape)
                    or jnp.dtype(o.dtype) != jnp.dtype(n.dtype)):
                raise ValueError(
                    f"swap_params: leaf {i} is "
                    f"{tuple(n.shape)}/{jnp.dtype(n.dtype)} but the "
                    f"served params have "
                    f"{tuple(o.shape)}/{jnp.dtype(o.dtype)} — a "
                    f"different model's weights cannot be hot-swapped")
        if self._tp_cfg is not None:
            # committed mesh placement, same as __init__ — a no-op
            # when the restore already landed on these shardings
            params = jax.device_put(
                params, tp_param_shardings(params, self._mesh))
        else:
            # same committed single-device placement as __init__
            # (zero-copy when already there): committed-vs-uncommitted
            # is a jit cache key, and a placement flip would retrace
            params = jax.device_put(params, self._device)
        old = self.params
        self.params = params
        self._weights_version += 1
        return old

    # ---- paged-cache state (no-ops / None on dense engines) --------------
    @property
    def paged(self) -> Optional[PagedCacheConfig]:
        """The paged-cache config, or ``None`` on a dense engine."""
        return self._paged_cfg

    @property
    def block_pool(self) -> Optional[PagedCacheManager]:
        """The host block manager (allocation, refcounts, tables) —
        ``None`` on a dense engine."""
        return self._pager

    @property
    def block_size(self) -> Optional[int]:
        return None if self._pager is None else self._pager.block_size

    def free_blocks(self) -> Optional[int]:
        """Unallocated pool blocks (``None`` on a dense engine) — the
        admission-pricing number."""
        return None if self._pager is None else self._pager.free_blocks

    def block_pool_utilization(self) -> float:
        """Allocated pool blocks / allocatable blocks in ``[0, 1]``
        (0.0 on a dense engine) — feeds the
        ``apex_serving_block_pool_utilization`` gauge."""
        return 0.0 if self._pager is None else self._pager.utilization

    def slot_block_ids(self, slot: int) -> list[int]:
        """The pool block ids backing a slot, in token order — what a
        paged prefix cache captures (by reference, zero-copy)."""
        self._check_slot(slot)
        if self._pager is None:
            raise ValueError("slot_block_ids on a dense engine — "
                             "construct with paged=PagedCacheConfig(...)")
        return self._pager.slot_block_ids(slot)

    def block_stats(self) -> dict:
        """Cumulative pool accounting (alloc/free/CoW/alias counts) —
        empty on a dense engine."""
        return {} if self._pager is None else self._pager.stats()

    def set_block_reclaim(self, callback) -> None:
        """Install the pool's last-resort reclaim hook
        (``(n_blocks) -> freed``), consulted once before an allocation
        raises :class:`~apex_tpu.serving.paged_kv_cache.BlockPoolExhausted`
        — the scheduler wires prefix-cache eviction here."""
        if self._pager is None:
            raise ValueError("set_block_reclaim on a dense engine")
        self._pager.reclaim = callback

    def cow_compiles(self) -> int:
        """Number of distinct compiles of the copy-on-write block copy
        (<= 1: src/dst are traced scalars).  Zero until the first CoW —
        the witness that unshared workloads never pay the program."""
        return compile_count(self._cow)

    def _flush_tables(self, *, with_lengths: bool = False) -> None:
        """Install the host table mirror on the device cache — one
        small transfer, only when allocation actually changed (the
        common within-block decode step flushes nothing).  With
        ``with_lengths`` the committed-length mirror travels in the
        SAME functional replace (alias/fork commit a table and a
        length together — the zero-copy dispatch witness is that this
        is the call's only device traffic)."""
        if self._pager is not None and self._pager.consume_dirty():
            # committed placement on purpose: an uncommitted jnp array
            # here would make pjit specialize a SECOND executable for
            # the changed placement, breaking the one-decode-compile
            # contract (same trap as the init-time device_put).  Under
            # tp the target is the replicated NamedSharding — tables
            # and lengths must land identically on every rank.
            kwargs = {"tables": jax.device_put(self._pager.table_snapshot(),
                                               self._host_target)}
            if with_lengths:
                kwargs["lengths"] = jax.device_put(
                    self._lengths_host.astype(np.int32), self._host_target)
            self._cache = dataclasses.replace(self._cache, **kwargs)
        elif with_lengths:
            self._cache = dataclasses.replace(
                self._cache,
                lengths=jax.device_put(self._lengths_host.astype(np.int32),
                                       self._host_target))

    def _ensure_paged(self, writes) -> None:
        """Pre-dispatch allocation for a batch of write spans
        ``(slot, start, stop)``: allocate table entries, run the CoW
        copies any shared block needs (one compiled program per pair,
        BEFORE the write lands), and flush the table mirror once for
        the whole batch — the per-step device cost is bounded by
        [0 table flushes on within-block steps, 1 otherwise] plus one
        tiny copy per CoW'd block."""
        if self._pager is None:
            return
        pairs = []
        for slot, start, stop in writes:
            pairs.extend(self._pager.ensure(slot, start, stop))
        for src, dst in pairs:
            self._cache = self._cow(self._cache, np.int32(src),
                                    np.int32(dst))
        if pairs:
            emit_event("serving_block_cow", blocks=len(pairs))
        self._flush_tables()

    def alias_prefix(self, slot: int, block_ids: Sequence[int],
                     length: int) -> None:
        """Zero-copy prefix reuse: point a free slot's block table at
        already-resident shared blocks and commit ``length`` valid
        tokens — the paged replacement for :meth:`restore_prefix`.
        No K/V bytes move and no compiled program runs (the whole call
        is host bookkeeping plus one table/length snapshot transfer);
        each block just gains a reference, and the slot's later writes
        into any shared block copy-on-write first.  After the call
        :meth:`prefill`/``prefill_chunk`` may resume the prompt at
        offset ``length``, exactly like a restore."""
        self._check_slot(slot)
        if self._pager is None:
            raise ValueError("alias_prefix on a dense engine — use "
                             "restore_prefix (copy-based) instead")
        if self._lengths_host[slot]:
            raise ValueError(
                f"slot {slot} is occupied ({self._lengths_host[slot]} "
                f"tokens); release() it before aliasing into it")
        length = int(length)
        if not 1 <= length <= self.max_len - 1:
            raise ValueError(
                f"aliased prefix of {length} tokens not in [1, "
                f"{self.max_len - 1}] (the resume chunk must still fit)")
        bs = self._pager.block_size
        want = blocks_per_slot(length, bs)
        if len(block_ids) != want:
            raise ValueError(
                f"{len(block_ids)} blocks cannot hold exactly {length} "
                f"tokens at block_size {bs} (want {want})")
        self._pager.alias(slot, block_ids, length)
        self._lengths_host[slot] = length
        self._restored[slot] = length
        self._flush_tables(with_lengths=True)

    def fork_slot(self, src: int, dst: int) -> None:
        """Branch a live stream: share every block of ``src`` into free
        slot ``dst`` (zero-copy — refcounts only) and commit the same
        length.  Both streams may keep decoding; the first write either
        side makes into a shared block — including the partial tail
        block both are about to append into — triggers copy-on-write,
        so the streams stay bit-isolated from that point on (the
        parallel-sampling / n-best primitive)."""
        self._check_slot(src)
        self._check_slot(dst)
        self.refuse_other_state("fork_slot")
        if self._pager is None:
            raise ValueError("fork_slot on a dense engine — the dense "
                             "layout has no shareable blocks")
        if not self._lengths_host[src]:
            raise ValueError(f"fork of empty slot {src}")
        if self._lengths_host[dst]:
            raise ValueError(
                f"slot {dst} is occupied ({self._lengths_host[dst]} "
                f"tokens); release() it before forking into it")
        self._pager.fork(src, dst)
        self._lengths_host[dst] = self._lengths_host[src]
        self._flush_tables(with_lengths=True)

    def decode_compiles(self) -> int:
        """Number of distinct compiles of the decode step (1 == the
        shape-stable contract held: no per-request retraces)."""
        return compile_count(self._decode)

    def prefill_compiles(self) -> int:
        """Number of distinct compiles of the prefill-chunk program —
        bounded by ``len(prefill_buckets)`` (each bucket is one input
        shape), asserted in tier-1 and by the bench regression guard."""
        return compile_count(self._prefill)

    def restore_compiles(self) -> int:
        """Number of distinct compiles of the prefix-restore program —
        bounded by ``len(prefill_buckets)`` (a restore chunk pads to
        the same bucket table prefill uses), asserted in tier-1 and by
        the bench regression guard.  Zero until the first
        :meth:`restore_prefix` call — the witness that leaving prefix
        caching off leaves the compiled-program set untouched."""
        return compile_count(self._restore)

    def verify_compiles(self) -> int:
        """Number of distinct compiles of the speculative verify
        program — bounded by ``len(draft_buckets)`` (each bucket is one
        input width), asserted in tier-1 and by the bench regression
        guard.  Zero until the first :meth:`verify_draft` call — the
        witness that disabling speculation leaves the compiled-program
        set untouched."""
        return compile_count(self._verify)

    @property
    def max_draft(self) -> int:
        """Widest draft :meth:`verify_draft` accepts (the largest
        draft bucket)."""
        return self.draft_buckets[-1]

    def bucket_for(self, n: int) -> int:
        """Smallest prefill bucket covering an ``n``-token chunk."""
        if not 1 <= n <= self.prefill_len:
            raise ValueError(f"chunk length {n} not in [1, "
                             f"{self.prefill_len}]")
        return next(b for b in self.prefill_buckets if b >= n)

    def draft_bucket_for(self, k: int) -> int:
        """Smallest draft bucket covering a ``k``-token draft."""
        if not 1 <= k <= self.draft_buckets[-1]:
            raise ValueError(f"draft length {k} not in [1, "
                             f"{self.draft_buckets[-1]}]")
        return next(b for b in self.draft_buckets if b >= k)

    # ---- the compiled programs -------------------------------------------
    def prefill_chunk(self, slot: int, tokens: Sequence[int]) -> jax.Array:
        """Cache one prompt chunk (``<= prefill_len`` tokens) at
        ``slot``'s current depth; returns the next-token logits
        ``[vocab]`` (f32) after the chunk's last real token — the
        first-token distribution when this was the prompt's final chunk,
        an intermediate prediction otherwise.

        The chunk is padded to the smallest covering bucket (one compile
        per bucket, ever) and its causal block attends everything the
        slot already cached, so ``prefill_chunk`` *continues* a slot:
        callers own the slot's lifecycle and must feed chunks of one
        prompt in order (the scheduler does; for one-shot use call
        :meth:`prefill`, which also guards against clobbering a live
        stream).
        """
        self._check_slot(slot)
        n = len(tokens)
        bucket = self.bucket_for(n)      # raises on n < 1 / n too long
        offset = int(self._lengths_host[slot])
        with obs_trace.span("engine.prefill_chunk", slot=int(slot),
                            bucket=bucket, tokens=n, offset=offset):
            if offset + n > self.max_len:
                raise ValueError(
                    f"chunk of {n} tokens at offset {offset} overruns "
                    f"cache max_len {self.max_len}")
            ids = np.zeros((1, bucket), np.int32)
            ids[0, :n] = np.asarray(tokens, np.int32)
            # paged: allocate/CoW the REAL rows' blocks before the write
            # lands (bucket-padding rows past the frontier route to the
            # null table entry and are dropped by the scatter)
            self._ensure_paged([(slot, offset, offset + n)])
            # np scalars, not jnp: a jnp.int32() wrapper costs a
            # device_put (~35us) EACH on the dispatching host thread —
            # three of them tripled this call's host cost (see
            # PERF_NOTES; same move as read_region)
            logits, self._cache = self._prefill(
                self.params, self._cache, ids,
                np.int32(slot), np.int32(offset), np.int32(n))
            self._lengths_host[slot] = offset + n
        return logits

    def prefill(self, slot: int, tokens: Sequence[int], *,
                resume: int = 0) -> jax.Array:
        """Fill ``slot`` with a whole prompt (chunked as needed); return
        its next-token logits ``[vocab]`` (f32).  Prompts up to
        ``max_len`` serve — anything longer than ``prefill_len`` runs as
        ``prefill_len``-sized chunks plus a bucketed tail.

        ``resume`` (default 0) resumes prefill mid-prompt over
        restored cache state: it must equal the token count a preceding
        :meth:`restore_prefix` placed into this slot, and ``tokens`` is
        still the WHOLE prompt — only the uncovered suffix
        ``tokens[resume:]`` is computed.  Because the restored K/V are
        bit-identical to what prefill would have written, the resumed
        chunks (and everything after) are bit-identical to a cold
        prefill of the full prompt.  Any other nonzero-offset use is
        still rejected loudly: silently clobbering (or silently
        trusting) a live stream is the corruption class these guards
        exist for.
        """
        self._check_slot(slot)
        resume = int(resume)
        n = len(tokens)
        if not 1 <= n <= self.max_len:
            raise ValueError(f"prompt length {n} not in [1, "
                             f"{self.max_len}] (cache capacity)")
        if resume:
            if (self._restored.get(slot) != resume
                    or self._lengths_host[slot] != resume):
                raise ValueError(
                    f"prefill(resume={resume}) on slot {slot}: the slot "
                    f"holds {self._lengths_host[slot]} tokens of which "
                    f"{self._restored.get(slot, 0)} are engine-restored "
                    f"— resume must equal the restore_prefix() length "
                    f"exactly")
            if n <= resume:
                raise ValueError(
                    f"prompt of {n} tokens has no suffix past "
                    f"the {resume} restored tokens — at least the final "
                    f"prompt token must be computed to produce the "
                    f"next-token logits")
            # every argument validated: the slot is a live stream from
            # here on — a second resume (or a re-restore) over it must
            # fail the guards above.  (The mark is consumed only after
            # validation so a rejected call stays side-effect-free: the
            # caller may retry with a corrected prompt instead of
            # re-paying the whole device restore.)
            self._restored.pop(slot, None)
        elif self._lengths_host[slot]:
            raise ValueError(
                f"slot {slot} is occupied ({self._lengths_host[slot]} "
                f"tokens); release() it before prefilling — silently "
                f"clobbering a live stream is the corruption class these "
                f"guards exist for")
        logits = None
        for start in range(resume, n, self.prefill_len):
            logits = self.prefill_chunk(
                slot, tokens[start:start + self.prefill_len])
        return logits

    # ---- prefix-cache primitives (capture + restore) ---------------------
    def read_region(self, slot: int, start: int, stop: int
                    ) -> tuple[jax.Array, jax.Array]:
        """Snapshot ``[start, stop)`` of a slot's cached K/V across every
        layer: ``(k, v)`` of shape ``[layers, stop - start, kv_heads,
        head_dim]`` — fresh owned buffers (safe to hold across later
        donated cache updates).  Only *valid* rows may be read (the span
        must sit inside the slot's committed length — bytes past it are
        masked garbage by contract).  One compiled program per distinct
        extent; block-granular prefix capture batches each chunk's new
        blocks into one span read, so its compiles are bounded by
        ``ceil(prefill_len / block_size)`` distinct extents."""
        self._check_slot(slot)
        self.refuse_other_state("read_region / prefix capture")
        if self._pager is not None:
            raise ValueError(
                "read_region on a paged engine — prefix capture is "
                "by-reference there (slot_block_ids + refcounts), not "
                "by copy")
        start, stop = int(start), int(stop)
        if not 0 <= start < stop <= int(self._lengths_host[slot]):
            raise ValueError(
                f"region [{start}, {stop}) outside slot {slot}'s valid "
                f"length {int(self._lengths_host[slot])} — rows past the "
                f"committed length are masked garbage and must never be "
                f"handed out")
        # np scalars, not jnp: a jnp.int32() wrapper costs a device_put
        # (~35us) per argument, tripling this dispatch's host cost —
        # and capture rides the serving hot path
        return self._read(self._cache, np.int32(slot), np.int32(start),
                          n=stop - start)

    def capture_slot(self, slot: int) -> tuple[np.ndarray, np.ndarray, int]:
        """Snapshot a live slot's ENTIRE valid K/V to the host —
        ``(k, v, length)`` with ``k`` / ``v`` of shape ``[layers,
        length, kv_heads, head_dim]`` — the lossless-preemption capture
        primitive: :meth:`restore_prefix` of exactly these arrays into
        a free slot reproduces the slot's cache state bit for bit (the
        bytes ARE the cache's bytes), so a preempted DECODE stream
        resumes with identical f32 logits.

        The snapshot runs as :meth:`read_region` spans decomposed over
        the *prefill bucket table* (greedy largest-bucket-first, the
        sub-floor tail overlap-read inside a floor-sized span), so the
        read program's compile count stays bounded by
        ``len(prefill_buckets)`` plus at most ``prefill_buckets[0] - 1``
        sub-floor whole-slot extents — no new program family
        (:meth:`capture_compiles` is the witness).  Dense engines only:
        a paged slot is captured by *reference*
        (:meth:`slot_block_ids` + pool refcounts), never by copy.
        """
        self._check_slot(slot)
        self.refuse_other_state("capture_slot (preemption snapshot)")
        if self._pager is not None:
            raise ValueError(
                "capture_slot on a paged engine — capture by reference "
                "instead (slot_block_ids + block_pool.ref; resume via "
                "alias_prefix)")
        length = int(self._lengths_host[slot])
        if length < 1:
            raise ValueError(f"capture of empty slot {slot}")
        buckets = self.prefill_buckets
        parts_k, parts_v = [], []
        pos = 0
        while pos < length:
            rem = length - pos
            if length < buckets[0]:
                # whole slot shorter than the smallest bucket: one
                # sub-floor read (extent < buckets[0], bounded)
                lo, hi = 0, length
            elif rem >= buckets[0]:
                b = max(x for x in buckets if x <= rem)
                lo, hi = pos, pos + b
            else:
                # sub-floor tail of a longer slot: overlap-read the
                # last floor-sized span and trim the replayed rows
                lo, hi = length - buckets[0], length
            k_span, v_span = self.read_region(slot, lo, hi)
            skip = pos - lo                    # rows already captured
            parts_k.append(np.asarray(k_span)[:, skip:])
            parts_v.append(np.asarray(v_span)[:, skip:])
            pos = hi
        k = parts_k[0] if len(parts_k) == 1 else np.concatenate(
            parts_k, axis=1)
        v = parts_v[0] if len(parts_v) == 1 else np.concatenate(
            parts_v, axis=1)
        return k, v, length

    def capture_compiles(self) -> int:
        """Number of distinct compiles of the region-read program
        (shared by prefix-cache capture and preemption capture) —
        bounded by the distinct span extents those callers use
        (block-granular capture: ``ceil(prefill_len / block_size)``;
        preemption: the prefill bucket table plus sub-floor whole-slot
        lengths).  Zero until the first read — the witness that a run
        with neither feature compiles nothing extra."""
        return compile_count(self._read)

    def restore_prefix(self, slot: int, kv, length: int) -> None:
        """Place previously captured K/V back into a free slot: after
        the call the slot holds ``length`` cached tokens, bit-for-bit
        the state a cold prefill of those tokens would have produced
        (the arrays ARE prefill's output, snapshotted via
        :meth:`read_region`), and :meth:`prefill`/``prefill_chunk`` may
        resume the prompt at offset ``length``.

        ``kv`` is ``(k, v)`` with shape ``[layers, >= length, kv_heads,
        head_dim]`` (extra rows are ignored).  The write runs as
        ``prefill_len``-sized chunks padded to the prefill bucket
        table, so restore compiles are bounded by ``len(
        prefill_buckets)`` (:meth:`restore_compiles`).  ``length`` is
        capped at ``max_len - 1``: a full-cache restore could never
        compute the next-token logits the stream needs.
        """
        self._check_slot(slot)
        self.refuse_other_state("restore_prefix")
        if self._pager is not None:
            raise ValueError(
                "restore_prefix on a paged engine — hits alias shared "
                "blocks zero-copy (alias_prefix), never write K/V back")
        if self._lengths_host[slot]:
            raise ValueError(
                f"slot {slot} is occupied ({self._lengths_host[slot]} "
                f"tokens); release() it before restoring into it")
        k, v = kv
        length = int(length)
        layers = self._cache.num_layers
        tail = self._cache.k.shape[3:]          # (kv_heads, head_dim)
        for name, arr in (("k", k), ("v", v)):
            shape = tuple(getattr(arr, "shape", ()))
            if (len(shape) != 4 or shape[0] != layers
                    or shape[2:] != tail):
                raise ValueError(
                    f"restore {name} shape {shape} does not match the "
                    f"cache's [layers={layers}, n, kv_heads={tail[0]}, "
                    f"head_dim={tail[1]}] layout")
        if not 1 <= length <= min(k.shape[1], v.shape[1]):
            raise ValueError(
                f"restore length {length} not in [1, "
                f"{min(k.shape[1], v.shape[1])}] (rows provided)")
        if length > self.max_len - 1:
            raise ValueError(
                f"restored prefix of {length} tokens leaves no room in "
                f"a max_len={self.max_len} cache for the resume chunk "
                f"that must produce the next-token logits")
        # the VALUE dtype, not the storage dtype: staging a restore
        # chunk in a KV-int8 cache's int8 payload dtype would crush the
        # captured fp rows to garbage before the in-program requantize
        dtype = value_dtype(self._cache)
        for start in range(0, length, self.prefill_len):
            n = min(self.prefill_len, length - start)
            bucket = self.bucket_for(n)
            k_blk = jnp.zeros((layers, bucket) + tail, dtype)
            v_blk = jnp.zeros((layers, bucket) + tail, dtype)
            k_blk = k_blk.at[:, :n].set(
                jnp.asarray(k[:, start:start + n], dtype))
            v_blk = v_blk.at[:, :n].set(
                jnp.asarray(v[:, start:start + n], dtype))
            if self._tp_cfg is not None:
                # commit the chunk head-sharded BEFORE the dispatch:
                # an uncommitted block would cost a resharding copy
                # per chunk and a second compiled placement variant
                k_blk = jax.device_put(k_blk, self._kv_chunk_sharding)
                v_blk = jax.device_put(v_blk, self._kv_chunk_sharding)
            self._cache = self._restore(
                self._cache, k_blk, v_blk, np.int32(slot),
                np.int32(start), np.int32(n))
        self._lengths_host[slot] = length
        self._restored[slot] = length

    def decode(self, tokens, active, *, on_device=None) -> jax.Array:
        """One batched decode step: append ``tokens[slot]`` to every
        active slot, return per-slot next-token logits ``[slots, vocab]``
        (f32).  Inactive lanes return garbage rows — callers mask by
        ``active``.  Raises when an active slot is already at
        ``max_len`` (the append would silently clobber the last cached
        token otherwise).

        A lane set in ``on_device`` (``[slots]`` bool; none by default)
        appends the token :meth:`keep_sampled` last kept for it on the
        device in place of ``tokens[slot]``: the scheduler enqueues a step
        on tokens the host has not read yet.  The kept vector is an operand
        of the one compiled program on every call, so host-fed and
        device-fed lanes, mixed in any way, share it."""
        with obs_trace.span("engine.decode") as sp:
            act = np.asarray(active, bool)
            fed = (np.zeros((self.slots,), bool) if on_device is None
                   else np.asarray(on_device, bool))
            if sp is not None:
                # the cached tokens this step's attention reads: what a
                # roofline of the decode program counts as KV bytes
                sp.set_attribute("lanes", int(act.sum()))
                sp.set_attribute("kv_tokens",
                                 int(self._lengths_host[act].sum()))
            for name, rows in self._count_rows(act).items():
                self._rows_read[name] = self._rows_read.get(name, 0) + rows
                if sp is not None:
                    sp.set_attribute(name, rows)
            full = act & (self._lengths_host >= self.max_len)
            if full.any():
                raise ValueError(
                    f"slots {np.flatnonzero(full).tolist()} are at cache "
                    f"capacity ({self.max_len}); release or raise max_len")
            empty = act & (self._lengths_host == 0)
            if empty.any():
                raise ValueError(
                    f"slots {np.flatnonzero(empty).tolist()} are active "
                    f"but never prefilled — a decode step would expose a "
                    f"garbage token as their whole context")
            if self._pager is not None:
                # one batched allocation pass for every active lane, ONE
                # table flush at most (none at all on the (block_size-1)
                # of block_size steps that cross no block boundary)
                self._ensure_paged(
                    [(int(s), int(self._lengths_host[s]),
                      int(self._lengths_host[s]) + 1)
                     for s in np.flatnonzero(act)])
            if self._tp_cfg is None:
                logits, self._cache = self._decode(
                    self.params, self._cache, self._last,
                    np.asarray(tokens, np.int32), fed, act)
            else:
                # time the step wall-to-wall and publish it as
                # serving_tp_step: an honest UPPER BOUND on the per-step
                # collective cost (dispatch + compute + the per-layer psum
                # pair; exact collective attribution needs a profiler).
                # The block_until_ready adds ~nothing — the caller samples
                # from these logits immediately, syncing anyway.  tp=None
                # emits nothing: the default-off event stream is identical.
                t0 = time.perf_counter()
                logits, self._cache = self._decode(
                    self.params, self._cache, self._last,
                    np.asarray(tokens, np.int32), fed, act)
                jax.block_until_ready(logits)
                # a fleet scheduler stamps its replica name onto the engine
                # (anonymous engines splat nothing — byte-identical stream)
                replica = getattr(self, "name", None)
                emit_event("serving_tp_step", tp=self.tp_size,
                           active=int(act.sum()),
                           duration_s=time.perf_counter() - t0,
                           **({"replica": replica}
                              if isinstance(replica, str) else {}))
            self._lengths_host[act] += 1
        return logits

    def verify_draft(self, slot: int, tokens: Sequence[int]
                     ) -> tuple[int, np.ndarray, jax.Array]:
        """One speculative verify: score ``tokens`` (the slot's pending
        last-sampled token followed by 1..``max_draft`` drafted
        candidates) in ONE cached multi-token forward, accept the
        longest draft prefix the target's greedy argmax agrees with,
        and roll the slot back to the accepted depth.

        Returns ``(accepted, greedy, logits)``: ``accepted`` = draft
        tokens accepted (0 == immediate rejection); ``greedy[i]`` =
        the target's argmax after ``tokens[:i+1]`` (so the step emits
        ``tokens[1:1+accepted] + [greedy[accepted]]`` — the accepted
        draft plus the bonus token the verify forward computed for
        free, exactly the stream ``accepted + 1`` plain decode steps
        would emit, bit for bit); ``logits`` = the per-row f32
        next-token distributions ``[bucket+1, vocab]`` (rows past
        ``accepted`` scored rejected/padded context — valid for
        inspection, already rolled back on device).

        The draft is padded to the smallest covering ``draft_buckets``
        entry (one compile per bucket, ever — padded rows' K/V land
        past the committed length, unreadable like every other masked
        byte).  After the call the slot's length is
        ``offset + accepted + 1``: the pending token and accepted
        draft are cached, the bonus token is the new pending token —
        the same invariant a plain decode step leaves.
        """
        self._check_slot(slot)
        self.refuse_other_state("verify_draft (speculation rolls rejected "
                                 "rows back by a length)")
        k = len(tokens) - 1
        if k < 1:
            raise ValueError(
                f"verify_draft needs the pending token plus >= 1 draft "
                f"token, got {len(tokens)} token(s) — with no draft to "
                f"verify, run the plain decode step")
        bucket = self.draft_bucket_for(k)    # raises past max_draft
        with obs_trace.span("engine.verify_draft", slot=int(slot),
                            drafted=k):
            offset = int(self._lengths_host[slot])
            if offset == 0:
                raise ValueError(
                    f"slot {slot} was never prefilled — a verify would "
                    f"expose garbage as its whole context")
            if offset + k + 1 > self.max_len:
                raise ValueError(
                    f"verify of {k + 1} tokens at offset {offset} overruns "
                    f"cache max_len {self.max_len}")
            ids = np.zeros((1, bucket + 1), np.int32)
            ids[0, :k + 1] = np.asarray(tokens, np.int32)
            # paged: cover the pending token + the whole real draft; a
            # rollback leaves the surplus blocks owned by the slot (refs
            # untouched), so the re-decode over them re-allocates nothing
            self._ensure_paged([(slot, offset, offset + k + 1)])
            greedy, rows, accepted, self._cache = self._verify(
                self.params, self._cache, ids, np.int32(slot),
                np.int32(offset), np.int32(k + 1))
            a = int(accepted)
            self._lengths_host[slot] = offset + a + 1
            return a, np.asarray(greedy), rows

    # ---- sampling --------------------------------------------------------
    def keep_sampled(self, sampled, lanes) -> None:
        """Keep ``sampled`` (a decode step's ``[slots]`` tokens, or the
        ``[1]`` first token of the one lane set) as the last sampled token
        of ``lanes`` (``[slots]`` bool), on the device: what
        :meth:`decode` appends for a lane in ``on_device``.  One small
        program behind the sampler; nothing is read back."""
        self._last = self._keep(self._last, sampled,
                                np.asarray(lanes, bool))

    @property
    def last_sampled(self) -> jax.Array:
        """``[slots] int32`` on the device: each slot's last kept token
        (whatever a slot held before, where nothing was kept since)."""
        return self._last

    @staticmethod
    def sample(logits, base_keys, indices, temperatures,
               top_ks) -> jax.Array:
        """Vectorized deterministic sampling (see :func:`sample_tokens`)."""
        with obs_trace.span("engine.sample"):
            return sample_tokens(
                jnp.asarray(logits), jnp.asarray(base_keys),
                jnp.asarray(indices, jnp.int32),
                jnp.asarray(temperatures, jnp.float32),
                jnp.asarray(top_ks, jnp.int32))
