"""Paged KV cache (ISSUE 11): block pool + block tables with zero-copy
refcounted prefix sharing.

THE acceptance runs: paged-engine greedy streams are **bit-identical**
(exact f32 logits per step) to the dense engine across chunked prefill,
batched decode, speculative verification, and prefix reuse — including
the multi-stream scheduler interleaving where a routing bug would first
show (each decode lane must write through its OWN slot's table row, the
regression this suite pins).  Prefix-cache hits on a paged engine
perform ZERO K/V copies, witnessed by compile counts: the restore and
region-read programs never compile, and CoW only compiles once a write
actually targets a shared block.

Plus the block-table edge cases the issue names: a table exactly full
at ``max_len`` (including ``max_len`` not a block multiple), CoW on a
shared tail block with both sharers still decoding (bit-isolation both
ways), refcount-pinned blocks surviving a tight-budget eviction pass,
and allocator exhaustion raising instead of clamping.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import _logging
from apex_tpu import serving as sv
from apex_tpu.models import LlamaConfig, LlamaForCausalLM
from apex_tpu.serving.kv_cache import (
    append_token,
    decode_read,
    init_cache,
    prefill_into_slot,
)
from apex_tpu.serving.paged_kv_cache import (
    BlockPoolExhausted,
    PagedCacheConfig,
    PagedCacheManager,
    blocks_per_slot,
)
from apex_tpu.utils.compat import compile_count

# the serving suite's GQA config (kv_heads < heads)
CFG = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, max_position_embeddings=256)
MAX = 96


@pytest.fixture(scope="module")
def model():
    return LlamaForCausalLM(CFG)


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def _prompt(seed=0, n=5):
    rng = np.random.default_rng(seed)
    return [int(x) for x in rng.integers(0, CFG.vocab_size, n)]


class _EventTap:
    """Capture emit_event kinds (and payloads) for a with-block."""

    def __init__(self):
        self.events = []

    def __enter__(self):
        self._sink = lambda e: self.events.append(dict(e))
        _logging.add_event_sink(self._sink)
        return self

    def __exit__(self, *exc):
        _logging.remove_event_sink(self._sink)

    def of(self, kind):
        return [e for e in self.events if e.get("event") == kind]


# ---------------------------------------------------------------------------
# host allocator: refcounts, LIFO determinism, CoW planning
# ---------------------------------------------------------------------------


def test_manager_alloc_release_refcount_lifo():
    mgr = PagedCacheManager(slots=2, max_len=32, block_size=8,
                            num_blocks=9)        # null + 8
    assert mgr.free_blocks == 8 and mgr.used_blocks == 0
    assert mgr.utilization == 0.0
    # growth allocates deterministically (LIFO free list pops 1, 2, ...)
    assert mgr.ensure(0, 0, 20) == []            # 3 fresh blocks, no CoW
    assert mgr.slot_block_ids(0) == [1, 2, 3]
    assert mgr.used_blocks == 3 and mgr.refcount(2) == 1
    assert mgr.consume_dirty() and not mgr.consume_dirty()
    # within-span re-ensure: nothing allocated, nothing dirty
    assert mgr.ensure(0, 8, 16) == []
    assert not mgr.consume_dirty()
    # release frees in reverse token order; LIFO reuse is replayable
    assert mgr.release(0) == 3
    assert mgr.free_blocks == 8
    mgr.ensure(1, 0, 8)
    assert mgr.slot_block_ids(1) == [3]          # last freed, first reused
    assert mgr.stats()["allocated_total"] == 4
    assert mgr.stats()["freed_total"] == 3


def test_manager_alias_fork_cow_planning():
    mgr = PagedCacheManager(slots=3, max_len=32, block_size=8,
                            num_blocks=9)
    mgr.ensure(0, 0, 20)                         # blocks 1..3, tail partial
    shared = mgr.slot_block_ids(0)
    # aliasing the whole chain: every block gains a reference
    mgr.alias(1, shared, tokens=20)
    assert [mgr.refcount(b) for b in shared] == [2, 2, 2]
    assert mgr.aliased_total == 3
    # a write into slot 1's shared tail block must CoW exactly it
    pairs = mgr.ensure(1, 20, 21)
    assert len(pairs) == 1 and pairs[0][0] == shared[2]
    new = pairs[0][1]
    assert mgr.slot_block_ids(1) == shared[:2] + [new]
    assert mgr.refcount(shared[2]) == 1 and mgr.refcount(new) == 1
    assert mgr.cow_total == 1
    # releasing the original owner keeps the still-shared prefix alive
    assert mgr.release(0) == 1                   # only the un-CoW'd tail
    assert [mgr.refcount(b) for b in shared[:2]] == [1, 1]
    # fork shares every block of a live slot (no aliased_total noise)
    before = mgr.aliased_total
    mgr.fork(1, 2)
    assert mgr.slot_block_ids(2) == mgr.slot_block_ids(1)
    assert mgr.aliased_total == before
    assert mgr.refcount(new) == 2


def test_manager_validation_and_guards():
    mgr = PagedCacheManager(slots=2, max_len=16, block_size=8,
                            num_blocks=5)
    with pytest.raises(ValueError):              # ref of a free block
        mgr.ref([1])
    with pytest.raises(ValueError):              # deref must pair
        mgr.deref([1])
    mgr.ensure(0, 0, 16)
    with pytest.raises(ValueError):              # alias into occupied
        mgr.alias(0, [1], tokens=8)
    with pytest.raises(ValueError):              # tokens not coverable
        mgr.alias(1, [1], tokens=9)
    with pytest.raises(ValueError):              # table overflow
        mgr.alias(1, [1, 2, 1], tokens=17)
    with pytest.raises(ValueError):              # span outside capacity
        mgr.ensure(0, 8, 17)
    with pytest.raises(ValueError):              # fork of empty slot
        mgr.fork(1, 0)
    with pytest.raises(ValueError):
        PagedCacheManager(slots=1, max_len=8, block_size=16, num_blocks=3)
    with pytest.raises(ValueError):
        PagedCacheManager(slots=1, max_len=8, block_size=4, num_blocks=1)
    with pytest.raises(ValueError):
        sv.PagedCacheConfig(block_size=0)
    with pytest.raises(ValueError):
        sv.PagedCacheConfig(num_blocks=1)


def test_allocator_exhaustion_raises_never_clamps():
    mgr = PagedCacheManager(slots=2, max_len=16, block_size=8,
                            num_blocks=3)        # null + 2
    mgr.ensure(0, 0, 16)                         # both blocks gone
    with pytest.raises(BlockPoolExhausted):
        mgr.ensure(1, 0, 8)
    # the failed ensure must not have corrupted slot 1's table
    assert mgr.slot_block_ids(1) == []
    # a reclaim hook that frees nothing still raises; one that frees
    # satisfies the allocation
    calls = []
    mgr.reclaim = lambda n: calls.append(n) or 0
    with pytest.raises(BlockPoolExhausted):
        mgr.ensure(1, 0, 8)
    assert calls == [1]
    mgr.reclaim = lambda n: mgr.release(0)
    assert mgr.ensure(1, 0, 8) == []
    assert mgr.slot_block_ids(1) != []


# ---------------------------------------------------------------------------
# device ops: per-slot routing + drop-safe scatters (unit level)
# ---------------------------------------------------------------------------


def _tiny_cache(slots=2, max_len=16, block_size=8, num_blocks=9):
    return init_cache(
        LlamaForCausalLM(CFG).cache_layers(), slots=slots, max_len=max_len,
        paged=PagedCacheConfig(block_size=block_size, num_blocks=num_blocks))


def test_append_routes_each_lane_through_its_own_table():
    """REGRESSION: the batched append must take the table DIAGONAL —
    row i through slot i's table.  The outer-product form (plain
    ``take`` over the last axis) scattered every lane's token through
    every slot's table at its block offset, corrupting any neighbor
    whose table had an entry at the same index: first visible as a
    one-bit stream divergence with >= 2 concurrently decoding
    scheduler streams."""
    cache = _tiny_cache()
    mgr = PagedCacheManager(slots=2, max_len=16, block_size=8,
                            num_blocks=9)
    mgr.ensure(0, 0, 9)                          # slot 0: blocks 1, 2
    mgr.ensure(1, 0, 9)                          # slot 1: blocks 3, 4
    cache = dataclasses.replace(
        cache, tables=jnp.asarray(mgr.table_snapshot()))
    hd = CFG.hidden_size // CFG.num_attention_heads
    k_tok = jnp.stack([jnp.full((CFG.kv_heads, hd), 7.0),
                       jnp.full((CFG.kv_heads, hd), 9.0)])
    # both lanes append at position 8 — block index 1 in BOTH tables
    cache = append_token(cache, 0, k_tok, k_tok,
                         jnp.asarray([8, 8], jnp.int32))
    pool = np.asarray(cache.k[0])                # [nblk, bs, kvh, hd]
    assert (pool[2, 0] == 7.0).all()             # slot 0 -> its block 2
    assert (pool[4, 0] == 9.0).all()             # slot 1 -> its block 4
    assert (pool[2, 0] != 9.0).all() and (pool[4, 0] != 7.0).all()
    # inactive sentinel (-1) and past-capacity rows are DROPPED
    cache = append_token(cache, 0, k_tok * 0 + 5.0, k_tok,
                         jnp.asarray([-1, 16], jnp.int32))
    pool = np.asarray(cache.k[0])
    assert not (pool == 5.0).any()


def test_prefill_write_drops_padding_past_frontier():
    cache = _tiny_cache()
    mgr = PagedCacheManager(slots=2, max_len=16, block_size=8,
                            num_blocks=9)
    mgr.ensure(0, 0, 5)                          # one block allocated
    cache = dataclasses.replace(
        cache, tables=jnp.asarray(mgr.table_snapshot()))
    hd = CFG.hidden_size // CFG.num_attention_heads
    chunk = jnp.full((8, CFG.kv_heads, hd), 3.0)  # bucket-padded chunk
    cache = prefill_into_slot(cache, 0, 0, chunk, chunk, start=0)
    pool = np.asarray(cache.k[0])
    assert (pool[1] == 3.0).all()                # the allocated block
    assert (pool[0] == 0.0).all()                # null block never written
    assert (pool[2:] == 0.0).all()               # nothing else touched
    # rows past the frontier (table entry null) drop silently: writing
    # at start=8 with no second block allocated lands nowhere
    cache = prefill_into_slot(cache, 0, 0, chunk * 0 + 4.0, chunk,
                              start=8)
    assert not (np.asarray(cache.k[0]) == 4.0).any()


def test_gather_view_slices_to_max_len_when_not_block_multiple():
    # max_len 20 with block_size 8 -> 3 blocks cover 24 rows; the view
    # must slice back to exactly 20 so reduction extents match dense
    cache = _tiny_cache(max_len=20)
    assert cache.blocks_per_slot == blocks_per_slot(20, 8) == 3
    k, v = decode_read(cache, 0)
    assert k.shape == (2, 20, CFG.kv_heads,
                       CFG.hidden_size // CFG.num_attention_heads)
    assert v.shape == k.shape


# ---------------------------------------------------------------------------
# engine parity: paged == dense, bit for bit
# ---------------------------------------------------------------------------


def _engines(model, params, *, max_len=MAX, block_size=16, slots=2,
             num_blocks=None, prefill_len=16):
    dense = sv.DecodeEngine(model, params, slots=slots, max_len=max_len,
                            prefill_len=prefill_len)
    paged = sv.DecodeEngine(
        model, params, slots=slots, max_len=max_len,
        prefill_len=prefill_len,
        paged=sv.PagedCacheConfig(block_size=block_size,
                                  num_blocks=num_blocks))
    return dense, paged


@pytest.mark.parametrize("block_size", [16, 12])
def test_engine_prefill_decode_bit_identical(model, params, block_size):
    """Chunked prefill + 12 greedy decode steps: every f32 logit vector
    identical between the dense and paged engines — including a
    block_size that does NOT divide max_len (the gather-slice edge)."""
    dense, paged = _engines(model, params, block_size=block_size)
    prompt = _prompt(seed=1, n=42)               # 3 chunks, bucketed tail
    ld = dense.prefill(0, prompt)
    lp = paged.prefill(0, prompt)
    assert np.array_equal(np.asarray(ld), np.asarray(lp)), (
        "paged prefill logits diverged from dense")
    for step in range(12):
        nxt = int(jnp.argmax(ld))
        ld = dense.decode(np.array([nxt, 0], np.int32),
                          np.array([True, False]))[0]
        lp = paged.decode(np.array([nxt, 0], np.int32),
                          np.array([True, False]))[0]
        assert np.array_equal(np.asarray(ld), np.asarray(lp)), (
            f"paged decode diverged from dense at step {step}")
    assert paged.decode_compiles() == 1
    assert paged.prefill_compiles() <= len(paged.prefill_buckets)


@pytest.mark.slow   # ~7 s: tier-1 keeps the dense spec-verify parity
# witnesses in test_serving.py and the sharded one in test_serving_tp.py
def test_engine_verify_draft_bit_identical(model, params):
    dense, paged = _engines(model, params)
    prompt = _prompt(seed=2, n=30)
    ld = dense.prefill(0, prompt)
    lp = paged.prefill(0, prompt)
    pending = int(jnp.argmax(ld))
    draft = _prompt(seed=3, n=4)
    draft[0] = pending                           # guarantee >= 0 accepts
    ad, gd, rd = dense.verify_draft(0, [pending] + draft)
    ap, gp, rp = paged.verify_draft(0, [pending] + draft)
    assert ad == ap and np.array_equal(gd, gp)
    assert np.array_equal(np.asarray(rd), np.asarray(rp))
    assert dense.lengths()[0] == paged.lengths()[0]
    # post-rollback decode still agrees (the rolled-back rows are
    # unreadable on both layouts)
    tok = int(gd[ad])
    ld = dense.decode(np.array([tok, 0], np.int32),
                      np.array([True, False]))[0]
    lp = paged.decode(np.array([tok, 0], np.int32),
                      np.array([True, False]))[0]
    assert np.array_equal(np.asarray(ld), np.asarray(lp))


@pytest.mark.slow   # ~12 s: tier-1 keeps the engine-level paged==dense
# bit-identity witnesses (test_engine_prefill_decode_bit_identical[12/16])
# plus the paged scheduler streams driven by the policy/fleet/rollout suites
def test_scheduler_streams_bit_identical_multi_stream(model, params):
    """THE scheduler acceptance run: 4 shared-prefix prompts through
    dense, paged, paged+speculation, and paged+prefix-caching
    schedulers — identical token streams everywhere, with prefill and
    decode interleaving across >= 2 concurrently decoding slots (the
    regime that exposes any cross-slot table routing bug)."""
    shared = _prompt(seed=4, n=40)
    prompts = [shared + _prompt(seed=100 + i, n=8) for i in range(4)]

    def run(paged, *, spec=False, prefix=False):
        eng = sv.DecodeEngine(
            model, params, slots=4, max_len=MAX, prefill_len=16,
            paged=sv.PagedCacheConfig(block_size=16) if paged else None)
        sched = sv.ContinuousBatchingScheduler(
            eng, log_interval=10 ** 9,
            speculation=sv.SpeculationConfig() if spec else None,
            prefix_caching=sv.PrefixCacheConfig() if prefix else None)
        for i, p in enumerate(prompts):
            sched.submit(sv.Request(f"r{i}", p, max_new_tokens=6))
        res = sched.run()
        return eng, sched, [res[f"r{i}"].tokens for i in range(4)]

    _, _, want = run(False)
    _, _, got = run(True)
    assert got == want, "paged scheduler streams diverged from dense"
    _, _, got = run(True, spec=True)
    assert got == want, "paged+speculation streams diverged"
    eng, sched, got = run(True, prefix=True)
    assert got == want, "paged+prefix streams diverged"
    # warm round: same prompts re-admit via zero-copy aliasing and
    # still match the dense stream bit for bit
    for i, p in enumerate(prompts):
        sched.submit(sv.Request(f"w{i}", p, max_new_tokens=6))
    res = sched.run()
    assert [res[f"w{i}"].tokens for i in range(4)] == want, (
        "warm aliased streams diverged")
    assert eng.block_stats()["aliased_total"] > 0
    # every stream drained: only the prefix cache's references remain
    assert eng.block_pool.used_blocks == len(sched.prefix_cache)


def test_table_exactly_full_at_max_len(model, params):
    """A stream may fill its table to exactly ``max_len`` (every block
    allocated, the last row written) — parity holds at the boundary and
    the overflow append still raises instead of clamping.  max_len 24
    with block_size 16 also pins the not-a-multiple table extent."""
    dense, paged = _engines(model, params, max_len=24, block_size=16,
                            prefill_len=8)
    prompt = _prompt(seed=5, n=20)
    ld = dense.prefill(0, prompt)
    lp = paged.prefill(0, prompt)
    toks = []
    for step in range(4):                        # 20 + 4 appends == 24
        nxt = int(jnp.argmax(ld))
        toks.append(nxt)
        ld = dense.decode(np.array([nxt, 0], np.int32),
                          np.array([True, False]))[0]
        lp = paged.decode(np.array([nxt, 0], np.int32),
                          np.array([True, False]))[0]
        assert np.array_equal(np.asarray(ld), np.asarray(lp)), (
            f"diverged at step {step} while filling to max_len")
    assert dense.lengths()[0] == paged.lengths()[0] == 24
    assert paged.block_pool.slot_block_ids(0) != []
    assert len(paged.block_pool.slot_block_ids(0)) \
        == blocks_per_slot(24, 16)
    for eng in (dense, paged):
        with pytest.raises(ValueError):          # full is full
            eng.decode(np.array([toks[-1], 0], np.int32),
                       np.array([True, False]))
    # release returns every block of the full table
    paged.release(0)
    assert paged.block_pool.used_blocks == 0


def test_cow_shared_tail_bit_isolation_both_ways(model, params, same_logits):
    """Fork a live stream mid-block and keep BOTH sharers decoding
    different continuations in the same batched step: the first write
    into the shared tail block copies it, each stream's logits stay
    those of a solo dense run of its own continuation (to
    ``conftest.LOGITS_ATOL``: a 1-lane step against this 2-lane one; a
    sharer reading the other's rows moves them by orders more), and
    exactly one CoW (one compile) is paid."""
    prompt = _prompt(seed=6, n=20)               # tail block 20..31 shared
    _, paged = _engines(model, params, slots=2, block_size=16)
    lp = paged.prefill(0, prompt)
    first = int(jnp.argmax(lp))
    paged.fork_slot(0, 1)
    assert paged.cow_compiles() == 0             # sharing alone is free
    conts = [first, (first + 1) % CFG.vocab_size]

    # solo dense references, one per continuation
    refs = []
    for cont in conts:
        eng = sv.DecodeEngine(model, params, slots=1, max_len=MAX,
                              prefill_len=16)
        logits = eng.prefill(0, prompt)
        steps = []
        tok = cont
        for _ in range(8):
            logits = eng.decode(np.array([tok], np.int32),
                                np.array([True]))[0]
            steps.append(np.asarray(logits))
            tok = int(jnp.argmax(logits))
        refs.append(steps)

    with _EventTap() as tap:
        toks = list(conts)
        for step in range(8):
            logits = paged.decode(np.array(toks, np.int32),
                                  np.array([True, True]))
            for slot in (0, 1):
                same_logits(
                    logits[slot], refs[slot][step],
                    f"sharer {slot} diverged from its solo run at "
                    f"step {step} — CoW bit-isolation broken")
            toks = [int(jnp.argmax(logits[s])) for s in (0, 1)]
    # exactly one block copied: the first writer CoW'd the tail, the
    # second then owned the original exclusively
    assert paged.block_stats()["cow_total"] == 1
    assert paged.cow_compiles() == 1
    assert sum(e["blocks"] for e in tap.of("serving_block_cow")) == 1


def test_refcount_pinned_blocks_survive_tight_eviction(model, params):
    """An eviction pass under a tight block budget must free ONLY
    unpinned, childless entries: pinned chains (a live prefill's) and
    blocks still shared by slots survive, and the pass reports the
    honest freed count."""
    _, paged = _engines(model, params, slots=2, block_size=16)
    mgr = paged.block_pool
    sched = sv.ContinuousBatchingScheduler(
        paged, log_interval=10 ** 9,
        prefix_caching=sv.PrefixCacheConfig())
    pc = sched.prefix_cache
    sched.submit(sv.Request("a", _prompt(seed=7, n=40), max_new_tokens=2))
    sched.run()
    assert len(pc) == 2                          # two whole shared blocks
    entries = list(pc._entries.values())
    # pin one entry (a live prefill would); its block must survive any
    # reclaim pressure while the unpinned sibling frees
    pc.acquire([entries[1]])
    assert pc.evictable_blocks() == 0            # [0] parents [1]: chained
    freed = pc.evict_blocks(2)
    assert freed == 0                            # nothing legally freeable
    assert entries[0].chain in pc and entries[1].chain in pc
    pc.release([entries[1]])
    # now the leaf is evictable but its parent still is not
    assert pc.evictable_blocks() == 1
    freed = pc.evict_blocks(2)
    assert freed == 2                            # leaf, then freed parent
    assert len(pc) == 0 and mgr.used_blocks == 0


def test_pool_exhaustion_reclaims_prefix_then_raises(model, params):
    """The engine's allocator consults the prefix cache exactly once
    under pressure: cached-but-idle blocks are evicted to satisfy the
    allocation; with nothing reclaimable the error is loud — and no
    stream's table was harmed."""
    # pool of 5 usable blocks, slots 2, max_len 48 (3 blocks/slot)
    _, paged = _engines(model, params, max_len=48, block_size=16,
                        slots=2, num_blocks=6, prefill_len=16)
    sched = sv.ContinuousBatchingScheduler(
        paged, log_interval=10 ** 9,
        prefix_caching=sv.PrefixCacheConfig())
    sched.submit(sv.Request("a", _prompt(seed=8, n=33), max_new_tokens=2))
    sched.run()
    assert len(sched.prefix_cache) == 2          # 2 blocks cached
    assert paged.block_pool.used_blocks == 2
    # a fresh 3-block prompt fits only if the cache gives blocks back
    with _EventTap():
        sched.submit(sv.Request("b", _prompt(seed=9, n=33),
                                max_new_tokens=2))
        sched.run()
    assert paged.block_pool.free_blocks >= 1
    # exhaustion with nothing evictable: the reclaim hook drains the
    # prefix cache during these prefills, then the boundary-crossing
    # decode append finds a truly empty pool and raises
    paged.reset()
    paged.prefill(0, _prompt(seed=10, n=48))     # 3 of 5 blocks
    paged.prefill(1, _prompt(seed=11, n=32))     # 5 of 5 (block-aligned)
    assert len(sched.prefix_cache) == 0          # reclaim drained it
    with pytest.raises(BlockPoolExhausted):
        paged.decode(np.array([1, 1], np.int32),
                     np.array([False, True]))    # slot 1 needs block 3
    # the failed step corrupted nothing: slot tables intact, and after
    # releasing slot 0 the same step succeeds
    assert len(paged.block_pool.slot_block_ids(1)) == 2
    paged.release(0)
    paged.decode(np.array([1, 1], np.int32), np.array([False, True]))
    assert paged.lengths()[1] == 33


def test_scheduler_admission_prices_blocks(model, params):
    """Paged admission holds a request back while its WORST-CASE
    footprint (prompt + decode growth) cannot be covered by free +
    evictable blocks (instead of grabbing a free slot and dying at
    allocation), and admits it once live streams drain.  Oversized
    requests are rejected at submit."""
    _, paged = _engines(model, params, max_len=64, block_size=16,
                        slots=4, num_blocks=7, prefill_len=16)
    sched = sv.ContinuousBatchingScheduler(paged, log_interval=10 ** 9)
    with pytest.raises(ValueError):              # > whole pool: reject
        sched.submit(sv.Request("big", _prompt(seed=12, n=64),
                                max_new_tokens=48))
    sched.submit(sv.Request("a", _prompt(seed=13, n=48),
                            max_new_tokens=4))   # 51 rows: 4 blocks
    sched.submit(sv.Request("b", _prompt(seed=14, n=64),
                            max_new_tokens=1))   # 4 > the 2 unreserved:
    #                                              waits for a to drain
    seen_concurrent = 0
    for _ in range(60):
        sched.step()
        seen_concurrent = max(seen_concurrent, sched.active_count)
        if not (sched.queue_depth or sched.active_count):
            break
    res = sched.results
    assert set(res) == {"a", "b"}                # both served...
    assert seen_concurrent == 1                  # ...never concurrently
    # a roomier pool admits both at once (the held-back witness), and
    # the serialized streams equal the concurrent ones bit for bit
    _, roomy = _engines(model, params, max_len=64, block_size=16,
                        slots=4, prefill_len=16)
    sched2 = sv.ContinuousBatchingScheduler(roomy, log_interval=10 ** 9)
    sched2.submit(sv.Request("a", _prompt(seed=13, n=48),
                             max_new_tokens=4))
    sched2.submit(sv.Request("b", _prompt(seed=14, n=64),
                             max_new_tokens=1))
    for _ in range(4):
        sched2.step()
        if sched2.active_count == 2:
            break
    assert sched2.active_count == 2
    res2 = sched2.run()
    assert [res2[r].tokens for r in ("a", "b")] \
        == [res[r].tokens for r in ("a", "b")]


def test_admission_prices_decode_growth_not_just_prompt(model, params):
    """THE mid-decode exhaustion regression: four 2-prompt-block streams
    whose decode growth needs a 3rd block each (12 worst-case blocks)
    on a 9-block pool.  Pricing prompts alone admits all four and the
    pool exhausts when every stream crosses the block boundary
    mid-decode — an uncatchable BlockPoolExhausted that loses every
    in-flight stream.  Pricing the full footprint holds the 4th stream
    back (backpressure, not a crash) and every stream completes,
    bit-identical to the dense run."""
    prompts = [_prompt(seed=200 + i, n=17) for i in range(4)]

    def run(eng):
        sched = sv.ContinuousBatchingScheduler(eng, log_interval=10 ** 9)
        for i, p in enumerate(prompts):
            sched.submit(sv.Request(f"g{i}", p, max_new_tokens=20))
        peak = 0
        for _ in range(400):
            sched.step()
            peak = max(peak, sched.active_count)
            if not (sched.queue_depth or sched.active_count):
                break
        return [sched.results[f"g{i}"].tokens for i in range(4)], peak

    dense = sv.DecodeEngine(model, params, slots=4, max_len=64,
                            prefill_len=16)
    want, _ = run(dense)
    _, paged = _engines(model, params, max_len=64, block_size=16,
                        slots=4, num_blocks=10, prefill_len=16)
    got, peak = run(paged)
    assert got == want, "held-back streams diverged from dense"
    # the 4th stream waited: 3 x 3 reserved blocks saturate the 9-block
    # pool (prompt-only pricing would have admitted all 4 — and died)
    assert peak == 3
    assert paged.block_pool.used_blocks == 0     # clean drain


def test_scheduler_close_releases_cache_blocks_and_reclaim_hook(
        model, params):
    """close() on a caching paged scheduler derefs every cached pool
    block and unhooks the allocator's reclaim callback — abandoning the
    cache instead would pin its blocks forever and leave the engine
    reclaiming into a dead store.  A successor caching scheduler over
    the same engine starts from an empty pool and replays the same
    streams; close() with work in flight refuses."""
    _, paged = _engines(model, params, slots=2, block_size=16)
    prompt = _prompt(seed=21, n=40)

    def fleet():
        sched = sv.ContinuousBatchingScheduler(
            paged, log_interval=10 ** 9,
            prefix_caching=sv.PrefixCacheConfig())
        sched.submit(sv.Request("a", prompt, max_new_tokens=3))
        return sched, sched.run()["a"].tokens

    sched, want = fleet()
    assert len(sched.prefix_cache) == 2          # two whole blocks cached
    assert paged.block_pool.used_blocks == 2     # ...holding pool refs
    assert paged.block_pool.reclaim is not None
    sched.close()
    assert len(sched.prefix_cache) == 0
    assert paged.block_pool.used_blocks == 0     # refs released
    assert paged.block_pool.reclaim is None      # hook unwired
    sched2, got = fleet()                        # successor: clean start
    assert got == want
    sched2.close()
    assert paged.block_pool.used_blocks == 0
    sched3 = sv.ContinuousBatchingScheduler(
        paged, log_interval=10 ** 9,
        prefix_caching=sv.PrefixCacheConfig())
    sched3.submit(sv.Request("q", prompt, max_new_tokens=1))
    with pytest.raises(RuntimeError):
        sched3.close()                           # queued work: refuse
    sched3.run()
    # closing an OLDER scheduler must not unhook a newer one's reclaim
    # callback — only the hook it installed itself
    sched4 = sv.ContinuousBatchingScheduler(
        paged, log_interval=10 ** 9,
        prefix_caching=sv.PrefixCacheConfig())
    sched3.close()
    assert paged.block_pool.reclaim is not None  # sched4's hook survives
    sched4.close()
    assert paged.block_pool.reclaim is None


# ---------------------------------------------------------------------------
# zero-copy witness + events/metrics + default-off identity
# ---------------------------------------------------------------------------


def test_prefix_hit_zero_copy_dispatch_witness(model, params):
    """A paged prefix hit moves NO K/V: the restore program and the
    region-read program never compile (the whole capture/restore
    dispatch family is gone), CoW never compiles while nothing writes
    into shared tails before the suffix diverges past whole blocks,
    and the alias is visible in events + counters."""
    from apex_tpu.obs import bridge as obs_bridge

    shared = _prompt(seed=15, n=64)
    p1 = shared + _prompt(seed=16, n=4)
    p2 = shared + _prompt(seed=17, n=4)
    _, paged = _engines(model, params, slots=1, block_size=16)
    sched = sv.ContinuousBatchingScheduler(
        paged, log_interval=10 ** 9,
        prefix_caching=sv.PrefixCacheConfig())
    alias0 = obs_bridge.SERVING_BLOCK_ALIAS_HITS.value()
    with _EventTap() as tap:
        for i, p in enumerate((p1, p2)):
            sched.submit(sv.Request(f"r{i}", p, max_new_tokens=4))
        sched.run()
    hits = tap.of("serving_prefix_hit")
    assert len(hits) == 1 and hits[0]["saved_tokens"] == 64
    alias = tap.of("serving_block_alias")
    assert len(alias) == 1 and alias[0]["blocks"] == 4
    # THE witness: zero restore compiles, zero region-read compiles —
    # the hit was table aliasing, not a copy through any program
    assert paged.restore_compiles() == 0
    assert compile_count(paged._read) == 0
    assert paged.block_stats()["aliased_total"] == 4
    assert obs_bridge.SERVING_BLOCK_ALIAS_HITS.value() == alias0 + 4
    assert obs_bridge.SERVING_BLOCK_POOL_UTILIZATION.value() \
        == paged.block_pool_utilization()
    # both streams produced tokens (sanity on the hit path)
    assert all(len(r.tokens) == 4 for r in sched.results.values())


def test_paged_prefix_store_by_reference_semantics(model, params):
    """put_block_ids is idempotent per chain position, refuses orphans,
    rejects span-mode calls, and clear() returns every cached block's
    reference to the pool."""
    _, paged = _engines(model, params, slots=1, block_size=16)
    mgr = paged.block_pool
    prompt = _prompt(seed=18, n=40)
    paged.prefill(0, prompt)
    ids = mgr.slot_block_ids(0)
    pc = sv.PrefixCache(block_size=16, max_tokens=1 << 20, pool=mgr,
                        bytes_per_block=128)
    blocks = [prompt[:16], prompt[16:32]]
    a, b = pc.put_block_ids(sv.PrefixCache.ROOT, blocks, ids[:2])
    assert [mgr.refcount(i) for i in ids[:2]] == [2, 2]
    assert pc.cached_bytes == 2 * 128
    again = pc.put_block_ids(sv.PrefixCache.ROOT, blocks, ids[:2])
    assert again == [a, b]                       # idempotent, no re-ref
    assert [mgr.refcount(i) for i in ids[:2]] == [2, 2]
    gone = sv.PrefixCache.chain_hash(sv.PrefixCache.ROOT, (0,) * 16)
    assert pc.put_block_ids(gone, [prompt[:16]], [ids[0]]) == []
    assert pc.stats()["refused"] == 1
    with pytest.raises(ValueError):              # span call on paged store
        pc.put_blocks(sv.PrefixCache.ROOT, [prompt[:16]],
                      jnp.zeros((2, 16, 2, 16)), jnp.zeros((2, 16, 2, 16)))
    with pytest.raises(ValueError):              # and the reverse
        sv.PrefixCache(block_size=16, max_tokens=4).put_block_ids(
            sv.PrefixCache.ROOT, [prompt[:16]], [1])
    with pytest.raises(ValueError):              # no materializing aliases
        sv.PrefixCache.gather_kv([a, b])
    pc.clear()
    assert [mgr.refcount(i) for i in ids[:2]] == [1, 1]


def test_paged_off_identity_and_guards(model, params):
    """A dense engine reports inert paged state, rejects paged-only
    calls loudly, and the paged engine rejects the dense capture
    family — no silent wrong-layout fallbacks."""
    dense, paged = _engines(model, params)
    assert dense.paged is None and dense.block_pool is None
    assert dense.block_size is None and dense.free_blocks() is None
    assert dense.block_pool_utilization() == 0.0
    assert dense.block_stats() == {}
    for call in (lambda: dense.slot_block_ids(0),
                 lambda: dense.alias_prefix(0, [1], 16),
                 lambda: dense.fork_slot(0, 1),
                 lambda: dense.set_block_reclaim(lambda n: 0)):
        with pytest.raises(ValueError):
            call()
    dense.prefill(0, _prompt(seed=19, n=8))
    paged.prefill(0, _prompt(seed=19, n=8))
    with pytest.raises(ValueError):              # capture is by reference
        paged.read_region(0, 0, 8)
    with pytest.raises(ValueError):              # hits alias, never copy
        paged.restore_prefix(1, (jnp.zeros((2, 8, 2, 16)),) * 2, 8)
    with pytest.raises(ValueError):              # mismatched prefix block
        sv.ContinuousBatchingScheduler(
            paged, prefix_caching=sv.PrefixCacheConfig(block_size=8))
    with pytest.raises(ValueError):              # block_size > max_len
        sv.DecodeEngine(model, params, slots=1, max_len=8, prefill_len=4,
                        paged=sv.PagedCacheConfig(block_size=16))
    # aliasing guards
    with pytest.raises(ValueError):              # occupied slot
        paged.alias_prefix(0, [1], 16)
    with pytest.raises(ValueError):              # id count != token need
        paged.alias_prefix(1, [1, 2], 16)
