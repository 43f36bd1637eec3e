"""Cross-request prefix caching (ISSUE 10): reuse shared-prompt K/V
with bit-exact resume.

THE acceptance run: two requests sharing a 70+ token prefix — the
second admits via a cache hit, and its full logit trajectory (prefill
plus >= 20 greedy decode steps) is **bit-identical** to a cold-cache
run of the same prompt, with a neighbor slot mid-chunked-prefill
asserted bit-isolated throughout.  Eviction under a tight budget never
evicts a ref'd (pinned) entry, and a post-eviction miss falls back to
full prefill bit-identically.

Plus: `kv_cache` slot-region primitive edges (start=0, spans abutting
``max_len``, interaction with ``commit_slot_length`` on a full slot —
the rollback primitive PR 8 added), prefix-store unit semantics (chain
hashing, LRU leaf-first eviction, pinning, orphan refusal, span-shared
byte accounting), the hit/miss events + metrics wiring, and the
default-off identity witnesses (no prefix events, zero restore
compiles, unchanged program set).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import _logging
from apex_tpu import serving as sv
from apex_tpu.models import LlamaConfig, LlamaForCausalLM
from apex_tpu.serving.kv_cache import (
    commit_slot_length,
    init_cache,
    read_slot_region,
    write_slot_region,
)
from apex_tpu.serving.prefix_cache import PrefixCache

# the serving suite's GQA config (kv_heads < heads)
CFG = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, max_position_embeddings=256)
LAYERS = LlamaForCausalLM(CFG).cache_layers()
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

    def kinds(self):
        return [e.get("event") for e in self.events]

    def of(self, kind):
        return [e for e in self.events if e.get("event") == kind]


# ---------------------------------------------------------------------------
# kv_cache slot-region primitives: edges
# ---------------------------------------------------------------------------


def _region(seed, n):
    hd = CFG.hidden_size // CFG.num_attention_heads
    rng = np.random.default_rng(seed)
    shape = (CFG.num_hidden_layers, n, CFG.kv_heads, hd)
    return (jnp.asarray(rng.standard_normal(shape), jnp.float32),
            jnp.asarray(rng.standard_normal(shape), jnp.float32))


def test_slot_region_write_read_start_zero_roundtrip():
    cache = init_cache(LAYERS, slots=3, max_len=16)
    k, v = _region(0, 6)
    cache = write_slot_region(cache, slot=1, start=0, k_region=k,
                              v_region=v)
    rk, rv = read_slot_region(cache, 1, 0, 6)
    assert np.array_equal(np.asarray(rk), np.asarray(k))
    assert np.array_equal(np.asarray(rv), np.asarray(v))
    # neighbors and rows past the span untouched
    assert np.asarray(cache.k)[:, 0].sum() == 0
    assert np.asarray(cache.k)[:, 2].sum() == 0
    assert np.asarray(cache.k)[:, 1, 6:].sum() == 0
    # lengths untouched by design: the caller commits
    assert np.asarray(cache.lengths).tolist() == [0, 0, 0]


def test_slot_region_span_abutting_max_len():
    cache = init_cache(LAYERS, slots=2, max_len=16)
    k, v = _region(1, 4)
    cache = write_slot_region(cache, slot=0, start=12, k_region=k,
                              v_region=v)      # rows [12, 16): exact fit
    rk, _ = read_slot_region(cache, 0, 12, 16)
    assert np.array_equal(np.asarray(rk), np.asarray(k))
    # an overhanging span DROPS its out-of-range rows (mode="drop"),
    # never clamps the write backward onto earlier rows
    k2, v2 = _region(2, 4)
    cache2 = write_slot_region(cache, slot=0, start=14, k_region=k2,
                               v_region=v2)    # rows 14, 15 land; 16, 17 drop
    got = np.asarray(cache2.k)[:, 0]
    assert np.array_equal(got[:, 14:16], np.asarray(k2)[:, :2])
    # rows [12, 14) keep the FIRST write (no backward clamp)
    assert np.array_equal(got[:, 12:14], np.asarray(k)[:, :2])


def test_slot_region_with_commit_slot_length_on_full_slot():
    """Fill a slot to max_len, commit, roll back via commit_slot_length
    (the PR-8 rollback primitive), and overwrite the rolled-back span —
    region reads see exactly the committed truth at each stage."""
    cache = init_cache(LAYERS, slots=2, max_len=16)
    k, v = _region(3, 16)
    cache = write_slot_region(cache, slot=0, start=0, k_region=k,
                              v_region=v)
    cache = commit_slot_length(cache, 0, 16)          # full slot
    assert np.asarray(cache.lengths).tolist() == [16, 0]
    rk, _ = read_slot_region(cache, 0, 0, 16)         # whole-slot read
    assert np.array_equal(np.asarray(rk), np.asarray(k))
    # rollback: same O(1) move as speculative-verify rejection
    cache = commit_slot_length(cache, 0, 10)
    assert np.asarray(cache.lengths).tolist() == [10, 0]
    # the bytes past the rollback are still there (unreadable by the
    # masking contract, not erased) — and an overwrite replaces them
    k2, v2 = _region(4, 6)
    cache = write_slot_region(cache, slot=0, start=10, k_region=k2,
                              v_region=v2)
    cache = commit_slot_length(cache, 0, 16)
    rk2, _ = read_slot_region(cache, 0, 10, 16)
    assert np.array_equal(np.asarray(rk2), np.asarray(k2))
    rk3, _ = read_slot_region(cache, 0, 0, 10)        # prefix untouched
    assert np.array_equal(np.asarray(rk3), np.asarray(k)[:, :10])


def test_slot_region_validation():
    cache = init_cache(LAYERS, slots=1, max_len=8)
    with pytest.raises(ValueError):           # empty region
        read_slot_region(cache, 0, 4, 4)
    with pytest.raises(ValueError):
        read_slot_region(cache, 0, 5, 3)


# ---------------------------------------------------------------------------
# prefix store unit semantics (host-side, no model)
# ---------------------------------------------------------------------------


def test_prefix_cache_chain_hash_encodes_position():
    blk = tuple(range(16))
    h1 = PrefixCache.chain_hash(PrefixCache.ROOT, blk)
    h2 = PrefixCache.chain_hash(h1, blk)
    assert h1 != h2                  # same tokens, different position
    assert h1 == PrefixCache.chain_hash(PrefixCache.ROOT, list(blk))


def test_prefix_cache_match_caps_at_prompt_minus_one():
    pc = PrefixCache(block_size=4, max_tokens=1 << 20)
    prompt = list(range(12))
    h = PrefixCache.ROOT
    for i in range(3):
        k, v = _region(i, 4)
        e = pc.put(h, prompt[4 * i:4 * i + 4], k, v)
        h = e.chain
    # 12 cached tokens exist, but a 12-token prompt may only reuse 8:
    # the final token must be recomputed for the next-token logits
    covered, entries = pc.match(prompt)
    assert covered == 8 and len(entries) == 2
    covered, entries = pc.match(prompt + [99])   # 13 tokens: all 3 match
    assert covered == 12 and len(entries) == 3
    covered, entries = pc.match(prompt[:4])      # too short for a block
    assert covered == 0 and entries == []
    covered, _ = pc.match([7] * 12)              # different content
    assert covered == 0


def test_prefix_cache_lru_leaf_first_eviction_and_pinning():
    pc = PrefixCache(block_size=4, max_tokens=8)       # room for 2 blocks
    a = pc.put(PrefixCache.ROOT, [1, 2, 3, 4], *_region(0, 4))
    b = pc.put(a.chain, [5, 6, 7, 8], *_region(1, 4))
    assert pc.cached_tokens == 8
    # pin a only: inserting c must evict b (the oldest unpinned LEAF),
    # never a — a is pinned AND mid-chain while b lives
    pc.acquire([a])
    c = pc.put(PrefixCache.ROOT, [9, 9, 9, 9], *_region(2, 4))
    assert c is not None
    assert pc.cached_tokens == 8
    assert b.chain not in pc
    assert a.chain in pc and c.chain in pc
    # with everything else pinned, a fresh insert is itself the only
    # evictable entry: the budget holds, the pinned chain is untouched
    d = pc.put(c.chain, [8, 8, 8, 8], *_region(3, 4))
    assert pc.cached_tokens <= 12
    # release a: the next insert evicts LRU-first among unpinned leaves
    pc.release([a])
    e = pc.put(PrefixCache.ROOT, [3, 3, 3, 3], *_region(4, 4))
    assert e is not None and e.chain in pc
    assert a.chain not in pc          # unpinned now, oldest -> evicted
    assert pc.cached_tokens <= 8
    stats = pc.stats()
    assert stats["evicted"] >= 2 and stats["inserted"] == 5
    del d


def test_put_blocks_own_entries_survive_their_own_eviction_pass():
    """With every other entry pinned and the budget exhausted, an
    insert must NOT evict the blocks it just created before the caller
    can pin them: put_blocks' returned entries are guaranteed live
    (the pre-pin eviction window would hand back dead entries, kill
    the chain a live prefill is extending, and break the capture
    path's bounded-compile contract downstream)."""
    pc = PrefixCache(block_size=4, max_tokens=8)
    a = pc.put(PrefixCache.ROOT, [1, 2, 3, 4], *_region(0, 4))
    b = pc.put(a.chain, [5, 6, 7, 8], *_region(1, 4))
    pc.acquire([a, b])               # everything pinned, budget full
    k, v = _region(2, 8)
    c, d = pc.put_blocks(PrefixCache.ROOT, [[9, 9, 9, 9], [8, 8, 8, 8]],
                         k, v)
    assert c.chain in pc and d.chain in pc, (
        "fresh entries evicted by their own insert's budget pass")
    assert pc.cached_tokens == 16    # transiently over budget instead
    # once the caller pins them, a later unpinned insert is the one
    # that gets evicted (or itself refused room) — never the pinned
    pc.acquire([c, d])
    e = pc.put(PrefixCache.ROOT, [3, 3, 3, 3], *_region(3, 4))
    assert a.chain in pc and b.chain in pc
    assert c.chain in pc and d.chain in pc
    pc.release([a, b, c, d])
    del e


def test_prefill_resume_rejection_is_side_effect_free(model, params):
    """A rejected prefill(resume=...) must not consume the restore
    mark: the caller can retry with a corrected prompt instead of
    re-paying the whole device restore."""
    eng = sv.DecodeEngine(model, params, slots=1, max_len=32,
                          prefill_len=8)
    eng.prefill(0, _prompt(n=12))
    k, v = eng.read_region(0, 0, 8)
    eng.release(0)
    eng.restore_prefix(0, (k, v), 8)
    with pytest.raises(ValueError):       # prompt beyond cache capacity
        eng.prefill(0, _prompt(n=40), resume=8)
    # the restored state is intact — the corrected retry succeeds
    logits = eng.prefill(0, _prompt(n=12), resume=8)
    assert logits is not None and eng.lengths()[0] == 12


def test_prefix_cache_orphan_insert_refused_and_idempotence():
    pc = PrefixCache(block_size=4, max_tokens=1 << 20)
    gone = PrefixCache.chain_hash(PrefixCache.ROOT, (0, 0, 0, 0))
    assert pc.put(gone, [1, 1, 1, 1], *_region(0, 4)) is None
    assert pc.stats()["refused"] == 1
    a = pc.put(PrefixCache.ROOT, [1, 2, 3, 4], *_region(1, 4))
    again = pc.put(PrefixCache.ROOT, [1, 2, 3, 4], *_region(2, 4))
    assert again is a                 # idempotent: first capture wins
    assert pc.stats()["inserted"] == 1
    with pytest.raises(ValueError):   # release must pair with acquire
        pc.release([a])
    pc.acquire([a])
    with pytest.raises(ValueError):   # live pins block clear()
        pc.clear()
    pc.release([a])
    pc.clear()
    assert len(pc) == 0 and pc.cached_bytes == 0


def test_prefix_cache_span_sharing_and_byte_accounting():
    pc = PrefixCache(block_size=4, max_tokens=8)
    k, v = _region(0, 8)
    nbytes = k.nbytes + v.nbytes
    a, b = pc.put_blocks(PrefixCache.ROOT, [[1, 2, 3, 4], [5, 6, 7, 8]],
                         k, v)
    assert a.span is b.span and pc.cached_bytes == nbytes
    # gather of the whole span is the span arrays themselves (no slice)
    gk, gv = PrefixCache.gather_kv([a, b])
    assert gk is k and gv is v
    # a partial chain slices once
    gk2, _ = PrefixCache.gather_kv([a])
    assert np.array_equal(np.asarray(gk2), np.asarray(k)[:, :4])
    # evicting ONE block of the span frees no bytes (the span survives
    # for its sibling); evicting the last frees them all
    pc.put(PrefixCache.ROOT, [7, 7, 7, 7], *_region(1, 4))  # forces evict
    assert pc.cached_tokens == 8
    assert b.chain not in pc and a.chain in pc
    assert pc.cached_bytes == nbytes + _region(1, 4)[0].nbytes * 2
    pc.put(PrefixCache.ROOT, [6, 6, 6, 6], *_region(2, 4))
    assert a.chain not in pc
    assert pc.cached_bytes == _region(1, 4)[0].nbytes * 4


def test_prefix_cache_config_validation():
    with pytest.raises(ValueError):
        sv.PrefixCacheConfig(block_size=0)
    with pytest.raises(ValueError):
        sv.PrefixCacheConfig(max_tokens=0)
    with pytest.raises(ValueError):
        PrefixCache(block_size=4, max_tokens=8).put(
            PrefixCache.ROOT, [1, 2, 3], *_region(0, 3))  # partial block


# ---------------------------------------------------------------------------
# THE acceptance run: hit trajectory bit-identical, neighbor isolated
# ---------------------------------------------------------------------------


def test_prefix_hit_full_trajectory_bit_identical_with_neighbor(model,
                                                                params):
    """A 74-token prompt decodes cold; a second engine restores the
    70-token cached prefix (captured from the first), resumes prefill
    mid-prompt, and decodes 20 greedy steps — every f32 logit vector,
    prefill included, is bit-identical to the cold run, while a
    neighbor slot runs chunked prefill in the warm engine the whole
    time (bit-isolation both ways)."""
    prompt = _prompt(seed=11, n=74)
    neighbor_prompt = _prompt(seed=12, n=64)

    # cold reference: full prefill + 20 greedy steps, solo
    eng_cold = sv.DecodeEngine(model, params, slots=2, max_len=MAX,
                               prefill_len=16)
    logits = eng_cold.prefill(0, prompt)
    cold = [np.asarray(logits)]
    toks_cold = list(prompt)
    for _ in range(20):
        nxt = int(jnp.argmax(logits))
        toks_cold.append(nxt)
        logits = eng_cold.decode(np.array([nxt, 0], np.int32),
                                 np.array([True, False]))[0]
        cold.append(np.asarray(logits))

    # capture the first 70 tokens from the cold slot via the prefix
    # store (block 10 keeps 70 = 7 whole blocks)
    pc = PrefixCache(block_size=10, max_tokens=1 << 20)
    k, v = eng_cold.read_region(0, 0, 70)
    blocks = [prompt[i * 10:(i + 1) * 10] for i in range(7)]
    entries = pc.put_blocks(PrefixCache.ROOT, blocks, k, v)
    assert len(entries) == 7
    covered, chain = pc.match(prompt)
    assert covered == 70 and len(chain) == 7

    # warm engine: restore + resume, with the neighbor mid-prefill
    eng_warm = sv.DecodeEngine(model, params, slots=2, max_len=MAX,
                               prefill_len=16)
    eng_warm.prefill_chunk(1, neighbor_prompt[:16])    # neighbor starts
    eng_warm.restore_prefix(0, PrefixCache.gather_kv(chain), covered)
    assert eng_warm.lengths()[0] == 70
    logits = eng_warm.prefill(0, prompt, resume=70)
    assert np.array_equal(np.asarray(logits), cold[0]), (
        "resumed prefill diverged from the cold prefill")
    toks = list(prompt)
    for t in range(20):
        if t < 3:                                       # neighbor chunks
            eng_warm.prefill_chunk(
                1, neighbor_prompt[16 * (t + 1):16 * (t + 2)])
        nxt = int(jnp.argmax(logits))
        toks.append(nxt)
        logits = eng_warm.decode(np.array([nxt, 0], np.int32),
                                 np.array([True, False]))[0]
        assert np.array_equal(np.asarray(logits), cold[t + 1]), (
            f"warm decode diverged from cold at step {t}")
    assert toks == toks_cold
    # ... and the neighbor the warm engine prefilled next door equals
    # an isolated prefill of the same prompt, bit for bit
    eng_solo = sv.DecodeEngine(model, params, slots=1, max_len=MAX,
                               prefill_len=16)
    want = eng_solo.prefill(0, neighbor_prompt)
    got = eng_warm.prefill_chunk(1, neighbor_prompt[64:]) \
        if len(neighbor_prompt) > 64 else None
    # neighbor_prompt is exactly 64 tokens = 4 chunks, already complete
    assert got is None
    nk, _ = eng_warm.read_region(1, 0, 64)
    sk, _ = eng_solo.read_region(0, 0, 64)
    assert np.array_equal(np.asarray(nk), np.asarray(sk))
    del want
    # compile-count guards: restore bounded by the bucket table, the
    # decode step untouched
    assert eng_warm.restore_compiles() <= len(eng_warm.prefill_buckets)
    assert eng_warm.decode_compiles() == 1
    assert eng_cold.restore_compiles() == 0


def test_scheduler_hit_streams_and_telemetry(model, params):
    """Scheduler route of the acceptance claim: the second request
    admits via a cache hit (event + counters + saved-tokens histogram
    + cached-tokens gauge), prefill spends budget only on the suffix,
    and the hit stream equals a cold-scheduler run token for token."""
    from apex_tpu.obs import bridge as obs_bridge

    shared = _prompt(seed=21, n=72)
    p1 = shared + _prompt(seed=22, n=4)
    p2 = shared + _prompt(seed=23, n=4)

    def run(prefix_caching, rid_tag):
        eng = sv.DecodeEngine(model, params, slots=1, max_len=MAX,
                              prefill_len=16)
        sched = sv.ContinuousBatchingScheduler(
            eng, log_interval=10 ** 9, prefix_caching=prefix_caching)
        for i, p in enumerate((p1, p2)):
            sched.submit(sv.Request(f"{rid_tag}{i}", p,
                                    max_new_tokens=8))
        return sched, sched.run()

    hits0 = obs_bridge.SERVING_PREFIX_HITS.value()
    misses0 = obs_bridge.SERVING_PREFIX_MISSES.value()
    saved0 = obs_bridge.SERVING_PREFIX_SAVED.count()
    with _EventTap() as tap:
        sched_on, on = run(sv.PrefixCacheConfig(), "on")
    _, off = run(None, "off")
    assert [r.tokens for r in on.values()] \
        == [r.tokens for r in off.values()]
    # r0 missed (cold), r1 hit the 64 tokens of whole shared blocks
    assert len(tap.of("serving_prefix_miss")) == 1
    hits = tap.of("serving_prefix_hit")
    assert len(hits) == 1
    assert hits[0]["rid"] == "on1"
    assert hits[0]["saved_tokens"] == 64      # 4 x 16-token blocks <= 71
    # the suffix is the only prefill the hit paid: its chunk events
    # start at offset 64
    chunk_offsets = [e["offset_tokens"] for e in
                     tap.of("serving_prefill_chunk")
                     if e["rid"] == "on1"]
    assert chunk_offsets and min(chunk_offsets) == 64
    # metrics wiring (global registry: compare deltas)
    assert obs_bridge.SERVING_PREFIX_HITS.value() == hits0 + 1
    assert obs_bridge.SERVING_PREFIX_MISSES.value() == misses0 + 1
    assert obs_bridge.SERVING_PREFIX_SAVED.count() == saved0 + 1
    assert obs_bridge.SERVING_PREFIX_CACHED_TOKENS.value() \
        == sched_on.prefix_cache.cached_tokens
    assert sched_on.prefix_cache.stats()["hits"] == 1


def test_eviction_never_touches_pinned_and_miss_falls_back(model, params):
    """Under a tight budget, a request mid-chunked-prefill keeps its
    chain pinned across steps while another stream's capture forces
    eviction — the pinned entries survive, the OTHER chain is evicted,
    and a later admission of the evicted prompt misses and re-prefills
    to the exact cold-run stream."""
    pa = _prompt(seed=31, n=48)     # 3 x 16-token blocks
    pb = _prompt(seed=32, n=48)

    eng = sv.DecodeEngine(model, params, slots=2, max_len=MAX,
                          prefill_len=16)
    sched = sv.ContinuousBatchingScheduler(
        eng, log_interval=10 ** 9, prefill_budget=16,
        prefix_caching=sv.PrefixCacheConfig(max_tokens=48))
    pc = sched.prefix_cache

    # A's prompt populates the cache (3 blocks = the whole budget)
    sched.submit(sv.Request("a", pa, max_new_tokens=2))
    res_a = sched.run()["a"]
    assert pc.cached_tokens == 48
    cov_a, _ = pc.match(pa + [0])
    assert cov_a == 48

    # B admits and prefills one 16-token chunk per step (budget 16);
    # its captures push the store over budget every step WHILE B's own
    # chain is pinned — eviction must consume A's released chain only
    sched.submit(sv.Request("b", pb, max_new_tokens=2))
    sched.step()
    pinned = [e for e in pc._entries.values() if e.refs]
    assert len(pinned) == 1          # B's first block, mid-prefill pin
    assert pc.cached_tokens > 0
    sched.run()
    cov_b, _ = pc.match(pb + [0])
    assert cov_b == 48               # B's chain intact (was pinned)
    cov_a2, _ = pc.match(pa + [0])
    assert cov_a2 < 48               # A's chain (partially) evicted
    assert pc.stats()["evicted"] >= 1
    assert not [e for e in pc._entries.values() if e.refs]  # all released

    # post-eviction: A's prompt misses (or partially hits) and the
    # stream still equals the original cold stream bit-for-bit at the
    # token level
    with _EventTap() as tap:
        sched.submit(sv.Request("a2", pa, max_new_tokens=2))
        res_a2 = sched.run()["a2"]
    assert res_a2.tokens == res_a.tokens
    assert (len(tap.of("serving_prefix_miss"))
            + len(tap.of("serving_prefix_hit"))) == 1


# ---------------------------------------------------------------------------
# default-off identity + guards
# ---------------------------------------------------------------------------


def test_prefix_caching_off_leaves_serving_path_untouched(model, params):
    """The default (no ``prefix_caching``) must not change a byte:
    no prefix events, no restore/read compiles, the same program set —
    and the scheduler signature stays backward compatible."""
    eng = sv.DecodeEngine(model, params, slots=2, max_len=MAX,
                          prefill_len=16)
    sched = sv.ContinuousBatchingScheduler(eng, log_interval=10 ** 9)
    assert sched.prefix_cache is None
    with _EventTap() as tap:
        sched.submit(sv.Request("r", _prompt(seed=41, n=40),
                                max_new_tokens=4))
        sched.run()
    kinds = set(tap.kinds())
    assert not any("prefix" in str(k) for k in kinds)
    assert kinds <= {"serving_request_queued", "serving_request_admitted",
                     "serving_prefill_chunk", "serving_first_token",
                     "serving_request_finished", "serving_step"}
    assert eng.restore_compiles() == 0
    assert eng.prefill_compiles() <= len(eng.prefill_buckets)
    assert eng.decode_compiles() == 1


def test_restore_and_resume_guards(model, params):
    eng = sv.DecodeEngine(model, params, slots=2, max_len=32,
                          prefill_len=8)
    eng.prefill(0, _prompt(n=12))
    k, v = eng.read_region(0, 0, 8)
    with pytest.raises(ValueError):           # read past valid length
        eng.read_region(0, 8, 16)
    with pytest.raises(ValueError):           # restore into occupied slot
        eng.restore_prefix(0, (k, v), 8)
    with pytest.raises(ValueError):           # resume without restore
        eng.prefill(1, _prompt(n=12), resume=8)
    with pytest.raises(ValueError):           # shape mismatch
        eng.restore_prefix(1, (k[:1], v[:1]), 8)
    with pytest.raises(ValueError):           # more rows than provided
        eng.restore_prefix(1, (k, v), 9)
    with pytest.raises(ValueError):           # full-cache restore
        big = jnp.zeros((CFG.num_hidden_layers, 32, CFG.kv_heads,
                         CFG.hidden_size // CFG.num_attention_heads))
        eng.restore_prefix(1, (big, big), 32)
    eng.restore_prefix(1, (k, v), 8)
    with pytest.raises(ValueError):           # resume offset mismatch
        eng.prefill(1, _prompt(n=12), resume=4)
    with pytest.raises(ValueError):           # no suffix to compute
        eng.prefill(1, _prompt(n=8), resume=8)
    # release clears the restored mark
    eng.release(1)
    eng.prefill_chunk(1, [1, 2])              # plain continue still fine
    with pytest.raises(ValueError):
        eng.prefill(1, _prompt(n=12), resume=8)
    # scheduler-level: a block that cannot fit beside the resume token
    with pytest.raises(ValueError):
        sv.ContinuousBatchingScheduler(
            eng, prefix_caching=sv.PrefixCacheConfig(block_size=32))
