"""Continuous batching: a host loop that keeps decode slots full.

The device-side contract (PAPERS.md: "Exploring the limits of
Concurrency in ML Training on Google TPUs" — keep the host off the
device critical path) is that the *only* per-step device work is the one
compiled batched decode step; everything here — admission, eviction,
sampling bookkeeping, telemetry — is cheap host logic at step
boundaries:

- **Bounded queue**: ``submit`` rejects past ``max_queue`` with
  :class:`QueueFull` (backpressure belongs to the caller, not a silent
  unbounded buffer).
- **Slot admission**: at each step boundary, free slots are filled from
  the queue in FIFO order (no starvation: a request's wait is bounded by
  the streams ahead of it).
- **Prefill/decode interleaving**: prompt caching is *chunked* and
  metered by a per-step ``prefill_budget`` (in tokens) — each step
  spends at most the budget on prefill chunks (oldest admitted request
  first), then runs the shared batched decode step for every decoding
  slot.  A long prompt therefore never stalls live streams for its
  whole length: it advances one chunk at a time while decode keeps
  producing tokens, and the deferred remainder is visible as the
  ``apex_serving_prefill_backlog`` gauge.  Prompts longer than the
  engine's ``prefill_len`` (up to cache capacity) are admitted — the
  chunked cached prefill path serves them.
- **Per-request state machine**: QUEUED → PREFILL → DECODE → DONE, with
  eviction on EOS or ``max_new_tokens`` and *immediate* slot reuse at
  the same step boundary.
- **Decode-ahead** (the step's contract: enqueue all, read once): a
  step enqueues admission's restores, its prefill chunks AND its decode,
  and only then waits for the device, once, for what was enqueued
  *before* that decode — this step's first tokens and the previous
  step's decoded tokens — so the device runs the decode while the host
  appends, finishes, publishes and enqueues the next step.  Sampled
  tokens stay on the device (the engine keeps each slot's last one and
  the decode program reads it there; a lane whose newest token only the
  host has — resumed, adopted, settled — is fed from the host through
  the same program), a request's PRNG key words are made on the host,
  and a lane is issued no further than its ``max_new_tokens``.  What a
  caller sees: a decoded token is delivered one step after the step
  that computed it, a request is reported finished by the step that
  reads its last token, ``ttft_s`` is stamped in the step that sampled
  the first token, and no token of any stream changes.  A stream that
  ends on EOS has one more lane in flight; its token is dropped when
  read.  **Settle points** read everything in flight first, because
  they need a stream's newest token or its slot's rows on the host:
  speculation (drafting reads the history: such a scheduler settles
  before drafting and after its decode, every step), preemption (the
  victim is captured whole), ``cancel`` of an active request (the
  partial output is all that was computed), ``export_streams`` (a stream
  moves with its tokens), ``swap_weights`` (the displaced buffer goes
  back to the caller idle), ``close`` and the end of ``run()`` (what is
  left belongs to ended streams).  :meth:`ContinuousBatchingScheduler.
  overlap_stats` counts steps, steps ahead, settles by reason and
  dropped tokens.
- **Exact-greedy speculation** (opt-in via
  ``speculation=SpeculationConfig(...)``): greedy requests draft up to
  k tokens per step by prompt lookup (:mod:`apex_tpu.serving.draft`)
  and verify them in one multi-token dispatch
  (:meth:`~apex_tpu.serving.engine.DecodeEngine.verify_draft`),
  emitting the accepted prefix plus a bonus token — the stream is
  bit-identical to plain decode by construction.  The draft length
  adapts per request (double on full accept, halve on rejection);
  no-match streams and sampled-temperature requests ride the plain
  batched decode step, the latter byte-for-byte (no drafting, no
  verify compiles, no extra events or metrics).
- **Cross-request prefix caching** (opt-in via
  ``prefix_caching=PrefixCacheConfig(...)``): at admission the prompt
  is matched against a chain-hashed block store
  (:mod:`apex_tpu.serving.prefix_cache`) and the longest cached prefix
  is *restored* into the fresh slot
  (:meth:`~apex_tpu.serving.engine.DecodeEngine.restore_prefix`) —
  the prefill budget is then spent only on the uncovered suffix.
  Completed prompt blocks are offered back insert-on-miss (snapshotted
  from the slot immediately after the chunk that completed them), and
  every entry feeding a live prefill is ref-count-pinned against
  eviction.  Because restored K/V are bit-identical to what prefill
  would have written, a hit changes *nothing* about the stream: same
  logits, same tokens, bit for bit.  Off (the default), every
  existing path — tokens, events, metrics, compiles — is
  byte-for-byte untouched.
- **Telemetry**: structured ``emit_event`` lines
  (:mod:`apex_tpu._logging`) — ``serving_request_admitted`` /
  ``serving_prefix_hit`` / ``serving_prefix_miss`` (admission-time
  cache outcome; hits carry ``saved_tokens`` + restore wall time,
  feeding the ``apex_serving_prefix_{hit,miss}_total`` counters and
  the ``apex_serving_prefix_saved_tokens`` histogram) /
  ``serving_prefill_chunk`` (per-chunk bucket + dispatch wall time,
  feeding the ``apex_serving_prefill_duration_seconds{bucket}``
  histogram) / ``serving_spec_verify`` (per-verify drafted/accepted
  counts + dispatch wall time, feeding the speculation counters and
  the ``apex_serving_spec_accepted_tokens`` histogram) /
  ``serving_first_token`` (time-to-first-token) /
  ``serving_request_finished`` (tokens/s, mean per-token latency) per
  request, and a ``serving_step`` sample (queue depth, active slots,
  slot occupancy, KV-cache utilization, prefill backlog) every
  ``log_interval`` steps.  Current-state gauges
  (:mod:`apex_tpu.obs.bridge`: ``apex_serving_queue_depth`` /
  ``apex_serving_slot_occupancy`` / ``apex_serving_cache_utilization``
  / ``apex_serving_prefill_backlog``, plus
  ``apex_serving_prefix_cached_tokens`` when prefix caching is on)
  refresh every step, so a Prometheus scrape sees live state
  regardless of ``log_interval``.

- **Control plane** (opt-in via ``policy=SchedulingPolicy(...)`` —
  :mod:`apex_tpu.serving.policy`): priority classes with **lossless
  preemption** (a queued request may evict a strictly lower-priority
  DECODE stream; the victim's cache state is captured — dense: a
  bucketed :meth:`~apex_tpu.serving.engine.DecodeEngine.capture_slot`
  snapshot; paged: block references, zero-copy — and later resumed
  *bit-exactly*: same tokens, same f32 logits, because the restored
  bytes ARE the cache bytes), arrival-relative **deadline shedding**
  at every step boundary (admission-time and mid-queue), per-tenant
  **weighted round-robin** admission with in-flight caps, and
  :meth:`ContinuousBatchingScheduler.cancel` (available with or
  without a policy) releasing slot/blocks/pins without disturbing
  neighbors.  ``RequestResult.finish_reason`` distinguishes
  ``eos`` / ``length`` / ``cancelled`` / ``shed`` /
  ``preempted-resumed`` (finished normally after >= 1 lossless
  preemption; :data:`SERVED_REASONS` names the reasons that delivered
  full service).  Without a policy the scheduler is byte-for-byte the
  FIFO scheduler — identical event stream, identical metric snapshot
  (pinned by ``tests/test_serving_policy.py``).

Determinism: sampling draws from explicit per-request PRNG keys
(``fold_in(PRNGKey(seed), token_index)``) — the clock feeds telemetry
only, never token choice, so a replay with the same seeds reproduces
every stream bit-for-bit regardless of arrival timing.  Preemption
preserves this: the sampler's key index is the token count, which
suspend/resume never rewinds.
"""

from __future__ import annotations

import dataclasses
import enum
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from apex_tpu._logging import emit_event, get_logger
from apex_tpu.obs import bridge as obs_bridge
from apex_tpu.obs import trace as obs_trace
from apex_tpu.serving.draft import SpeculationConfig, adapt_k, propose
from apex_tpu.serving.engine import DecodeEngine, request_key_bits
from apex_tpu.serving.paged_kv_cache import blocks_per_slot
from apex_tpu.serving.paged_kv_cache import (
    bytes_per_block as pkv_bytes_per_block,
)
from apex_tpu.serving.policy import SchedulingPolicy, WeightedRoundRobin
from apex_tpu.serving.prefix_cache import PrefixCache, PrefixCacheConfig

__all__ = ["Request", "RequestPhase", "RequestResult", "QueueFull",
           "SchedulerStalled", "SERVED_REASONS", "StreamExport",
           "ContinuousBatchingScheduler"]

logger = get_logger("serving.scheduler")

#: finish reasons that delivered the request's full token stream —
#: goodput accounting counts ONLY these as completions (a cancelled or
#: shed request "finished" in the bookkeeping sense but served nothing
#: it promised)
SERVED_REASONS = frozenset({"eos", "length", "preempted-resumed"})


class QueueFull(RuntimeError):
    """The bounded request queue is at capacity — apply backpressure."""


class SchedulerStalled(RuntimeError):
    """``run()`` exceeded its progress bound with work still pending —
    an engine or driver bug (a stream that never finishes, a hook that
    re-queues forever), surfaced with the scheduler's state instead of
    spinning silently."""


class RequestPhase(enum.Enum):
    QUEUED = "queued"
    PREFILL = "prefill"
    DECODE = "decode"
    DONE = "done"


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request (sampling config rides along).

    ``temperature <= 0`` is greedy; ``top_k <= 0`` means no truncation.
    ``eos_id=None`` disables EOS eviction (run to ``max_new_tokens``).

    The control-plane fields are inert without a
    ``policy=``: ``priority`` (higher admits first and may preempt
    strictly lower), ``deadline_s`` (completion deadline relative to
    submission; expired queued requests are shed), and ``tenant``
    (fairness bucket for weighted round-robin admission and in-flight
    caps).  A FIFO scheduler ignores all three, byte-for-byte.
    """

    rid: str
    prompt: Sequence[int]
    max_new_tokens: int
    eos_id: Optional[int] = None
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0
    priority: int = 0
    deadline_s: Optional[float] = None
    tenant: str = "default"


@dataclasses.dataclass
class RequestResult:
    """Completed stream + the latency telemetry the events carried."""

    rid: str
    tokens: List[int]
    # "eos" | "length" | "cancelled" | "shed" | "preempted-resumed"
    # (the last: finished normally after >= 1 lossless preemption —
    # full service was delivered; see SERVED_REASONS)
    finish_reason: str
    ttft_s: float                      # submit -> first token (NaN if none)
    total_s: float                     # submit -> finished
    tokens_per_s: float
    preemptions: int = 0               # lossless preempt/resume cycles


@dataclasses.dataclass
class _Active:
    request: Request
    slot: int
    seq: int                 # admission order (FIFO prefill priority)
    base_key: np.ndarray     # host copy; folded per token INSIDE the sampler
    tokens: List[int]
    t_submit: float
    t_first: float
    prompt_pos: int = 0      # prompt tokens cached so far
    phase: RequestPhase = RequestPhase.PREFILL
    draft_k: int = 0         # adaptive draft length (speculation only)
    # prefix-caching state (unused when prefix_caching is off):
    # the chain hash of the last prompt block this request matched or
    # captured, how many blocks that is, and the entries pinned on its
    # behalf until the prompt is fully cached
    chain: str = PrefixCache.ROOT
    blocks_cached: int = 0
    pinned: List = dataclasses.field(default_factory=list)
    preemptions: int = 0     # lossless suspend/resume cycles survived
    wv: int = 0              # engine weights_version at admission
    # tokens whose computation has been enqueued: the sampler's index for
    # the next one.  ``issued - len(tokens)`` of them are still on the
    # device (0 or 1 when a decode step is enqueued: 0 = the host holds the
    # stream's newest token and feeds it, 1 = the engine's kept vector does)
    issued: int = 0

    @property
    def prompt_remaining(self) -> int:
        return len(self.request.prompt) - self.prompt_pos


@dataclasses.dataclass
class _InFlight:
    """Sampled tokens the device holds and the host has not read: one
    prompt's first token (``sampled`` is ``[1]``) or one decode step's
    vector (``[slots]``), with the streams they belong to as
    ``(index into sampled, stream)``.  The stream, not its slot: a lane
    whose stream ended meanwhile (an EOS the host saw a step late) is
    dropped when read, whoever holds the slot by then."""

    sampled: object
    lanes: List[tuple]
    first: bool


@dataclasses.dataclass
class StreamExport:
    """One live stream in portable form — the unit of fleet failover
    (:meth:`ContinuousBatchingScheduler.export_streams` produces them,
    :meth:`ContinuousBatchingScheduler.adopt_stream` consumes them on a
    *different* scheduler).

    Two fidelities:

    - ``kv`` present (dense engines, streams that reached DECODE):
      the captured cache bytes travel with the stream, so adoption
      restores mid-stream **bit-exactly** — same tokens kept, decode
      continues as if nothing happened (the PR 13 capture/restore
      contract, applied cross-engine per PR 14).
    - ``kv`` absent (hard-killed engine, mid-PREFILL streams, queued
      requests, or any stream on a *paged* engine — paged capture is
      by block reference into a per-engine pool and cannot cross
      engines): adoption re-queues the bare request.  Replay is
      deterministic (sampler keys fold from ``seed`` by token index),
      so the *final* token stream is still bit-identical to an
      uninterrupted run — the tokens are re-earned, not lost.
    """

    request: Request
    t_submit: float                   # original submit stamp, preserved
    tokens: List[int] = dataclasses.field(default_factory=list)
    t_first: float = 0.0
    preemptions: int = 0
    length: int = 0                   # cached rows at capture
    kv: Optional[tuple] = None        # dense (k, v) host arrays
    # checkpoint step the donor was serving at export (None = unknown).
    # Captured bytes are only bit-faithful on a SAME-version adopter:
    # the router degrades a cross-version capture to a bare requeue so
    # no stream ever decodes a hybrid of two weight versions.
    weights_step: Optional[int] = None


@dataclasses.dataclass
class _Suspended:
    """A preempted DECODE stream awaiting resume: the frozen host
    stream state plus the captured cache — a dense host K/V snapshot,
    or held paged block references (the blocks themselves never moved;
    the hold keeps them alive across the slot release)."""

    st: _Active
    length: int                               # cached rows at capture
    kv: Optional[tuple] = None                # dense: (k, v) host arrays
    block_ids: Optional[List[int]] = None     # paged: referenced blocks
    t_suspended: float = 0.0


class ContinuousBatchingScheduler:
    """FIFO continuous batching over one :class:`DecodeEngine`.

    >>> sched = ContinuousBatchingScheduler(engine, max_queue=64)
    >>> sched.submit(Request("r0", prompt, max_new_tokens=32, eos_id=2))
    >>> results = sched.run()          # drain queue + all active slots

    ``prefill_budget`` is the prompt-token cap per :meth:`step` (default
    ``engine.prefill_len`` — one full-size chunk): the knob that trades
    time-to-first-token for new admissions against decode latency for
    live streams.  Set it large to drain prompts greedily (admission
    stalls decode, the pre-budget behavior), small to bound the decode
    hiccup any single step can suffer.

    ``policy=SchedulingPolicy(...)`` turns on the control plane —
    priority admission with lossless preemption, deadline shedding,
    weighted-round-robin tenant fairness (see
    :mod:`apex_tpu.serving.policy`).  ``None`` (the default) is the
    byte-for-byte FIFO scheduler: identical event stream, identical
    metric snapshot, identical compiled-program set.
    """

    def __init__(self, engine: DecodeEngine, *, max_queue: int = 64,
                 log_interval: int = 32,
                 prefill_budget: Optional[int] = None,
                 speculation: Optional[SpeculationConfig] = None,
                 prefix_caching: Optional[PrefixCacheConfig] = None,
                 policy: Optional[SchedulingPolicy] = None,
                 clock: Callable[[], float] = time.monotonic,
                 name: Optional[str] = None):
        if name is not None and (not isinstance(name, str) or not name):
            raise ValueError(
                f"scheduler name must be a non-empty string (it becomes "
                f"the bounded 'replica' metric label), got {name!r}")
        if prefill_budget is None:
            prefill_budget = engine.prefill_len
        if prefill_budget < 1:
            raise ValueError(f"prefill_budget must be >= 1 token per "
                             f"step, got {prefill_budget}")
        if (speculation is not None
                and speculation.max_draft > engine.max_draft):
            raise ValueError(
                f"speculation.max_draft {speculation.max_draft} exceeds "
                f"the engine's draft bucket table (max "
                f"{engine.max_draft}) — widen draft_buckets or narrow "
                f"the config")
        # each of these copies, shares or rolls back K/V rows; no other
        # per-layer state is among them (ROADMAP Queue R)
        for given, what in (
                (speculation is not None, "speculation="),
                (prefix_caching is not None, "prefix_caching="),
                (policy is not None and policy.preemption,
                 "policy= with preemption (its snapshot is of K/V rows)")):
            if given:
                engine.refuse_other_state(what)
        self.engine = engine
        # replica identity: None == anonymous (today's unlabeled event
        # stream and metric snapshot, byte-identical).  The engine gets
        # the name too — its serving_tp_step emits attribute to this
        # scheduler — and ALWAYS gets it assigned (None clears a stale
        # name when an engine is reused across scheduler lifetimes, so
        # a later anonymous run stays identity-clean).
        self.name = name
        engine.name = name
        if name is not None:
            obs_bridge.register_replica(name)
        self.max_queue = int(max_queue)
        self.log_interval = max(1, int(log_interval))
        self.prefill_budget = int(prefill_budget)
        self.speculation = speculation
        # paged engines price admission in POOL BLOCKS (memory scales
        # with used tokens, not slots x max_len) and capture/reuse
        # prefixes by block-table aliasing instead of K/V copies
        self._paged = engine.paged is not None
        # cross-request prefix caching (opt-in; None == off leaves every
        # existing path byte-for-byte untouched — no events, no gauge
        # sets, no extra engine programs).  Block size defaults to the
        # engine's smallest prefill bucket so restored chains land on
        # bucket-friendly chunk boundaries; a paged engine pins it to
        # the POOL block size (a cache entry IS a pool block there).
        self._prefix: Optional[PrefixCache] = None
        self._reclaim_hook = None
        if prefix_caching is not None:
            if self._paged:
                block = engine.block_size
                if (prefix_caching.block_size is not None
                        and prefix_caching.block_size != block):
                    raise ValueError(
                        f"prefix block_size {prefix_caching.block_size} "
                        f"!= the engine's pool block_size {block} — a "
                        f"paged cache entry IS a pool block, so the "
                        f"sizes cannot differ")
            else:
                block = (prefix_caching.block_size
                         if prefix_caching.block_size is not None
                         else engine.prefill_buckets[0])
            if block > engine.max_len - 1:
                raise ValueError(
                    f"prefix block_size {block} cannot fit a "
                    f"max_len={engine.max_len} cache alongside the "
                    f"resume token")
            if self._paged:
                # true per-block bytes — on a KV-int8 pool this counts
                # the fp32 scale pools riding the same block ids, not
                # just the int8 payload (pool-byte gauges and prefix
                # eviction budgets would otherwise undercount ~20%)
                per_block = pkv_bytes_per_block(engine.cache)
                self._prefix = PrefixCache(
                    block_size=block,
                    max_tokens=prefix_caching.max_tokens,
                    pool=engine.block_pool, bytes_per_block=per_block)
                # last-resort backpressure: an exhausted pool evicts
                # unpinned cache entries before raising.  The bound
                # method is STORED so close() can unhook exactly the
                # hook it installed (a re-fetched bound method is a
                # fresh object — identity would never match)
                self._reclaim_hook = self._prefix.evict_blocks
                engine.set_block_reclaim(self._reclaim_hook)
            else:
                self._prefix = PrefixCache(
                    block_size=block,
                    max_tokens=prefix_caching.max_tokens)
        self._clock = clock
        self._queue: deque[tuple[Request, float]] = deque()
        self._active: Dict[int, _Active] = {}
        self._results: Dict[str, RequestResult] = {}
        self._step_index = 0
        self._admit_seq = 0
        # O(1) duplicate-rid guard: every rid currently queued, active,
        # suspended, or holding an unclaimed result (pop_result removes
        # it — the rid becomes reusable, exactly the old linear-scan
        # semantics at set-lookup cost)
        self._live_rids: set = set()
        # control plane (None == byte-for-byte FIFO: no shedding, no
        # preemption, no tenant gauge, no new events)
        self.policy = policy
        self._wrr = (WeightedRoundRobin(policy)
                     if policy is not None else None)
        self._suspended: List[_Suspended] = []
        self._tenants_seen: set = set()
        self._preempted_total = 0
        self._resumed_total = 0
        self._cancelled_total = 0
        self._shed_total = 0
        # cumulative speculative-path accounting (host ints; the
        # speedup gauge and bench read these)
        self._spec_dispatches = 0
        self._spec_emitted = 0
        self._spec_drafted = 0
        self._spec_accepted = 0
        # checkpoint step of the weights being served (None = unknown
        # provenance).  Set by swap_weights(step=) and seeded by
        # HotReloader at construction; rides every routed/finished
        # event so a mixed-version fleet mid-rollout is observable.
        self.weights_step: Optional[int] = None
        # decode-ahead: what the device holds unread, in enqueue order, the
        # rids that finished when a settle point read it between steps (the
        # next step() reports them), and the counts of overlap_stats()
        self._flight: List[_InFlight] = []
        self._unreported: List[str] = []
        self._decode_steps = 0
        self._steps_ahead = 0
        self._settled_early: Dict[str, int] = {}
        self._dropped_tokens = 0

    def _emit(self, kind: str, **fields) -> None:
        """Every serving event this scheduler emits, replica-stamped
        when named.  Anonymous schedulers forward untouched — the
        event stream stays byte-identical to the pre-fleet one."""
        if self.name is not None:
            fields["replica"] = self.name
        emit_event(kind, **fields)

    # ---- submission ------------------------------------------------------
    def submit(self, request: Request) -> None:
        """Enqueue; raises :class:`QueueFull` at ``max_queue`` and
        ``ValueError`` for requests the engine can never serve."""
        rid = request.rid
        with obs_trace.span("serving.submit", rid=rid):
            # O(1): the live-rid set mirrors queue + active + suspended +
            # unclaimed results exactly (updated at submit / finish /
            # pop_result) — the old three linear scans made every submit
            # O(n) and a loadgen run O(n^2)
            if rid in self._live_rids:
                raise ValueError(
                    f"duplicate rid {rid!r}: already "
                    f"{'finished' if rid in self._results else 'in flight'} "
                    f"— two streams under one rid would overwrite each "
                    f"other's results")
            n = len(request.prompt)
            if request.max_new_tokens < 1:
                raise ValueError(
                    f"{request.rid}: max_new_tokens must be >= 1 "
                    f"(got {request.max_new_tokens})")
            if n < 1:
                raise ValueError(f"{request.rid}: empty prompt")
            if request.deadline_s is not None and request.deadline_s <= 0:
                raise ValueError(
                    f"{request.rid}: deadline_s must be > 0 (or None), got "
                    f"{request.deadline_s} — an already-expired deadline "
                    f"is a caller bug, not a sheddable request")
            if not request.tenant:
                raise ValueError(
                    f"{request.rid}: tenant must be a non-empty string")
            # prompts longer than prefill_len are fine (chunked cached
            # prefill serves them); the only hard ceiling is cache capacity.
            # The FINAL sampled token is never appended (the request finishes
            # right after sampling it), so peak cache use is one less than
            # prompt + output budget — a stream may fill the cache exactly
            if n + request.max_new_tokens - 1 > self.engine.max_len:
                raise ValueError(
                    f"{request.rid}: prompt {n} + max_new_tokens "
                    f"{request.max_new_tokens} needs "
                    f"{n + request.max_new_tokens - 1} cached positions, "
                    f"over cache max_len {self.engine.max_len}")
            if self._paged:
                # the paged analog of the max_len guard: a stream whose
                # worst-case (zero-sharing) footprint exceeds the whole
                # pool could stall every other stream before dying at
                # BlockPoolExhausted — reject it at the door instead
                bs = self.engine.block_size
                need = blocks_per_slot(n + request.max_new_tokens - 1, bs)
                usable = self.engine.block_pool.num_blocks - 1
                if need > usable:
                    raise ValueError(
                        f"{request.rid}: worst-case footprint of {need} "
                        f"blocks (block_size {bs}) exceeds the whole pool "
                        f"({usable} allocatable blocks) — raise num_blocks "
                        f"or shrink the request")
            if len(self._queue) >= self.max_queue:
                raise QueueFull(f"queue at capacity ({self.max_queue})")
            self._queue.append((request, self._clock()))
            self._live_rids.add(rid)
            if self.policy is not None:
                self._tenants_seen.add(request.tenant)
            self._emit("serving_request_queued", rid=request.rid,
                       prompt_tokens=n, queue_depth=len(self._queue))

    # ---- introspection ---------------------------------------------------
    @property
    def clock(self) -> Callable[[], float]:
        """The injectable monotonic clock every timing field
        (``t_submit`` / ``ttft_s`` / ``per_token_ms`` / event
        ``duration_s``) is measured on — ``time.monotonic`` by default.
        The load generator and request-trace recorder read THIS so all
        three layers stamp one timeline (a
        :class:`~apex_tpu.serving.loadgen.VirtualClock` here makes
        every latency in a test deterministic)."""
        return self._clock

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def active_count(self) -> int:
        return len(self._active)

    @property
    def steps_run(self) -> int:
        return self._step_index

    @property
    def spec_stats(self) -> Dict[str, int]:
        """Cumulative speculative-path accounting: verify ``dispatches``,
        ``drafted`` / ``accepted`` draft tokens, and ``emitted`` tokens
        (accepted + the per-verify bonus token).  All zero when
        speculation is off or bypassed — the escape-hatch witness."""
        return {"dispatches": self._spec_dispatches,
                "drafted": self._spec_drafted,
                "accepted": self._spec_accepted,
                "emitted": self._spec_emitted}

    @property
    def queued_rids(self) -> List[str]:
        """Rids waiting for admission, in arrival order."""
        return [r.rid for r, _ in self._queue]

    @property
    def active_rids(self) -> List[str]:
        """Rids holding a slot, in slot order."""
        return [self._active[s].request.rid
                for s in sorted(self._active)]

    def progress_of(self, rid: str) -> int:
        """Tokens emitted so far for ``rid`` — live count while active
        or suspended, the result's count once terminal, 0 while queued
        or unknown (lenient, like :meth:`phase_of`: fault drivers poll
        rids that may not have been submitted yet)."""
        result = self._results.get(rid)
        if result is not None:
            return len(result.tokens)
        for st in self._active.values():
            if st.request.rid == rid:
                return len(st.tokens)
        for sus in self._suspended:
            if sus.st.request.rid == rid:
                return len(sus.st.tokens)
        return 0

    def phase_of(self, rid: str) -> RequestPhase:
        if rid in self._results:
            return RequestPhase.DONE
        for st in self._active.values():
            if st.request.rid == rid:
                return st.phase
        for sus in self._suspended:
            if sus.st.request.rid == rid:
                return sus.st.phase      # DECODE, parked for resume
        return RequestPhase.QUEUED

    # ---- the loop --------------------------------------------------------
    def _paged_available(self) -> int:
        """Blocks an admission may claim: free pool blocks minus what
        already-admitted streams still RESERVE for their worst-case
        growth (blocks allocate lazily — pricing the prompt alone would
        let concurrent streams pass the gate and race each other into
        an uncatchable ``BlockPoolExhausted`` mid-DECODE), plus what
        prefix-cache eviction could reclaim."""
        bs = self.engine.block_size
        reserved = 0
        for st in self._active.values():
            rows = (len(st.request.prompt)
                    + st.request.max_new_tokens - 1)
            owned = self.engine.block_pool.owned_blocks(st.slot)
            reserved += max(blocks_per_slot(rows, bs) - owned, 0)
        return self.engine.free_blocks() - reserved + (
            self._prefix.evictable_blocks()
            if self._prefix is not None else 0)

    def _admit_request(self, request: Request, t_submit: float,
                       slot: int) -> None:
        """Shared admission body (FIFO and policy paths): bind the
        request to ``slot``, emit the admission event, and run the
        prefix-cache match — byte-for-byte the pre-policy sequence."""
        # per-request draft state: greedy requests under an enabled
        # speculation config start at the widest draft (adapt_k
        # narrows it on rejection); sampled-temperature requests get
        # draft_k=0 — drafting is BYPASSED for them and their whole
        # path (events, metrics, compiled programs) stays
        # byte-for-byte the plain one
        draft_k = (self.speculation.max_draft
                   if self.speculation is not None
                   and request.temperature <= 0 else 0)
        st = _Active(request=request, slot=slot, seq=self._admit_seq,
                     base_key=request_key_bits(request.seed),
                     tokens=[], t_submit=t_submit, t_first=0.0,
                     draft_k=draft_k,
                     wv=int(getattr(self.engine, "weights_version", 0)))
        self._admit_seq += 1
        self._active[slot] = st
        logger.debug("admitted %s into slot %d (queue %d deep)",
                     request.rid, slot, len(self._queue))
        # queue_wait_s rides the event so the obs bridge can feed
        # the apex_serving_queue_wait_seconds histogram and the
        # request-trace recorder can cross-check its own stamps —
        # measured on this scheduler's (injectable) clock
        self._emit("serving_request_admitted", rid=request.rid,
                   slot=slot, prompt_tokens=len(request.prompt),
                   queue_depth=len(self._queue),
                   queue_wait_s=round(self._clock() - t_submit, 6))
        if self._prefix is not None:
            self._match_and_restore(st)

    def _admit(self) -> None:
        """Fill free slots from the queue (FIFO).  Admission assigns a
        slot only — the prompt is cached chunk-by-chunk by
        :meth:`_prefill_work` under the per-step budget, so admitting a
        long prompt never blocks this step's decode for its whole
        length.  With a policy, selection (priority / fairness /
        preemption) is delegated to :meth:`_admit_policy`."""
        if self.policy is not None:
            self._admit_policy()
            return
        while self._queue:
            # the engine's slot-occupancy mirror is the ONE source of
            # truth for free slots (a scheduler-side copy could desync
            # from direct engine use and strand requests)
            free = [s for s in self.engine.free_slots()
                    if s not in self._active]
            if not free:
                break
            if self._paged and self._active:
                # admission prices BLOCKS, not slots: hold the next
                # request back while its WORST-CASE footprint — prompt
                # plus every decode token it may still grow, the same
                # ``n + max_new_tokens - 1`` rows submit() validates —
                # couldn't be covered by free + cache-evictable blocks
                # (live streams keep decoding and freeing; an idle
                # system always admits so a too-tight pool fails loudly
                # at allocation instead of deadlocking the queue).
                request, _ = self._queue[0]
                bs = self.engine.block_size
                need = blocks_per_slot(
                    len(request.prompt) + request.max_new_tokens - 1,
                    bs)
                if need > self._paged_available():
                    break
            request, t_submit = self._queue.popleft()
            self._admit_request(request, t_submit, free[0])

    # ---- the control plane (opt-in; every method below is only ever
    # reached when ``policy`` is set, except cancel() which is a plain
    # API and emits only when actually called) -------------------------
    def _tenant_inflight(self) -> Dict[str, int]:
        counts: Dict[str, int] = {}
        for st in self._active.values():
            t = st.request.tenant
            counts[t] = counts.get(t, 0) + 1
        return counts

    def _pick_victim(self, priority: int) -> Optional[_Active]:
        """The stream a ``priority``-class admission may evict: the
        lowest-priority DECODE stream strictly below ``priority``
        (equal classes never preempt each other — no thrash), youngest
        admission among equals (the least-established stream moves).
        Mid-PREFILL streams are never preempted: their partial prompt
        is cheaper to keep than to capture."""
        victims = [st for st in self._active.values()
                   if st.phase is RequestPhase.DECODE
                   and st.request.priority < priority]
        if not victims:
            return None
        return min(victims, key=lambda st: (st.request.priority,
                                            -st.seq))

    def _preempt(self, st: _Active, *, by_priority: int) -> None:
        """Losslessly evict an active DECODE stream: capture its cache
        state (dense: a bucketed host snapshot via
        :meth:`~apex_tpu.serving.engine.DecodeEngine.capture_slot`;
        paged: reference the slot's blocks — zero bytes move), release
        the slot, and park the stream for a bit-exact resume."""
        slot = st.slot
        length = int(self.engine.lengths()[slot])
        sus = _Suspended(st=st, length=length,
                         t_suspended=self._clock())
        if self._paged:
            # hold one reference per block across the release: the
            # slot's own references drop, ours keep the bytes resident
            ids = self.engine.slot_block_ids(slot)[
                :blocks_per_slot(length, self.engine.block_size)]
            self.engine.block_pool.ref(ids)
            sus.block_ids = ids
        else:
            k, v, _ = self.engine.capture_slot(slot)
            sus.kv = (k, v)
        self._active.pop(slot)
        self.engine.release(slot)
        st.slot = -1
        st.preemptions += 1
        self._suspended.append(sus)
        self._preempted_total += 1
        self._emit("serving_request_preempted", rid=st.request.rid,
                   slot=slot, priority=st.request.priority,
                   by_priority=by_priority,
                   new_tokens=len(st.tokens), cached_tokens=length)

    def _resume(self, sus: _Suspended, slot: int) -> None:
        """Restore a suspended stream into a free slot bit-exactly:
        the dense path writes the captured bytes back
        (:meth:`~apex_tpu.serving.engine.DecodeEngine.restore_prefix`
        — the existing restore program family, no new compiles), the
        paged path aliases the held blocks (zero-copy) and drops the
        suspension hold so the slot's writes need no spurious CoW."""
        st = sus.st
        if self._paged:
            self.engine.alias_prefix(slot, sus.block_ids, sus.length)
            # alias added the slot's references — drop the suspension
            # hold, or every tail append would copy-on-write against a
            # phantom sharer forever
            self.engine.block_pool.deref(sus.block_ids)
        else:
            self.engine.restore_prefix(slot, sus.kv, sus.length)
        st.slot = slot
        self._active[slot] = st
        self._resumed_total += 1
        self._emit("serving_request_resumed", rid=st.request.rid,
                   slot=slot, cached_tokens=sus.length,
                   suspended_s=round(self._clock() - sus.t_suspended,
                                     6))

    def _admit_policy(self) -> None:
        """Policy admission: serve the highest priority class with an
        admissible request; within a class resume preempted streams
        first (oldest preemption first), then draw tenants by smooth
        weighted round-robin (FIFO within a tenant).  When no slot is
        free, the class may preempt a strictly lower-priority DECODE
        stream (``policy.preemption``); tenants at their in-flight cap
        are skipped entirely."""
        policy = self.policy
        cap = policy.max_inflight_per_tenant
        while self._queue or self._suspended:
            inflight = self._tenant_inflight()

            def ok(tenant: str) -> bool:
                return cap is None or inflight.get(tenant, 0) < cap

            res = [(i, s) for i, s in enumerate(self._suspended)
                   if ok(s.st.request.tenant)]
            qs = [(i, rt) for i, rt in enumerate(self._queue)
                  if ok(rt[0].tenant)]
            if not res and not qs:
                break
            best = max([s.st.request.priority for _, s in res]
                       + [r.priority for _, (r, _) in qs])
            # choose the candidate FIRST (resume before queued within
            # the class; WRR across queued tenants), then check paged
            # block feasibility, and only THEN preempt for it — a
            # victim must never be evicted for an admission the pool
            # cannot cover (the victim's suspension hold would keep
            # its own blocks unavailable, and on a tight pool nothing
            # ever frees: a livelock the run() bound turns into
            # SchedulerStalled at best)
            res_best = [(i, s) for i, s in res
                        if s.st.request.priority == best]
            snap = None
            if res_best:
                qi, sus = res_best[0]      # oldest preemption first
                request = sus.st.request
                # the resume itself allocates nothing (alias), but the
                # stream's REMAINING growth must be coverable — its
                # original reservation evaporated while it was off the
                # active set
                held = len(sus.block_ids) if sus.block_ids else 0
            else:
                qs_best = [(i, rt) for i, rt in qs
                           if rt[0].priority == best]
                tenants = {rt[0].tenant for _, rt in qs_best}
                snap = self._wrr.snapshot()
                tenant = self._wrr.pick(tenants)
                qi, (request, t_submit) = next(
                    (i, rt) for i, rt in qs_best
                    if rt[0].tenant == tenant)
                held = 0
            if self._paged and self._active:
                need = blocks_per_slot(
                    len(request.prompt) + request.max_new_tokens - 1,
                    self.engine.block_size) - held
                if need > self._paged_available():
                    if snap is not None:
                        # roll the WRR charge back: the tenant was
                        # picked but never served — leaving the charge
                        # would skew fairness under pool pressure
                        self._wrr.restore(snap)
                    break
            free = [s for s in self.engine.free_slots()
                    if s not in self._active]
            if (not free and policy.preemption and self._flight
                    and self._pick_victim(best) is not None):
                # a victim is captured with its newest token and its
                # slot's rows: read what is in flight first.  That may
                # finish streams (the victim among them) and free a slot
                self._settle("preempt")
                free = [s for s in self.engine.free_slots()
                        if s not in self._active]
            if not free:
                victim = (self._pick_victim(best)
                          if policy.preemption else None)
                if victim is None:
                    if snap is not None:
                        self._wrr.restore(snap)
                    break
                self._preempt(victim, by_priority=best)
                free = [s for s in self.engine.free_slots()
                        if s not in self._active]
                if not free:            # defensive; release frees it
                    if snap is not None:
                        self._wrr.restore(snap)
                    break
            slot = free[0]
            if res_best:
                self._suspended.pop(qi)
                self._resume(sus, slot)
            else:
                del self._queue[qi]
                self._admit_request(request, t_submit, slot)

    def _shed_expired(self) -> List[str]:
        """Arrival-relative deadline shedding at the step boundary —
        both admission-time and mid-queue: any request (queued, or
        suspended by a preemption) whose completion deadline has
        already passed can no longer meet it, so it is shed before it
        wastes prefill budget.  Charged to goodput exactly like a
        QueueFull rejection (``finish_reason="shed"`` is not a
        :data:`SERVED_REASONS` member)."""
        now = self._clock()
        shed: List[str] = []
        if self._queue and any(
                r.deadline_s is not None for r, _ in self._queue):
            keep: deque = deque()
            for request, t_submit in self._queue:
                if (request.deadline_s is not None
                        and now - t_submit >= request.deadline_s):
                    self._terminal_result(
                        request, t_submit, t_first=0.0, tokens=[],
                        reason="shed")
                    self._shed_total += 1
                    shed.append(request.rid)
                    self._emit("serving_request_shed", rid=request.rid,
                               deadline_s=request.deadline_s,
                               waited_s=round(now - t_submit, 6),
                               new_tokens=0,
                               queue_depth=len(self._queue))
                else:
                    keep.append((request, t_submit))
            self._queue = keep
        if self._suspended:
            keep_s: List[_Suspended] = []
            for sus in self._suspended:
                st = sus.st
                deadline = st.request.deadline_s
                if (deadline is not None
                        and now - st.t_submit >= deadline):
                    self._drop_suspended_state(sus)
                    self._terminal_result(
                        st.request, st.t_submit, t_first=st.t_first,
                        tokens=st.tokens, reason="shed",
                        preemptions=st.preemptions)
                    self._shed_total += 1
                    shed.append(st.request.rid)
                    self._emit("serving_request_shed",
                               rid=st.request.rid, deadline_s=deadline,
                               waited_s=round(now - st.t_submit, 6),
                               new_tokens=len(st.tokens),
                               queue_depth=len(self._queue))
                else:
                    keep_s.append(sus)
            self._suspended = keep_s
        return shed

    def _drop_suspended_state(self, sus: _Suspended) -> None:
        """Release a suspended stream's captured state without
        resuming it (shed past its deadline, or cancelled): the paged
        hold is dereferenced (blocks free unless shared), the dense
        host snapshot simply drops."""
        if sus.block_ids is not None:
            self.engine.block_pool.deref(sus.block_ids)
            sus.block_ids = None
        sus.kv = None

    def _terminal_result(self, request: Request, t_submit: float, *,
                         t_first: float, tokens: List[int], reason: str,
                         preemptions: int = 0) -> None:
        """Record a non-served terminal outcome (cancelled / shed):
        partial tokens are kept (they were really produced), ``ttft_s``
        is NaN when no first token ever emitted.  First-token existence
        is judged by the token count, never by ``t_first`` truthiness —
        a virtual clock starting at 0.0 stamps a legitimate first token
        as exactly 0.0."""
        now = self._clock()
        total = max(now - t_submit, 1e-9)
        self._results[request.rid] = RequestResult(
            rid=request.rid, tokens=list(tokens), finish_reason=reason,
            ttft_s=(t_first - t_submit) if tokens else float("nan"),
            total_s=total, tokens_per_s=len(tokens) / total,
            preemptions=preemptions)

    def cancel(self, rid: str) -> bool:
        """Cancel one request wherever it lives — queued, suspended,
        or active — releasing its slot, paged blocks, and prefix-cache
        pins without disturbing any neighboring stream.  Partial
        output is kept in the result (``finish_reason="cancelled"``).
        Returns ``True`` when cancelled, ``False`` when the request
        already finished (too late — the result stands); raises
        ``KeyError`` for a rid this scheduler does not know.  Works
        with or without a policy (cancellation is backpressure from
        the *caller* — a disconnected client — not a scheduling
        decision)."""
        for i, (request, t_submit) in enumerate(self._queue):
            if request.rid == rid:
                del self._queue[i]
                self._terminal_result(request, t_submit, t_first=0.0,
                                      tokens=[], reason="cancelled")
                self._cancelled_total += 1
                self._emit("serving_request_cancelled", rid=rid,
                           phase="queued", new_tokens=0)
                return True
        for i, sus in enumerate(self._suspended):
            if sus.st.request.rid == rid:
                self._suspended.pop(i)
                self._drop_suspended_state(sus)
                st = sus.st
                self._terminal_result(st.request, st.t_submit,
                                      t_first=st.t_first,
                                      tokens=st.tokens,
                                      reason="cancelled",
                                      preemptions=st.preemptions)
                self._cancelled_total += 1
                self._emit("serving_request_cancelled", rid=rid,
                           phase="suspended",
                           new_tokens=len(st.tokens))
                return True
        if any(st.request.rid == rid for st in self._active.values()):
            # the partial output is what was really produced: a token
            # still on the device is one (and may be the stream's last,
            # which finishes it: too late to cancel)
            self._settle("cancel")
        for slot, st in list(self._active.items()):
            if st.request.rid == rid:
                if self._prefix is not None:
                    # a mid-PREFILL cancellation still pins the chain
                    # it was matching/extending — release, or the pins
                    # leak and those entries can never be evicted
                    self._release_pins(st)
                st.phase = RequestPhase.DONE
                self._active.pop(slot)
                self.engine.release(slot)
                self._terminal_result(st.request, st.t_submit,
                                      t_first=st.t_first,
                                      tokens=st.tokens,
                                      reason="cancelled",
                                      preemptions=st.preemptions)
                self._cancelled_total += 1
                self._emit("serving_request_cancelled", rid=rid,
                           phase=("decode" if st.tokens else "prefill"),
                           new_tokens=len(st.tokens))
                return True
        if rid in self._results:
            return False
        raise KeyError(
            f"cancel({rid!r}): unknown rid — never submitted, or its "
            f"result was already claimed via pop_result")

    @property
    def suspended_count(self) -> int:
        """Preempted streams parked for a bit-exact resume."""
        return len(self._suspended)

    @property
    def control_stats(self) -> Dict[str, int]:
        """Cumulative control-plane accounting: ``preempted`` /
        ``resumed`` lossless preemption cycles, ``cancelled`` requests,
        ``shed`` deadline evictions.  All zero without a policy (and
        with no :meth:`cancel` calls) — the identity witness."""
        return {"preempted": self._preempted_total,
                "resumed": self._resumed_total,
                "cancelled": self._cancelled_total,
                "shed": self._shed_total}

    # ---- prefix caching (opt-in; every call below is guarded by
    # ``self._prefix is not None``, so the default path never changes) --
    @property
    def prefix_cache(self) -> Optional[PrefixCache]:
        """The live :class:`PrefixCache` when ``prefix_caching`` is
        enabled (``None`` otherwise) — introspection for tests/bench."""
        return self._prefix

    # ---- fleet failover (export / adopt) ---------------------------------
    def export_streams(self, *, capture: bool = True
                       ) -> List[StreamExport]:
        """Evacuate EVERY live stream — queued, active, suspended —
        into portable :class:`StreamExport` records, releasing this
        scheduler's slots, paged block holds, and prefix-cache pins on
        the way out.  Unlike :meth:`cancel`, nothing terminal is
        recorded and no per-request events fire: the streams are not
        ending, they are *moving* (the fleet router narrates the move
        with its own ``serving_fleet_*`` events).  After export the
        scheduler is drained, so :meth:`close` succeeds.

        ``capture=True`` (a wedged-but-intact replica, or a rolling
        drain) snapshots each dense DECODE stream's cache so adoption
        elsewhere resumes mid-stream bit-exactly.  ``capture=False``
        models a hard-killed replica: the device cache is gone, only
        host-side request records survive — every stream exports bare
        and replays deterministically on adoption.  Paged streams
        always export bare (their capture is by block reference into
        this engine's pool; the bytes cannot cross engines).

        Records come back in original admission/arrival order so a
        router re-placing them preserves FIFO fairness within a
        priority class."""
        if capture:
            # a stream moves with every token it was given and the rows
            # that go with them (one that finishes on its token in flight
            # stays here, as a result)
            self._settle("export")
        else:
            # the device is gone, and what it held unread with it: every
            # stream replays from its request
            self._flight.clear()
        out: List[StreamExport] = []
        dense = not self._paged
        # active streams, admission order (DECODE streams carry their
        # cache when capture is possible; mid-PREFILL streams are
        # cheaper to replay than to capture — same rule as _preempt)
        for slot, st in sorted(self._active.items(),
                               key=lambda kv_: kv_[1].seq):
            exp = StreamExport(request=st.request, t_submit=st.t_submit,
                               preemptions=st.preemptions,
                               weights_step=self.weights_step)
            if (capture and dense
                    and st.phase is RequestPhase.DECODE):
                length = int(self.engine.lengths()[slot])
                k, v, _ = self.engine.capture_slot(slot)
                exp.kv = (k, v)
                exp.length = length
                exp.tokens = list(st.tokens)
                exp.t_first = st.t_first
            if self._prefix is not None:
                self._release_pins(st)
            self._active.pop(slot)
            self.engine.release(slot)
            self._live_rids.discard(st.request.rid)
            out.append(exp)
        # suspended streams: the dense capture already exists — it is
        # portable as-is; paged holds are dropped (pool-local)
        for sus in self._suspended:
            st = sus.st
            exp = StreamExport(request=st.request, t_submit=st.t_submit,
                               preemptions=st.preemptions,
                               weights_step=self.weights_step)
            if capture and dense and sus.kv is not None:
                exp.kv = sus.kv
                exp.length = sus.length
                exp.tokens = list(st.tokens)
                exp.t_first = st.t_first
            self._drop_suspended_state(sus)
            self._live_rids.discard(st.request.rid)
            out.append(exp)
        self._suspended = []
        # the queue, arrival order
        for request, t_submit in self._queue:
            out.append(StreamExport(request=request, t_submit=t_submit))
            self._live_rids.discard(request.rid)
        self._queue.clear()
        return out

    def adopt_stream(self, exp: StreamExport) -> bool:
        """Take over one exported stream.  A bare record (``kv`` is
        ``None``) re-enters the queue with its ORIGINAL submit stamp —
        queue-wait and TTFT accounting keep charging from the first
        submission, so failover can never flatter the latency
        distribution.  A captured record needs a free slot: the cache
        bytes are restored and decode continues mid-stream,
        bit-exactly (returns ``False`` — without consuming the record
        — when every slot is busy; the router retries next step).
        Raises ``ValueError`` on a rid already live here and, for
        captured records, on a paged engine (restore needs the dense
        ``restore_prefix`` write path)."""
        request = exp.request
        if request.rid in self._live_rids:
            raise ValueError(
                f"adopt_stream({request.rid!r}): rid already live on "
                f"this scheduler")
        if exp.kv is None:
            if len(self._queue) >= self.max_queue:
                raise QueueFull(
                    f"queue at capacity ({self.max_queue})")
            self._queue.append((request, exp.t_submit))
            self._live_rids.add(request.rid)
            if self.policy is not None:
                self._tenants_seen.add(request.tenant)
            self._emit("serving_request_queued", rid=request.rid,
                       prompt_tokens=len(request.prompt),
                       queue_depth=len(self._queue))
            return True
        if self._paged:
            raise ValueError(
                f"adopt_stream({request.rid!r}): captured K/V cannot "
                f"restore into a paged engine — export the donor with "
                f"capture=False (requeue) instead")
        free = [s for s in self.engine.free_slots()
                if s not in self._active]
        if not free:
            return False
        slot = free[0]
        self.engine.restore_prefix(slot, exp.kv, exp.length)
        st = _Active(request=request, slot=slot, seq=self._admit_seq,
                     base_key=request_key_bits(request.seed),
                     tokens=list(exp.tokens), t_submit=exp.t_submit,
                     t_first=exp.t_first,
                     prompt_pos=len(request.prompt),
                     phase=RequestPhase.DECODE,
                     issued=len(exp.tokens),
                     draft_k=(self.speculation.max_draft
                              if self.speculation is not None
                              and request.temperature <= 0 else 0),
                     preemptions=exp.preemptions + 1,
                     wv=int(getattr(self.engine, "weights_version", 0)))
        self._admit_seq += 1
        self._active[slot] = st
        self._live_rids.add(request.rid)
        if self.policy is not None:
            self._tenants_seen.add(request.tenant)
        self._emit("serving_request_resumed", rid=request.rid,
                   slot=slot, cached_tokens=exp.length,
                   suspended_s=None)
        return True

    def close(self) -> None:
        """Tear down this scheduler's prefix cache: drop every entry
        (on a paged engine that derefs the cached pool blocks) and
        unhook the engine's block-reclaim callback.  REQUIRED before
        building a new caching scheduler over the same engine — an
        abandoned paged cache otherwise pins its blocks forever and
        the allocator keeps reclaiming into the dead store.  Refuses
        while work is in flight; idempotent once drained."""
        # what is left on the device now belongs to streams that ended (an
        # EOS seen a step late): read and dropped
        self._settle("close")
        if self._active or self._queue or self._suspended:
            raise RuntimeError(
                f"close() with {len(self._active)} active stream(s), "
                f"{len(self._queue)} queued request(s) and "
                f"{len(self._suspended)} suspended stream(s) — drain "
                f"with run() (or cancel()) first")
        if self._prefix is not None:
            self._prefix.clear()
            if (self._paged and self.engine.block_pool.reclaim
                    is self._reclaim_hook):
                # unhook ONLY our own hook: a newer caching scheduler
                # over the same engine may have re-wired reclaim to
                # ITS cache — clearing that would silently disable
                # its backpressure and turn pool pressure into
                # BlockPoolExhausted despite reclaimable blocks
                self.engine.set_block_reclaim(None)

    def swap_weights(self, params, *, step: Optional[int] = None
                     ) -> object:
        """Hot-swap the engine's served weights at this step boundary;
        returns the displaced buffer (the caller's rollback copy).

        Call between :meth:`step` calls only (the scheduler is a single
        host loop, so "between steps" is any point a driver or loadgen
        ``step_hook`` runs).  The swap is a host pointer write — every
        compiled program family re-dispatches unchanged under the new
        tree (:meth:`DecodeEngine.swap_params` enforces the same-spec
        contract that makes that true) — and in-flight streams are
        PRESERVED: decode state (KV cache, block tables, lengths,
        sampler keys) is weight-independent, so active slots simply
        continue under the new weights, token streams intact.  The
        prefix cache is version-bumped so no cached pre-swap K/V can
        ever feed a post-swap admission; streams admitted pre-swap
        stop offering their (now hybrid) blocks.  The FIFO/default
        path with no swap ever requested is byte-for-byte untouched —
        this method is the ONLY reload surface the scheduler grows.

        ``step`` records the candidate's checkpoint step in
        :attr:`weights_step` (the :class:`~apex_tpu.serving.reload.
        HotReloader` passes it on every reload and rollback); a raw
        swap with no ``step`` honestly resets it to ``None`` — the
        provenance is unknown, and a stale step on a routed/finished
        event would lie about what served the request.
        """
        # every token the displaced weights computed is delivered before
        # the buffer goes back to the caller, who may free it
        self._settle("swap_weights")
        old = self.engine.swap_params(params)
        self.weights_step = None if step is None else int(step)
        if self._prefix is not None:
            self._prefix.bump_version()
        return old

    def _match_and_restore(self, st: _Active) -> None:
        """Admission-time prefix reuse: longest-chain match against the
        prompt, bucketed restore of the hit into the fresh slot, and a
        pin on every matched entry until the prompt is fully cached.
        The per-step prefill budget is then spent only on the uncovered
        suffix (``st.prompt_pos`` starts past the restored tokens) —
        and because the restored K/V are bit-identical to what prefill
        would have written, the stream from here on is bit-identical to
        a cold admission."""
        request = st.request
        covered, entries = self._prefix.match(request.prompt)
        if not covered:
            self._emit("serving_prefix_miss", rid=request.rid,
                       prompt_tokens=len(request.prompt))
            return
        t0 = self._clock()
        if self._paged:
            # zero-copy hit: append the shared block ids to the fresh
            # slot's table — no K/V bytes move, no compiled program
            # runs; the whole restore dispatch family is gone
            self.engine.alias_prefix(
                st.slot, [e.block_id for e in entries], covered)
            self._emit("serving_block_alias", rid=request.rid,
                       blocks=len(entries), saved_tokens=covered)
        else:
            self.engine.restore_prefix(st.slot,
                                       self._prefix.gather_kv(entries),
                                       covered)
        dt = self._clock() - t0
        self._prefix.acquire(entries)
        st.pinned = list(entries)
        st.prompt_pos = covered
        st.chain = entries[-1].chain
        st.blocks_cached = len(entries)
        self._emit("serving_prefix_hit", rid=request.rid,
                   saved_tokens=covered, blocks=len(entries),
                   prompt_tokens=len(request.prompt),
                   duration_s=round(dt, 6))

    def _offer_blocks(self, st: _Active) -> None:
        """Insert-on-miss capture: every prompt block the slot has
        fully cached and not yet offered is snapshotted — a
        ``read_region`` over exactly the rows prefill just wrote,
        immediately after the chunk that completed the block, so the
        entry is deterministically THE bytes a later restore must
        reproduce — and chained into the cache.  Each entry this
        request matches or inserts is pinned until its prompt is fully
        cached, so the chain it is still extending cannot be evicted
        mid-prefill (a parentless insert would be refused).

        Device cost is kept off the zero-overlap worst case: blocks
        another stream already cached are advanced over with a pure
        host-side hash probe (no read), and the remaining missing
        blocks of this chunk — always a contiguous tail, because a
        chain hash cannot exist without its parent — are snapshotted
        in ONE batched region read and sliced per block."""
        if st.wv != int(getattr(self.engine, "weights_version", 0)):
            # a stream admitted before a hot weight swap: its remaining
            # prefill rows are computed under the NEW weights but attend
            # over pre-swap cached context — self-consistent for the
            # stream itself, but the hybrid K/V must never be offered to
            # the cache (chain hashes are pure token hashes, so a fresh
            # same-prompt admission would restore these bytes as if they
            # were clean new-weights prefill output)
            return
        block = self._prefix.block_size
        total = st.prompt_pos // block     # complete blocks available
        # 1) advance over blocks another stream already inserted
        while st.blocks_cached < total:
            lo = st.blocks_cached * block
            blk = st.request.prompt[lo:lo + block]
            entry = self._prefix.lookup(self._prefix.chain_hash(st.chain,
                                                                blk))
            if entry is None:
                break
            self._prefix.acquire([entry])
            st.pinned.append(entry)
            st.chain = entry.chain
            st.blocks_cached += 1
        missing = total - st.blocks_cached
        if missing <= 0:
            return
        if self._paged:
            # 2a) paged capture is BY REFERENCE: the prompt's K/V
            # already lives in pool blocks the slot's table names, so
            # each missing block's entry just records its id and takes
            # an allocator reference — zero device work, the
            # zero-overlap overhead budget is pure host hashing
            ids = self.engine.slot_block_ids(st.slot)
            lo = st.blocks_cached
            blocks = [st.request.prompt[(lo + i) * block:
                                        (lo + i + 1) * block]
                      for i in range(missing)]
            entries = self._prefix.put_block_ids(
                st.chain, blocks, ids[lo:lo + missing])
            for entry in entries:
                self._prefix.acquire([entry])
                st.pinned.append(entry)
                st.chain = entry.chain
                st.blocks_cached += 1
            return
        # 2) batched snapshots of every missing block — a region read
        # whose span buffer the new entries share (the zero-overlap
        # overhead budget is ONE dispatch per chunk), inserted in
        # chain order.  Spans are clamped to a chunk's worth of blocks
        # so the read program's compile count stays bounded by
        # ceil(prefill_len / block) STRUCTURALLY, even if a pathology
        # ever left more than one chunk's blocks pending.
        max_span = max(1, self.engine.prefill_len // block)
        while missing > 0:
            count = min(missing, max_span)
            lo = st.blocks_cached * block
            k_span, v_span = self.engine.read_region(
                st.slot, lo, lo + count * block)
            blocks = [st.request.prompt[lo + i * block:
                                        lo + (i + 1) * block]
                      for i in range(count)]
            entries = self._prefix.put_blocks(st.chain, blocks, k_span,
                                              v_span)
            for entry in entries:
                self._prefix.acquire([entry])
                st.pinned.append(entry)
                st.chain = entry.chain
                st.blocks_cached += 1
            if len(entries) < count:
                # parent evicted under a tight budget (unreachable
                # while this chain is pinned — defensive): stop
                # extending rather than re-reading a growing span
                return
            missing -= count

    def _release_pins(self, st: _Active) -> None:
        if st.pinned:
            self._prefix.release(st.pinned)
            st.pinned = []

    def _prefill_work(self) -> None:
        """Spend up to ``prefill_budget`` prompt tokens on chunks,
        oldest admitted request first (FIFO: a request's first token
        never waits on a later arrival).  When a prompt completes, its
        first token is sampled from the final chunk's logits — TTFT
        includes its prefill chunks + zero decode steps — and stays on
        the device: the lane joins this step's decode, and the token is
        read with the step's one readback (:meth:`_deliver`)."""
        with obs_trace.span("serving.prefill") as sp:
            budget = self.prefill_budget
            chunks = 0
            # FIFO by admission order; under a policy, priority classes
            # drain first (a high-priority admission's first token must
            # not wait behind an earlier low-priority long prompt)
            key = (
                (lambda s: s.seq) if self.policy is None
                else (lambda s: (-s.request.priority, s.seq)))
            for st in sorted((s for s in self._active.values()
                              if s.phase is RequestPhase.PREFILL),
                             key=key):
                while budget > 0 and st.prompt_remaining:
                    chunk = min(st.prompt_remaining,
                                self.engine.prefill_len, budget)
                    offset = st.prompt_pos      # the chunk's START position
                    t0 = self._clock()
                    logits = self.engine.prefill_chunk(
                        st.slot, st.request.prompt[offset:offset + chunk])
                    dt = self._clock() - t0
                    st.prompt_pos = offset + chunk
                    budget -= chunk
                    chunks += 1
                    self._emit("serving_prefill_chunk", rid=st.request.rid,
                               bucket=self.engine.bucket_for(chunk),
                               chunk_tokens=chunk, offset_tokens=offset,
                               duration_s=round(dt, 6))
                    if self._prefix is not None:
                        self._offer_blocks(st)
                    if not st.prompt_remaining:
                        sampled = self.engine.sample(
                            logits[None], st.base_key[None], np.int32([0]),
                            np.float32([st.request.temperature]),
                            np.int32([st.request.top_k]))
                        lane = np.zeros((self.engine.slots,), bool)
                        lane[st.slot] = True
                        self._leave_on_device(sampled, lane, [(0, st)],
                                              first=True)
                        st.issued = 1
                        st.phase = RequestPhase.DECODE
                        if self._prefix is not None:
                            # the prompt is fully cached: the chain it was
                            # matching/extending no longer needs protection
                            self._release_pins(st)
                if budget <= 0:
                    break
            if sp is not None:
                sp.set_attribute("chunks", chunks)

    # ---- decode-ahead: what is in flight, and the one place it is read ----
    def _leave_on_device(self, sampled, lanes: np.ndarray,
                         streams: List[tuple], *, first: bool) -> None:
        """Leave freshly sampled tokens on the device: the engine keeps
        them as the ``lanes``' next input, the copy to the host starts
        now, and the record of what is in flight grows by one entry."""
        self.engine.keep_sampled(sampled, lanes)
        sampled.copy_to_host_async()
        self._flight.append(_InFlight(sampled, streams, first))

    def _deliver(self, upto: int) -> None:
        """Read the oldest ``upto`` entries in flight — THE blocking read —
        and hand each stream its token: stamp ``t_first`` on a first token,
        finish a stream on EOS or its budget (its rid goes to
        ``_unreported``, which :meth:`step` returns), drop a token whose
        stream already ended.  ``lag`` on the span is 1 when a newer decode
        step was enqueued before this one's tokens were read."""
        entries, self._flight = self._flight[:upto], self._flight[upto:]
        if not entries:
            return
        kinds = {"first_token" if e.first else "decode" for e in entries}
        lag = int("decode" in kinds
                  and any(not e.first for e in self._flight))
        # the span wraps the host read and nothing else, so device idle
        # under it is "device done, host not yet resumed" and idle under
        # any other span is the host working
        with obs_trace.span("serving.readback",
                            what="+".join(sorted(kinds)), lag=lag):
            read = [np.asarray(e.sampled) for e in entries]
        # a first token's arrival is this read's return, whatever the host
        # does before it reaches that stream below
        t_read = self._clock()
        with obs_trace.span("serving.finish") as sp:
            n_done = len(self._unreported)
            for entry, values in zip(entries, read):
                for index, st in entry.lanes:
                    if st.phase is RequestPhase.DONE:
                        self._dropped_tokens += 1
                        continue
                    if entry.first:
                        st.t_first = t_read
                        self._emit("serving_first_token",
                                   rid=st.request.rid,
                                   ttft_s=round(st.t_first - st.t_submit,
                                                6))
                    st.tokens.append(int(values[index]))
                    if self._finish_if_done(st):
                        self._unreported.append(st.request.rid)
            if sp is not None:
                sp.set_attribute("finished",
                                 len(self._unreported) - n_done)

    def _settle(self, reason: str) -> None:
        """A settle point: read everything in flight now, because the
        caller needs a stream's newest token or its slot's rows on the
        host (``reason`` names it in :meth:`overlap_stats`).  Nothing in
        flight: nothing happens."""
        if self._flight:
            self._settled_early[reason] = (
                self._settled_early.get(reason, 0) + 1)
            self._deliver(len(self._flight))

    def overlap_stats(self) -> Dict[str, object]:
        """How often a step ran ahead of the host's reads: ``steps`` that
        enqueued a decode, ``steps_ahead`` of them enqueued while the
        previous step's tokens were still unread, ``settled_early``
        ``{reason: times}`` something in flight was read at a settle point
        instead of at its step's end, and ``dropped_tokens`` computed for
        a stream that had already ended (one a stream that ends on EOS
        while decoding)."""
        return {"steps": self._decode_steps,
                "steps_ahead": self._steps_ahead,
                "settled_early": dict(self._settled_early),
                "dropped_tokens": self._dropped_tokens}

    def _finish_if_done(self, st: _Active) -> bool:
        request = st.request
        done_eos = (request.eos_id is not None and st.tokens
                    and st.tokens[-1] == request.eos_id)
        done_len = len(st.tokens) >= request.max_new_tokens
        if not (done_eos or done_len):
            return False
        now = self._clock()
        total = max(now - st.t_submit, 1e-9)
        # a stream that survived >= 1 lossless preemption finished with
        # full service (same tokens it would have produced uninterrupted
        # — bit-exact resume) but reports it visibly: latency fields of
        # a "preempted-resumed" result include the suspension gaps
        reason = "eos" if done_eos else "length"
        if st.preemptions:
            reason = "preempted-resumed"
        result = RequestResult(
            rid=request.rid, tokens=list(st.tokens),
            finish_reason=reason,
            ttft_s=st.t_first - st.t_submit, total_s=total,
            tokens_per_s=len(st.tokens) / total,
            preemptions=st.preemptions)
        st.phase = RequestPhase.DONE
        self._results[request.rid] = result
        self._active.pop(st.slot, None)
        self.engine.release(st.slot)     # immediate slot reuse
        # per_token_ms measures the DECODE path only (first token to
        # finish): queue wait and prefill live in ttft_s, so the field
        # stays meaningful for decode-latency diagnosis under load
        decode_s = max(now - st.t_first, 0.0)
        decode_steps = max(len(st.tokens) - 1, 1)
        self._emit("serving_request_finished", rid=request.rid,
                   finish_reason=result.finish_reason,
                   new_tokens=len(result.tokens),
                   tokens_per_s=round(result.tokens_per_s, 3),
                   per_token_ms=round(decode_s / decode_steps * 1e3, 3),
                   weights_step=self.weights_step)
        return True

    def _spec_work(self, decoding: Dict[int, "_Active"]) -> set:
        """Run one speculative verify per eligible decoding slot: draft
        by prompt lookup over the request's own prompt + generated
        history, verify all candidates in one multi-token dispatch,
        emit the accepted prefix plus the bonus token, and adapt the
        next draft length.  Returns the slots consumed: they already
        advanced this step and must not ride the batched decode.

        A slot falls back to the plain decode step whenever drafting
        cannot help: sampled-temperature request (``draft_k == 0`` —
        never even looked up), no n-gram match, fewer than 2 tokens of
        output budget left, or no cache room for a draft.  The
        emitted stream is bit-identical to plain decode by
        construction (acceptance compares the target's own argmax), so
        speculation is pure scheduling — pinned by
        ``tests/test_serving_spec.py``.
        """
        consumed: set = set()
        cfg = self.speculation
        lengths = self.engine.lengths()
        for slot, st in sorted(decoding.items()):
            request = st.request
            if st.draft_k < 1:
                continue                 # sampling path: bypassed
            remaining = request.max_new_tokens - len(st.tokens)
            # a draft of k emits at most k+1 tokens; k is capped so a
            # full accept lands exactly on max_new_tokens, and a
            # remaining budget of 1 (or a full cache) is cheaper as one
            # plain decode lane than a 2-wide verify
            cap = min(st.draft_k, remaining - 1,
                      self.engine.max_len - int(lengths[slot]) - 1)
            if cap < 1:
                continue
            draft = propose(list(request.prompt) + st.tokens, cap,
                            ngram_max=cfg.ngram_max,
                            ngram_min=cfg.ngram_min)
            if not draft:
                continue                 # no match: plain decode lane
            t0 = self._clock()
            accepted, greedy, _ = self.engine.verify_draft(
                slot, [st.tokens[-1]] + draft)
            dt = self._clock() - t0
            consumed.add(slot)
            st.draft_k = adapt_k(st.draft_k, len(draft), accepted, cfg)
            self._spec_dispatches += 1
            self._spec_drafted += len(draft)
            self._spec_accepted += accepted
            # the accepted draft plus the verify's free bonus token —
            # appended one at a time so an EOS inside the batch
            # truncates the stream exactly where plain decode would
            # have stopped
            n_emitted = 0
            for tok in draft[:accepted] + [int(greedy[accepted])]:
                st.tokens.append(int(tok))
                n_emitted += 1
                if self._finish_if_done(st):
                    self._unreported.append(request.rid)
                    break
            st.issued = len(st.tokens)
            self._spec_emitted += n_emitted
            self._emit("serving_spec_verify", rid=request.rid,
                       bucket=self.engine.draft_bucket_for(len(draft)),
                       drafted=len(draft), accepted=accepted,
                       emitted=n_emitted, duration_s=round(dt, 6))
        return consumed

    @property
    def prefill_backlog(self) -> int:
        """Deferred prefill work, in prompt tokens: what the budget has
        not yet cached for admitted requests, plus every queued
        request's whole prompt."""
        return (sum(st.prompt_remaining for st in self._active.values()
                    if st.phase is RequestPhase.PREFILL)
                + sum(len(r.prompt) for r, _ in self._queue))

    def step(self) -> List[str]:
        """One step boundary: (with a policy) shed expired deadlines,
        then admit into free slots — possibly preempting — spend the
        prefill budget on prompt chunks, then one shared decode step
        for every decoding slot; all of it enqueued, and then ONE wait
        for the device: the read of this step's first tokens and of the
        PREVIOUS step's decoded tokens, while this step's decode runs.
        Returns rids that reached a terminal state since the last call
        (finished or shed)."""
        with obs_trace.span("serving.step", step=self._step_index + 1,
                            active=len(self._active),
                            queued=len(self._queue)):
            with obs_trace.span("serving.admit"):
                if self.policy is not None and self.policy.deadline_shedding:
                    self._unreported.extend(self._shed_expired())
                self._admit()
            self._prefill_work()
            if self.speculation is not None:
                # drafting reads each stream's newest token on the host:
                # this step's first tokens now, the decode's below
                self._settle("speculation")
            decoding = {
                slot: st for slot, st in self._active.items()
                if st.phase is RequestPhase.DECODE
                and st.issued < st.request.max_new_tokens}
            if decoding and self.speculation is not None:
                # speculative verifies run between the prefill budget and
                # the shared decode step; slots they advanced are excluded
                # from this step's decode (they already emitted), everyone
                # else — sampled requests, no-match streams, mid-prefill
                # lanes — proceeds exactly as before
                with obs_trace.span("serving.spec"):
                    consumed = self._spec_work(decoding)
                decoding = {slot: st for slot, st in decoding.items()
                            if slot not in consumed}
            before = len(self._flight)
            if decoding:
                ahead = any(not e.first for e in self._flight)
                self._decode_steps += 1
                self._steps_ahead += ahead
                with obs_trace.span("serving.decode", lanes=len(decoding),
                                    ahead=int(ahead)):
                    slots = self.engine.slots
                    tokens = np.zeros((slots,), np.int32)
                    on_device = np.zeros((slots,), bool)
                    active = np.zeros((slots,), bool)
                    base_keys = np.zeros((slots, 2), np.uint32)
                    indices = np.zeros((slots,), np.int32)
                    temps = np.zeros((slots,), np.float32)
                    top_ks = np.zeros((slots,), np.int32)
                    for slot, st in decoding.items():
                        if st.issued == len(st.tokens):
                            tokens[slot] = st.tokens[-1]
                        else:       # sampled, kept on the device, unread
                            on_device[slot] = True
                        active[slot] = True
                        base_keys[slot] = st.base_key
                        indices[slot] = st.issued
                        temps[slot] = st.request.temperature
                        top_ks[slot] = st.request.top_k
                        st.issued += 1
                    # per-step device work: ONE decode dispatch + ONE sampler
                    # dispatch (keys fold inside the sampler) + the kept
                    # vector's update; mid-prefill slots ride as inactive
                    # lanes (their lengths never advance, and the next chunk
                    # overwrites the lane's masked garbage write)
                    logits = self.engine.decode(tokens, active,
                                                on_device=on_device)
                    sampled = self.engine.sample(
                        logits, base_keys, indices, temps, top_ks)
                    self._leave_on_device(
                        sampled, active, list(decoding.items()), first=False)
            if self.speculation is not None:
                self._settle("speculation")
            else:
                # the one place a step WAITS on the device, for what was
                # enqueued before its decode: the device runs the decode
                # while the host appends, finishes, publishes and enqueues
                # the next step
                self._deliver(before)
            with obs_trace.span("serving.publish"):
                self._publish_step()
        return self.pop_finished()

    def pop_finished(self) -> List[str]:
        """The rids that reached a terminal state since this was last
        asked: what :meth:`step` returns.  A settle point between steps
        (``cancel``, ``export_streams``, ``swap_weights``) can finish a
        stream whose last token was still on the device; the next step
        reports it, and a caller that will not step this scheduler again
        (a router failing a replica over) asks here."""
        finished, self._unreported = self._unreported, []
        return finished

    def _publish_step(self) -> None:
        """Close the step: count it, refresh the gauges, and every
        ``log_interval`` steps emit ``serving_step``."""
        self._step_index += 1
        # current-state gauges refresh EVERY step (a gauge tied to
        # log_interval would be stale for interval-1 steps); occupancy
        # and cache utilization ride the same sample so neither has to
        # be inferred from the other
        occupancy = len(self._active) / max(self.engine.slots, 1)
        cache_util = self.engine.cache_utilization()
        backlog = self.prefill_backlog
        obs_bridge.SERVING_QUEUE_DEPTH.set(len(self._queue))
        obs_bridge.SERVING_SLOT_OCCUPANCY.set(occupancy)
        obs_bridge.SERVING_CACHE_UTILIZATION.set(cache_util)
        obs_bridge.SERVING_PREFILL_BACKLOG.set(backlog)
        if self._prefix is not None:
            # only when enabled: the off path must leave the metric
            # stream byte-for-byte untouched (the identity contract)
            obs_bridge.SERVING_PREFIX_CACHED_TOKENS.set(
                self._prefix.cached_tokens)
        if self._paged:
            # pool residency is the paged engine's capacity truth (the
            # token-based cache_utilization above still reports the
            # logical fill); only set when paged — the dense metric
            # stream stays byte-for-byte untouched
            obs_bridge.SERVING_BLOCK_POOL_UTILIZATION.set(
                self.engine.block_pool_utilization())
        if self.policy is not None:
            # per-tenant in-flight gauge, every tenant this scheduler
            # ever saw (a tenant dropping to 0 must READ 0, not hold
            # its last value) — only under a policy, so the default
            # metric stream stays byte-for-byte untouched
            counts = self._tenant_inflight()
            for tenant in self._tenants_seen:
                obs_bridge.SERVING_TENANT_INFLIGHT.set(
                    counts.get(tenant, 0), tenant=tenant)
        # every step like the others (a cheap host-side jit-cache read):
        # a scrape during the first log_interval steps must not read 0
        # for a gauge documented as "1 == shape-stable"
        obs_bridge.SERVING_DECODE_COMPILES.set(self.engine.decode_compiles())
        if self._spec_dispatches:
            # tokens emitted per verify dispatch — the amortization the
            # speculative path actually delivered (1.0 == plain
            # decode's rate).  Only ever set once a verify has run, so
            # a speculation-off (or all-sampled) run leaves the metric
            # stream untouched — the escape-hatch identity contract
            obs_bridge.SERVING_SPEC_SPEEDUP.set(
                self._spec_emitted / self._spec_dispatches)
        if self.name is not None:
            # named (fleet) schedulers mirror every per-step gauge into
            # a {replica=...} series — the process-global series above
            # stay as the fleet-wide "last stepped" view, the labeled
            # ones stop replicas clobbering each other.  Same values,
            # same conditionals, so the attributed series reconcile
            # exactly with the aggregate ones.
            r = self.name
            obs_bridge.SERVING_QUEUE_DEPTH.set(
                len(self._queue), replica=r)
            obs_bridge.SERVING_SLOT_OCCUPANCY.set(occupancy, replica=r)
            obs_bridge.SERVING_CACHE_UTILIZATION.set(
                cache_util, replica=r)
            obs_bridge.SERVING_PREFILL_BACKLOG.set(backlog, replica=r)
            if self._prefix is not None:
                obs_bridge.SERVING_PREFIX_CACHED_TOKENS.set(
                    self._prefix.cached_tokens, replica=r)
            if self._paged:
                obs_bridge.SERVING_BLOCK_POOL_UTILIZATION.set(
                    self.engine.block_pool_utilization(), replica=r)
            obs_bridge.SERVING_DECODE_COMPILES.set(
                self.engine.decode_compiles(), replica=r)
            if self._spec_dispatches:
                obs_bridge.SERVING_SPEC_SPEEDUP.set(
                    self._spec_emitted / self._spec_dispatches,
                    replica=r)
        if self._step_index % self.log_interval == 0:
            self._emit("serving_step", step=self._step_index,
                       queue_depth=len(self._queue),
                       active_slots=len(self._active),
                       slot_occupancy=round(occupancy, 4),
                       cache_utilization=round(cache_util, 6),
                       prefill_backlog=backlog,
                       # mesh width the step's programs ran over (1 =
                       # single-chip; getattr so engine doubles in
                       # tests keep working)
                       tp=int(getattr(self.engine, "tp_size", 1)))

    def _derived_step_bound(self) -> int:
        """A generous progress bound for :meth:`run`: every step of a
        healthy drain either caches >= 1 prompt token (budget >= 1),
        emits >= 1 token for >= 1 decoding stream, or retires a
        request — so total steps are bounded by the remaining token
        work.  4x slack plus a constant covers admission/resume
        boundaries; only a stream that genuinely never finishes (an
        engine bug) can exceed it."""
        work = 0
        for request, _ in self._queue:
            work += len(request.prompt) + request.max_new_tokens
        for st in self._active.values():
            work += st.prompt_remaining + max(
                st.request.max_new_tokens - len(st.tokens), 1)
        for sus in self._suspended:
            work += max(sus.st.request.max_new_tokens
                        - len(sus.st.tokens), 1)
        return 64 + 4 * work

    def run(self, max_steps: Optional[int] = None
            ) -> Dict[str, RequestResult]:
        """Drive :meth:`step` until queue, slots, and suspended
        streams drain; returns rid -> :class:`RequestResult`.

        ``max_steps`` is a progress bound, not a pacing knob (drive
        :meth:`step` directly for partial drains): left ``None`` it is
        derived from the queued work, and exceeding it raises
        :class:`SchedulerStalled` with the scheduler's state — an
        engine bug that never finishes a stream surfaces as a
        diagnosable error instead of spinning forever."""
        if max_steps is None:
            max_steps = self._derived_step_bound()
        steps = 0
        while self._queue or self._active or self._suspended:
            if steps >= max_steps:
                raise SchedulerStalled(
                    f"no drain after {steps} steps (bound {max_steps}):"
                    f" {len(self._queue)} queued, "
                    f"{len(self._active)} active "
                    f"({[st.request.rid for st in self._active.values()][:8]}),"
                    f" {len(self._suspended)} suspended, prefill "
                    f"backlog {self.prefill_backlog} tokens — an "
                    f"engine or driver bug is keeping a stream from "
                    f"finishing")
            self.step()
            steps += 1
        # drained: tokens still on the device are lanes of ended streams
        self._settle("drain")
        return dict(self._results)

    @property
    def results(self) -> Dict[str, RequestResult]:
        return dict(self._results)

    def pop_result(self, rid: str) -> RequestResult:
        """Claim (and forget) one finished result.  Long-running drivers
        should pop results as :meth:`step` reports them finished —
        unclaimed results are retained indefinitely (and their rids stay
        reserved by the duplicate guard)."""
        result = self._results.pop(rid)
        self._live_rids.discard(rid)
        return result

    def pop_results(self) -> Dict[str, RequestResult]:
        """Claim (and forget) every finished result."""
        out, self._results = self._results, {}
        self._live_rids.difference_update(out)
        return out
