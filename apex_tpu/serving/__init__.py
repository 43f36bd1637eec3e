"""apex_tpu.serving — KV-cached decode + continuous batching.

The ROADMAP's north star serves heavy traffic; this subsystem is the
inference-side counterpart of the training stack, reusing its kernels
(flash attention's masked read path, the rope offset machinery, the LM
head matmul), its amp policies, and its resilience checkpoints:

- :mod:`.kv_cache` — preallocated slot-indexed decode cache
  (``[layers, slots, max_len, kv_heads, head_dim]``) with per-slot
  lengths and pure shape-stable updates (drop-mode row scatters on
  the whole buffer, a chunk's rows for prefill and one row a lane for
  decode appends): one static shape for every decode step, zero recompiles
  after warmup.  A model declares what each layer keeps a slot
  (``cache_layers()``: ``KVRows``, ``RecurrentRows``, ``CallCounters``)
  and :func:`init_cache` builds every cache from that, in the layout
  (dense / paged) and storage format (float / int8) asked for; layers
  that are not all attention are served from one :class:`HybridCache`:
  K/V rows for the layers that have them, a :class:`RecurrentState` for
  the rest.  The module is also the one seam a model's attention calls
  through (``decode_attend`` / ``prefill_attend``: write, view, cast,
  the grouped masked read), whatever the layout and format.
- :mod:`.paged_kv_cache` — the opt-in **paged** layout
  (``DecodeEngine(..., paged=PagedCacheConfig(...))``): a global pool
  of fixed-size K/V blocks (``[layers, num_blocks, block_size,
  kv_heads, head_dim]``) read through per-slot block tables by
  fixed-extent gathers at the same ``-1e30`` mask convention — greedy
  streams stay **bit-identical** to the dense engine while memory
  scales with *used* tokens (several times more concurrent streams
  per byte; admission prices blocks).  Prefix-cache hits become
  zero-copy block-table aliasing with refcounts
  (``DecodeEngine.alias_prefix``), ``DecodeEngine.fork_slot``
  branches a live stream the same way, and copy-on-write keeps every
  sharer of a block bit-isolated.
- :mod:`.engine` — :class:`DecodeEngine`: length-bucketed **chunked
  prefill** (a prompt chunk is padded to the smallest covering
  power-of-two bucket, so a short prompt costs a short dispatch and
  compile count is bounded by the bucket table; prompts up to
  ``max_len`` serve — chunks past the first read the cached context
  through the decode path's masked fixed-extent attention) + a jitted
  batched single-token decode step, with deterministic
  greedy/temperature/top-k sampling from explicit PRNG keys.  Prefill
  AND cached incremental decode are bit-identical to the shape-stable
  uncached full-context forward (the tier-1 acceptance tests).
  Opt-in **tensor parallelism** (``tp=TPConfig(size=N)``) wraps the
  same program bodies in ``shard_map`` over a 1-D serving mesh:
  params take the training stack's Megatron column/row split, the KV
  cache shards head-wise, lengths/tables replicate, and tp=2/4 greedy
  streams stay token-identical to the single-chip engine (logits
  argmax-tier — the psum's reduction order genuinely differs).
- :mod:`.draft` — prompt-lookup drafting for **exact-greedy
  speculative decoding**: a host-side longest-suffix n-gram match over
  each request's prompt + generated history proposes up to k candidate
  tokens (no draft model, zero device cost); the engine's bucketed
  **verify** program scores all k+1 positions in one cached
  multi-token forward and accepts the longest prefix the target's own
  greedy argmax agrees with — the emitted stream is bit-identical to
  plain one-token decode by construction, and the per-request draft
  length adapts to the measured acceptance.
- :mod:`.prefix_cache` — **cross-request prefix caching**: prompts are
  hashed as a chain of fixed-size token blocks, each entry holding the
  captured per-layer K/V for its span as owned device arrays; at
  admission the scheduler restores the longest cached chain into the
  fresh slot (``DecodeEngine.restore_prefix``) and spends prefill only
  on the uncovered suffix — bit-identical to a cold admission, because
  the restored bytes ARE what prefill would have written.  LRU
  eviction under a token budget, ref-count pinning for entries feeding
  live slots, insert-on-miss capture.  Opt-in
  (``prefix_caching=PrefixCacheConfig(...)``), default off.
- :mod:`.scheduler` — :class:`ContinuousBatchingScheduler`: bounded
  FIFO queue, slot admission at step boundaries, a per-step
  ``prefill_budget`` (in tokens) that interleaves prompt chunks with
  the shared decode step — a long admission never stalls live streams
  for its whole prefill — QUEUED → PREFILL → DECODE → DONE per-request
  state machine, EOS/max-token eviction with immediate slot reuse, and
  structured telemetry (queue depth, prefill backlog, per-chunk
  dispatch time, TTFT, per-token latency, tokens/s) via
  ``emit_event``.
- :mod:`.policy` — the **serving control plane** knob
  (``ContinuousBatchingScheduler(..., policy=SchedulingPolicy(...))``):
  priority classes with **lossless preemption** (a low-priority DECODE
  stream is evicted by capturing its cache state — dense bucketed
  snapshot or paged block references — and resumed *bit-exactly*
  later: same tokens, same f32 logits), request ``cancel(rid)``,
  arrival-relative deadline load shedding at admission and mid-queue,
  and per-tenant smooth-weighted-round-robin admission with in-flight
  caps.  Default off: a scheduler without ``policy=`` is byte-for-byte
  the FIFO scheduler.
- :mod:`.loadgen` — deterministic **open-loop workload generation**:
  seeded arrival processes (uniform / Poisson / burst trains), the
  canonical prompt mixes (shared-prefix fleet, zero-overlap, the
  bench's short-skewed length recipe), per-request deadlines, and a
  :class:`LoadGenerator` that drives the scheduler at controlled
  offered load on its injectable clock — sleep-free and bit-
  reproducible on a :class:`VirtualClock`, shedding arrivals at
  :class:`QueueFull` so overload shows up as goodput, not as a slowed
  arrival process.  Pairs with
  :class:`apex_tpu.obs.RequestTraceRecorder` +
  :func:`apex_tpu.obs.build_report` for p50/p95/p99 TTFT / TPOT /
  queue-wait and goodput SLO reports.
- :mod:`.quant` — **quantized serving** (``DecodeEngine(...,
  quant=QuantConfig(...))``, default off): int8 weights (per-output-
  channel scales, dequant fused into the existing jitted program
  families — no new compiles), int8 KV cache (per-(position, head)
  scales beside the dense slots or the paged block pool; capture hands
  out dequantized fp32 so prefix caching, speculation, preemption, and
  fleet failover stay quantization-oblivious), and an opt-in grouped-
  scale int8 tp allreduce for the per-layer psum pair.  Acceptance is
  agreement-tier: pinned greedy-stream agreement + bounded per-
  position logit error vs the fp32 engine, and ≥1.8x decode streams
  per byte of KV budget.
- :mod:`.weights` — :func:`load_serving_params`: newest *valid* step
  from a resilience checkpoint root (v1 whole-tree and v2 sharded both
  work), params subtree selection, bf16 serving casts through
  ``amp.policy``, and mesh-direct restore for tensor-parallel serving
  (``shardings=tp_param_shardings(...)`` places every leaf onto the
  serving mesh inside the restore itself — no host-replicated detour).
- :mod:`.reload` — **zero-downtime weight lifecycle** over a live
  scheduler: :class:`WeightWatcher` polls for newer *committed*
  training steps (in-process ``AsyncCheckpointer``, supervisor
  heartbeat pointer, or registry-aware root walk);
  :class:`HotReloader` restores the candidate double-buffered through
  the validated path, gates on a structural/spec check, swaps at a
  step boundary with in-flight streams preserved and the prefix cache
  version-invalidated, retains the displaced buffer for one-step
  :meth:`~HotReloader.rollback`; :class:`ShadowABScheduler` mirrors a
  deterministic traffic fraction onto a shadow engine serving
  candidate weights and builds per-arm SLO reports for the promotion
  decision.  Default off: a scheduler that never constructs these is
  byte-for-byte unchanged.

End-to-end recipe (the shape ``tests/test_serving.py`` drives)::

    from apex_tpu import serving as sv
    from apex_tpu import amp

    params, step = sv.load_serving_params(
        "/ckpts/run7", like=train_state_template, params_key="params",
        policy=amp.policy.O2())
    eng = sv.DecodeEngine(model, params, slots=8, max_len=2048,
                          prefill_len=256)
    sched = sv.ContinuousBatchingScheduler(eng, max_queue=64)
    sched.submit(sv.Request("r0", prompt_ids, max_new_tokens=128,
                            eos_id=2, temperature=0.7, top_k=40, seed=7))
    results = sched.run()              # rid -> RequestResult
"""

from apex_tpu.serving.draft import SpeculationConfig, adapt_k, propose
from apex_tpu.serving.loadgen import (
    LoadGenerator,
    LoadgenResult,
    OpenLoopWorkload,
    VirtualClock,
    burst_arrivals,
    chain_hooks,
    make_workload,
    mixed_length_prompts,
    poisson_arrivals,
    shared_prefix_prompts,
    uniform_arrivals,
    zero_overlap_prompts,
)
from apex_tpu.serving.engine import (
    DecodeEngine,
    TPConfig,
    default_draft_buckets,
    default_prefill_buckets,
    request_key,
    request_key_bits,
    sample_tokens,
    token_key,
    tp_param_shardings,
)
from apex_tpu.serving.kv_cache import (
    CallCounters,
    HybridCache,
    KVCache,
    KVRows,
    QuantKVCache,
    RecurrentRows,
    RecurrentState,
    append_token,
    init_cache,
    prefill_into_slot,
    read_slot_region,
    release_slot,
    valid_token_mask,
    value_dtype,
    write_slot_region,
)
from apex_tpu.serving.paged_kv_cache import (
    BlockPoolExhausted,
    PagedCacheConfig,
    PagedCacheManager,
    PagedKVCache,
    QuantPagedKVCache,
)
from apex_tpu.serving.quant import (
    QTensor,
    QuantConfig,
    dequant_params,
    evaluate_quant,
    is_quantized,
    kv_bytes_per_token,
    max_logit_error,
    param_bytes,
    quantize_params,
    quantized_allreduce,
    serving_param_spec,
    stream_agreement,
)
from apex_tpu.serving.policy import SchedulingPolicy, WeightedRoundRobin
from apex_tpu.serving.prefix_cache import PrefixCache, PrefixCacheConfig
from apex_tpu.serving.scheduler import (
    SERVED_REASONS,
    ContinuousBatchingScheduler,
    QueueFull,
    Request,
    RequestPhase,
    RequestResult,
    SchedulerStalled,
    StreamExport,
)
from apex_tpu.serving.fleet import FleetConfig, FleetRouter, ReplicaState
from apex_tpu.serving.reload import (
    ABConfig,
    HotReloader,
    ReloadOutcome,
    ShadowABScheduler,
    WeightWatcher,
    assign_arm,
)
from apex_tpu.serving.rollout import (
    CanaryGate,
    CanaryVerdict,
    RollingReloadController,
    RolloutConfig,
)
from apex_tpu.serving.weights import load_serving_params

__all__ = [
    "KVCache",
    "HybridCache",
    "RecurrentState",
    "KVRows",
    "RecurrentRows",
    "CallCounters",
    "append_token",
    "init_cache",
    "prefill_into_slot",
    "read_slot_region",
    "release_slot",
    "valid_token_mask",
    "write_slot_region",
    "BlockPoolExhausted",
    "PagedCacheConfig",
    "PagedCacheManager",
    "PagedKVCache",
    "QuantKVCache",
    "QuantPagedKVCache",
    "value_dtype",
    "QTensor",
    "QuantConfig",
    "dequant_params",
    "evaluate_quant",
    "is_quantized",
    "kv_bytes_per_token",
    "max_logit_error",
    "param_bytes",
    "quantize_params",
    "quantized_allreduce",
    "serving_param_spec",
    "stream_agreement",
    "PrefixCache",
    "PrefixCacheConfig",
    "DecodeEngine",
    "TPConfig",
    "tp_param_shardings",
    "SpeculationConfig",
    "adapt_k",
    "default_draft_buckets",
    "default_prefill_buckets",
    "propose",
    "request_key",
    "request_key_bits",
    "sample_tokens",
    "token_key",
    "ContinuousBatchingScheduler",
    "QueueFull",
    "Request",
    "RequestPhase",
    "RequestResult",
    "SchedulerStalled",
    "StreamExport",
    "FleetConfig",
    "FleetRouter",
    "ReplicaState",
    "SchedulingPolicy",
    "WeightedRoundRobin",
    "SERVED_REASONS",
    "LoadGenerator",
    "LoadgenResult",
    "OpenLoopWorkload",
    "VirtualClock",
    "burst_arrivals",
    "chain_hooks",
    "make_workload",
    "mixed_length_prompts",
    "poisson_arrivals",
    "shared_prefix_prompts",
    "uniform_arrivals",
    "zero_overlap_prompts",
    "load_serving_params",
    "ABConfig",
    "HotReloader",
    "ReloadOutcome",
    "ShadowABScheduler",
    "WeightWatcher",
    "assign_arm",
    "CanaryGate",
    "CanaryVerdict",
    "RollingReloadController",
    "RolloutConfig",
]
