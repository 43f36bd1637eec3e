"""Generate the markdown API reference (docs/api/*.md) from the package.

Mirrors the coverage of the reference's sphinx tree
(``/root/reference/docs/source/index.rst``: amp, parallel, optimizers,
layernorm, fp16_utils) and extends it to every public apex_tpu package.
Signatures and docstrings are introspected from the live modules, so the
docs cannot drift from the code: re-run this after API changes.

    python tools/gen_api_docs.py [--check]

``--check`` exits 1 if the generated tree differs from what is on disk
(tests/test_docs.py runs a light version of this).
"""

from __future__ import annotations

import argparse
import importlib
import inspect
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "docs", "api")

# page -> (title, [module, ...]) — grouped like the reference's toctree
PAGES = {
    "amp": ("Mixed precision (amp)", [
        "apex_tpu.amp", "apex_tpu.amp.policy", "apex_tpu.amp.scaler",
        "apex_tpu.amp.lists", "apex_tpu.amp.functional",
        "apex_tpu.amp.quant",
        "apex_tpu.fp16_utils",
    ]),
    "optimizers": ("Fused optimizers", [
        "apex_tpu.optimizers", "apex_tpu.optimizers._common",
        "apex_tpu.contrib.optimizers",
        "apex_tpu.multi_tensor_apply",
    ]),
    "parallel": ("Data / model parallelism", [
        "apex_tpu.parallel", "apex_tpu.parallel.LARC",
        "apex_tpu.transformer.parallel_state",
    ]),
    "transformer": ("Transformer toolbox (tp / pp / sp / ep / cp)", [
        "apex_tpu.transformer.tensor_parallel",
        "apex_tpu.transformer.pipeline_parallel",
        "apex_tpu.transformer.moe",
        "apex_tpu.transformer.context_parallel",
        "apex_tpu.transformer.layers",
        "apex_tpu.transformer.functional",
        "apex_tpu.transformer.amp",
        "apex_tpu.transformer.testing",
    ]),
    "normalization": ("Normalization layers", [
        "apex_tpu.normalization",
    ]),
    "layers": ("Fused dense / MLP / RNN", [
        "apex_tpu.fused_dense", "apex_tpu.mlp", "apex_tpu.RNN",
    ]),
    "ops": ("Pallas kernels (ops)", [
        "apex_tpu.ops.flash_attention", "apex_tpu.ops.softmax",
        "apex_tpu.ops.rope", "apex_tpu.ops.layer_norm",
        "apex_tpu.ops.packed_update", "apex_tpu.ops.fused_lm_head",
        "apex_tpu.ops.pair_bias_attention",
        "apex_tpu.ops.cached_decode_attention",
        "apex_tpu.ops.latent_chunk_attention",
        "apex_tpu.ops.kv_chunk_attention",
    ]),
    "models": ("Model zoo", [
        "apex_tpu.models", "apex_tpu.models.llama",
        "apex_tpu.models.llama_pipeline", "apex_tpu.models.vit",
        "apex_tpu.models.nemotron_h", "apex_tpu.models.dots3",
        "apex_tpu.models.mellum",
    ]),
    "contrib": ("Contrib extensions", [
        "apex_tpu.contrib.xentropy", "apex_tpu.contrib.focal_loss",
        "apex_tpu.contrib.group_norm", "apex_tpu.contrib.groupbn",
        "apex_tpu.contrib.cudnn_gbn", "apex_tpu.contrib.index_mul_2d",
        "apex_tpu.contrib.fmha", "apex_tpu.contrib.multihead_attn",
        "apex_tpu.contrib.transducer", "apex_tpu.contrib.halo",
        "apex_tpu.contrib.conv_bias_relu", "apex_tpu.contrib.sparsity",
        "apex_tpu.contrib.clip_grad", "apex_tpu.contrib.openfold_triton",
    ]),
    "resilience": ("Training resilience", [
        "apex_tpu.resilience", "apex_tpu.resilience.checkpoint",
        "apex_tpu.resilience.async_checkpoint",
        "apex_tpu.resilience.elastic",
        "apex_tpu.resilience.consistency",
        "apex_tpu.resilience.fault_injection",
        "apex_tpu.resilience.guarded",
        "apex_tpu.resilience.supervisor",
        "apex_tpu.resilience.retry",
        "apex_tpu.resilience.data_guard",
    ]),
    "serving": ("Serving (KV-cached decode + continuous batching)", [
        "apex_tpu.serving", "apex_tpu.serving.kv_cache",
        "apex_tpu.serving.paged_kv_cache",
        "apex_tpu.serving.quant",
        "apex_tpu.serving.engine", "apex_tpu.serving.draft",
        "apex_tpu.serving.prefix_cache",
        "apex_tpu.serving.scheduler", "apex_tpu.serving.policy",
        "apex_tpu.serving.loadgen",
        "apex_tpu.serving.weights",
        "apex_tpu.serving.reload",
        "apex_tpu.serving.fleet",
        "apex_tpu.serving.rollout",
    ]),
    "observability": ("Observability (metrics, spans, exporters)", [
        "apex_tpu.obs", "apex_tpu.obs.metrics", "apex_tpu.obs.trace",
        "apex_tpu.obs.request_trace", "apex_tpu.obs.slo",
        "apex_tpu.obs.bridge", "apex_tpu.obs.scopes",
    ]),
    "utils": ("Utilities", [
        "apex_tpu.utils.packing",
        "apex_tpu.utils.serialization", "apex_tpu.utils.compat",
        "apex_tpu.utils.compile_cache",
        "apex_tpu.feature_registry", "apex_tpu._logging",
    ]),
}


# strip runtime memory addresses from default-value reprs (flax module
# sentinels, function objects, dataclass auto-docstrings): regenerated
# docs must be deterministic
_ADDR_RE = re.compile(r" at 0x[0-9a-f]+")


def _doc_first_block(obj) -> str:
    if inspect.isclass(obj) and vars(obj).get("__doc__") is None:
        # no own docstring: inspect.getdoc would return the (misleading)
        # inherited base-class doc — use the defining module's instead
        try:
            mod = importlib.import_module(obj.__module__)
            doc = (mod.__doc__ or "").split("\n\n")[0].strip()
            return _ADDR_RE.sub("", doc)
        except Exception:
            return ""
    doc = inspect.getdoc(obj) or ""
    block = doc.split("\n\n")[0].strip()
    # flax/dataclass auto-docstrings embed field-default reprs with
    # runtime addresses — scrub for deterministic regeneration
    return _ADDR_RE.sub("", block)


def _sig(obj) -> str:
    try:
        return _ADDR_RE.sub("", str(inspect.signature(obj)))
    except (ValueError, TypeError):
        return "(...)"


def _public_names(mod):
    if hasattr(mod, "__all__"):
        return list(mod.__all__)
    return [n for n, o in vars(mod).items()
            if not n.startswith("_")
            and getattr(o, "__module__", None) == mod.__name__
            and (inspect.isclass(o) or inspect.isfunction(o))]


def _render_symbol(name: str, obj) -> list[str]:
    lines = []
    if inspect.isclass(obj):
        lines.append(f"### class `{name}{_sig(obj)}`\n")
        d = _doc_first_block(obj)
        if d:
            lines.append(d + "\n")
        # public methods defined on the class itself.  NB classmethod
        # objects are NOT callable() in CPython 3.12 — test the wrapper
        # types first or every @classmethod constructor vanishes
        for mname, m in sorted(vars(obj).items()):
            is_wrapped = isinstance(m, (classmethod, staticmethod))
            if mname.startswith("_") or not (is_wrapped or callable(m)):
                continue
            try:
                func = m.__func__ if is_wrapped else m
                # skip dataclass FIELDS whose default happens to be a
                # function (flax `kernel_init=nn.initializers.zeros` etc.)
                # — they are data, not API methods.  A real method's
                # qualname is anchored to this class.
                qn = getattr(func, "__qualname__", "")
                if not is_wrapped and not qn.startswith(obj.__name__ + "."):
                    continue
                kind = "classmethod " if isinstance(m, classmethod) else ""
                lines.append(f"- **{kind}`.{mname}{_sig(func)}`** — "
                             f"{_doc_first_block(func) or '(no doc)'}")
            except Exception:
                continue
        if lines and lines[-1].startswith("- "):
            lines.append("")
    elif callable(obj):
        lines.append(f"### `{name}{_sig(obj)}`\n")
        d = _doc_first_block(obj)
        if d:
            lines.append(d + "\n")
    else:  # data export (e.g. enum instance, constant)
        if isinstance(obj, (set, frozenset)):
            # set reprs are hash-order dependent; sort for stable docs
            body = ", ".join(repr(x) for x in sorted(obj, key=repr))
            rendered = f"{type(obj).__name__}({{{body}}})"
        else:
            rendered = _ADDR_RE.sub("", repr(obj))
        lines.append(f"### `{name}` = `{rendered}`\n")
    return lines


# static per-page preamble rendered between the title and the module
# listings (deterministic text; the introspected API follows it)
PAGE_PROLOGUE = {
    "resilience": """\
Survive preemption, corruption, and numerical blow-ups: validated atomic
checkpointing, deterministic fault injection, and anomaly-aware step
skipping.  Every recovery path below is exercised by tier-1 tests
(`tests/test_resilience.py`), including a full kill → corrupt → restart →
bit-identical-resume cycle.

## Checkpoint format

One directory per step, written to a temp name and atomically
`os.replace`-renamed into place (a kill at any byte offset leaves either
the old checkpoint set or a complete new one):

```
<root>/step_0000000042/manifest.json   # format_version, step, per-leaf records
<root>/step_0000000042/data.bin        # concatenated raw little-endian bytes
```

`manifest.json` records `(path, shape, dtype, offset, nbytes, crc32)` for
every leaf — leaves are addressed by `jax.tree_util.keystr` path, so any
mix of dicts / NamedTuples (`AdamState`, `LossScalerState`) / typed PRNG
keys round-trips without custom serializers, and a checkpoint can be
audited with nothing but the manifest and `np.frombuffer`.  Keep-last-K
rotation runs only after the new checkpoint is durable.

## Recovery semantics

`restore_checkpoint(root, like)` walks checkpoints newest-first,
validates each candidate (manifest parse, payload size vs. manifest —
truncation; per-leaf CRC — bit corruption; shape/dtype vs. the `like`
template — structure drift) and loads the newest one that proves good,
emitting a `checkpoint_rejected` event for each one skipped.  Validation
happens *before* any training state is touched; a corrupt latest costs
one checkpoint interval, never the run.  `CheckpointError` is raised only
when nothing valid remains.

## Fault injection

`FaultInjector(FaultPlan(seed, nan_grad_steps, inf_grad_steps,
preempt_steps))` drives all three production fault classes
deterministically: jit-safe NaN/Inf gradient injection at chosen steps
(`inject_grads`), a simulated SIGTERM at the host step boundary
(`check_preemption` raising `SimulatedPreemption`), and on-disk
checkpoint damage (`corrupt_checkpoint` / `truncate_checkpoint`).  The
same seed produces the same faults on every run — recovery paths are
tested, not discovered.

## Anomaly-aware stepping

`make_guarded_step(loss_fn, optimizer, scaler)` builds a jit-safe train
step that localizes non-finite gradients per leaf (`nonfinite_counts` /
`nonfinite_report`), applies the capturable skip, and tracks consecutive
skips in `GuardState`; after `GuardConfig.patience` consecutive skips it
halves the dynamic loss-scale floor (continuing below the configured
`min_loss_scale`) and emits a structured `loss_scale_floor_halved` event
instead of silently looping.

## Step watchdog and heartbeat

`StepWatchdog(deadline_s)` puts a monotonic-clock deadline on every
step: `arm(i)` / `disarm()` bracket the step (or `with watchdog.step(i)`),
and `disarm` raises `StepDeadlineExceeded` when the step finished late —
deadline violations are control flow, not log lines.  `start()` adds a
monitor thread that notices a stall *mid-step* and dumps structured
diagnostics (step, heartbeat age, pipeline-timer snapshot, live-array
count) via a `watchdog_stall` event while the step is still stuck.
`beat(step, ckpt_path=...)` atomically rewrites a small JSON heartbeat
file (step, wall/monotonic time, newest checkpoint path) that external
orchestrators watch: mtime stopped advancing is the universal liveness
probe, and the recorded checkpoint path tells the restart where to
resume — it is sticky, so beats on steps that did not save re-publish
the newest path instead of erasing it.

## Transient-failure retry

`retry_transient(fn, policy=RetryPolicy(...))` is the one retry path for
host-side I/O (checkpoint save/restore, data fetch).  Only exceptions the
policy *classifies* as transient (by type — `OSError` family — or by a
status-code-anchored message marker) are retried, with exponential
backoff and **deterministic** jitter derived from `(seed, what, attempt)`
— the same call site produces the same schedule on every run, while
differently-seeded hosts de-synchronize their retry storms.  Every
attempt emits a `retry_attempt` event; recovery emits `retry_recovered`;
exhaustion raises `RetryExhausted` chaining the last error.
`CheckpointManager(root, retry=RetryPolicy(...))` wires it under
save/restore (a deterministic `CheckpointError` is never retried — the
newest-valid fallback walk handles that class).

## Data-pipeline guard

`GuardedIterator(it, spec=spec_of(batch))` validates every batch against
a spec (tree structure, per-leaf shape/dtype, finiteness of floating
leaves) on the host side of the pipeline.  Corrupt batches are dropped
with a `batch_skipped` event naming the offending leaf, up to a lifetime
`skip_budget` — beyond it `SkipBudgetExceeded` is raised, because a
systematically bad pipeline must not degrade into silently training on a
fraction of the data.  A fetch slower than `stall_timeout_s` raises
`DataStallError`.

## Escalation and graceful degradation

`TrainingSupervisor(manager, SupervisorConfig(...))` ties the layer
together: `run(step_fn, state, batches, num_steps=...)` retries
transient fetch failures, brackets every step with the watchdog, writes
heartbeat + periodic validated checkpoints, and counts *unrecovered*
failures (deadline blown, retry exhausted, skip budget exceeded, data
stall).  At `max_consecutive_failures` it degrades gracefully: write an
emergency checkpoint through the validated atomic machinery, prove it
good, record it in the heartbeat, and raise `TrainingAborted` — the run
dies clean and resumable instead of wedged.  Deterministic fault
injectors (`SlowStep`, `FlakyIterator`, `CorruptBatch`) drive every one
of these paths under tier-1 on CPU, including a full
flaky-fetch + corrupt-batch + slow-step → abort → bit-identical-resume
acceptance run.

## Elastic restart (sharded checkpoints, manifest v2)

A v1 checkpoint is one whole-tree byte stream and can only restore onto
the mesh shape that wrote it (mismatched-mesh restore of a v1 file
raises `CheckpointError` — every manifest now stamps the saving mesh's
shape and dp/tp/pp world sizes, so the guard is exact).  A *sharded*
checkpoint (`save_sharded_checkpoint` / `ShardedCheckpointManager`,
`format_version: 2`) is mesh-shape-agnostic: each leaf is cut into the
shard grid its `PartitionSpec` implies, and every shard gets its own
manifest record:

```
<root>/step_0000000042/manifest.json
  format_version: 2, sharded: true, step, data_nbytes,
  mesh: {axes: {dp: 4, pp: 1, tp: 2}, axis_names, world, dp, tp, pp},
  leaves: [{path, shape,            # GLOBAL shape
            dtype, prng_key, spec,  # per-dim partitioning axis names
            shards: [{coords,       # {axis: coordinate} on the saving mesh
                      index,        # [[start, stop], ...] per array dim
                      offset, nbytes, crc32}, ...]}, ...]
<root>/step_0000000042/data.bin     # concatenated shard bytes
```

Restore (`restore_sharded_checkpoint(root, like)`) reassembles each
global leaf shard-by-shard (seek + read + per-shard CRC, placed by the
recorded `index`) and re-shards it onto the **template's** sharding —
which may live on a completely different mesh shape.  Saving on
`(dp=4, tp=2)` and resuming on `(dp=2, tp=4)` or `dp=8` is bit-identical
by construction: the bytes never pass through arithmetic.  One flipped
byte (`CorruptShardFile`) is localized to one shard of one leaf by its
CRC, and the newest-valid fallback walk skips the damaged step with a
`checkpoint_rejected` event.  A root may mix v1 and v2 directories; dim
sizes must divide evenly by their partitioning axes (uneven/padded
shards have no stable byte layout to reshard from).

## Asynchronous checkpointing

`SupervisorConfig(async_save=True)` (default **off** — the synchronous
path stays the escape hatch and the bit-identical reference) takes the
periodic save off the training hot path.  The save splits into two
phases with an honest cost model:

- **Snapshot** (the only thing the step loop blocks on): ONE batched
  device→host copy into *owned* host buffers — donation-safe, so the
  next step may overwrite the live state immediately.  Cost ≈ a memcpy
  of the state (`apex_checkpoint_duration_seconds{op="snapshot"}`).
- **Write** (a background thread): the *existing* serialize / per-leaf
  CRC32 / manifest / atomic-rename / rotation machinery — v1
  `CheckpointManager` and v2 `ShardedCheckpointManager` both — streamed
  into a `tmp_*` dir with incremental fsync.  Cost ≈ serialize + CRC +
  disk bandwidth (`{op="write"}`), paid off the step loop.  The bytes
  on disk are **identical** to a synchronous save (both modes share one
  writer function; tier-1 compares the files), so restore is
  bit-identical too.

Join rules (`AsyncCheckpointer`; all pinned by tier-1):

- **At most one write in flight.**  Backpressure blocks the *next*
  `save()` — which joins the previous write first, counted in
  `apex_checkpoint_backpressure_total` — never the step itself.
- **A failed write surfaces at the next step boundary** (the
  supervisor polls the `SaveFuture` each step) and joins the same
  retry/escalation ladder as a synchronous save failure; an
  unharvested failure re-raises on the next `save()`.
- **Emergency checkpoint and shutdown JOIN the in-flight write first**:
  the escalation path never races the background writer for the
  single-writer root, and a run never exits abandoning a nearly
  committed checkpoint.
- **A failed consistency pass vetoes the in-flight commit**
  (`AsyncCheckpointer.veto`): the write aborts at its commit gate,
  *before* the atomic rename (`SaveVetoed`, temp dir cleaned).  The
  veto is honored up to the gate — a write already past it lands,
  which is exactly what synchronous mode would have committed at the
  previous boundary; untrusted-state protection for every NEW commit
  comes from the supervisor's sticky trust flag in both modes.
- **Crash-consistency is unchanged**: a writer killed mid-write leaves
  only a `tmp_*` dir that `latest_valid_step` / the restore walk can
  never select (`CrashCheckpointWriter` drives this in tier-1);
  rotation counts only committed dirs and never touches the step an
  in-flight writer is producing.

`bench.py`'s `ckpt_async` block measures the split: at the 64 MB bench
budget the step-loop blocking time per save drops from the full
serialize+fsync wall time to the snapshot alone (≥5x reduction
measured), with byte-identical files.

## Cross-replica consistency

Data-parallel replicas are supposed to be bit-identical; at pod scale
the invariant silently breaks (HBM bit flips, a stale host update), and
every later all-reduce averages the corruption into the whole pod.  The
checkable representation is *stacked* per-replica state — each leaf
carries a leading replica axis sharded over `dp` (`expand_replicas` /
`collapse_replicas` convert to and from the logical single-copy form,
which is what elastic checkpoints persist).  `verify_replicas` hashes
every leaf per replica inside `shard_map` (only a u32 digest and an f32
delta per (leaf, replica) cross the wire) and localizes each diverged
leaf — keystr path, diverged ranks, max-abs delta — via structured
`replica_desync` events; `resync_replicas` repairs in place by
re-broadcasting rank 0's copy.  `ReplicaConsistency` packages
verify → resync → re-verify as the policy object
`TrainingSupervisor(..., consistency=...,
SupervisorConfig(consistency_check_interval=K))` runs every K steps,
*before* the periodic checkpoint commit (a desynced state is never
persisted); an unrepairable desync (`ReplicaDesyncError`) counts as one
unrecovered failure in the same escalation ladder as every other fault.
""",
    "serving": """\
Serve a trained Llama from its resilience checkpoints: slotted KV-cached
incremental decode plus continuous batching, with a *bounded* set of
compiled device programs after warmup — one prefill program per bucket
in a small power-of-two table, one batched decode step.  Every path
below runs under tier-1 on CPU (`tests/test_serving.py`), including the
bit-parity acceptance runs.

## Cache layout

The decode cache is **preallocated** and slot-indexed:

```
k, v:     [layers, slots, max_len, kv_heads, head_dim]
lengths:  [slots]  int32   # valid tokens per slot; 0 = free
```

One slot per in-flight request.  Prefill writes a (padded) prompt chunk
at the slot's current depth with one per-row scatter (`mode="drop"`:
bucket padding overhanging the cache end is dropped, never clamped
backward onto cached tokens); each decode step appends one token per
slot at that slot's own depth (one more such scatter on the whole
buffer, in place on the donated cache: per-slot positions drift apart
freely under continuous batching without changing any shape, and a
position outside `[0, max_len)` is dropped).  Attention always
reads the full `max_len` axis under a per-row visibility bound whose
masked scores sit at the flash kernels' exact `-1e30`:
`exp(masked - max)` underflows to exactly `0.0`, so the fixed-extent
softmax of a float32 cache is *bit-identical* to a same-extent uncached
forward — masking is correctness, not approximation.  The read takes
the cache **as it is stored** (`serving.kv_cache.cached_attention`,
behind the two calls a model's attention makes, `decode_attend` and
`prefill_attend`, whatever the layout and storage format): query
heads are grouped over their KV head (`[slots, kv_heads, rep * rows,
head_dim]` against the `[slots, max_len, kv_heads, head_dim]` buffer,
both contractions batched over `(slot, kv_head)`), K/V are never
repeated to the query-head count nor upcast, the operands keep the
cache's dtype and only the accumulation, the mask and the softmax are
float32 — for a bf16 cache the flash kernel's arithmetic, the
precision the model is trained under.  No buffer of the size of an
expanded or float32 cache view exists in any program
(`tests/test_serving.py` walks the decode and prefill programs for
one).  **The decode step's read has two implementations, and
`decode_attend` chooses by what it is handed** (the `kernel_dispatch`
event `cached_decode_attention` says which, `pallas` or `reference`):
on a TPU backend a dense float cache in the query's dtype with a head
width of whole 128-lane tiles and a `max_len` of whole blocks is read
**in place** by one Pallas kernel (`ops.cached_decode_attention`) that
takes the stored `[layers, slots, max_len, kv_heads, head_dim]` buffers
whole with the layer index and each lane's bound as scalars, walks a
lane's rows in blocks and stops at its last live block - no layer slab
is cut out, nothing is fetched or multiplied past the bound, same
operand and accumulation dtypes, sums taken blockwise (close to the
reference, not bit-equal: `tests/test_decode_read_kernel.py`);
everything else - a CPU backend, int8 rows, a block table, a 64-wide
head, and every prefill chunk and verify - goes through
`cached_attention`.  Bytes past `lengths` (chunk
padding, evicted streams) are garbage by contract and unreadable by
construction.

## Paged KV cache (block pool + block tables)

`DecodeEngine(..., paged=PagedCacheConfig(block_size=16,
num_blocks=None))` swaps the dense per-slot buffer for a **global
block pool** with per-slot **block tables**:

```
k, v:     [layers, num_blocks, block_size, kv_heads, head_dim]
tables:   [slots, ceil(max_len / block_size)]  int32  # pool block ids
lengths:  [slots]  int32
```

Block 0 is the reserved **null block** (finite zeros, never allocated):
free and unallocated table entries point there, so a gather through any
table state reads finite bytes — masked reads must never meet NaN,
because `0 * NaN` would poison the PV matmul where masked probabilities
are exact zeros.  Memory now scales with **used tokens**: a slot
holding 40 tokens pins `ceil(40/16)` blocks, not `max_len` rows, so at
a fixed byte budget several times more concurrent streams fit than the
dense layout admits (the `serving_paged` bench block pins ≥ 4×), and
admission prices **blocks**, with block-granular backpressure.  The
scheduler's gate prices each stream's **worst-case footprint** —
`ceil((prompt + max_new_tokens − 1) / block_size)` blocks, the same
bound `submit()` validates — minus what the stream already owns, and
holds the next request back until free + cache-evictable blocks cover
it (evictability counted pessimistically: a cached block still shared
by a live slot's table frees nothing when evicted).  Pricing prompts
alone would admit streams whose *decode growth* later exhausts the
pool — an uncatchable mid-run crash, not backpressure.  Direct engine
users without the gate get the loud failure mode: `BlockPoolExhausted`
raises — never clamps — after a last-resort prefix-cache reclaim
pass.

**Table semantics.** The host `PagedCacheManager` owns allocation,
per-block refcounts, and the table mirror; the device `tables` array is
a snapshot flushed (one small transfer) only on steps whose allocation
changed — a decode step inside a block crosses no boundary and flushes
nothing.  Writes go through drop-safe scatters: a row whose table entry
is the null block (bucket padding past the allocated frontier), whose
position is `-1` (an inactive decode lane — the dense cache parks those
writes in the lane's own masked rows; a paged table has no private
scratch, so they are dropped), or `>= max_len` redirects out of pool
range and is dropped.  Unlike the dense cache, padding is never written
at all — no stale table can route a garbage row into another stream's
live block.

**Aliasing and copy-on-write.** Every user of a block holds one
refcount: the owning slot, each aliasing slot, each prefix-cache entry.
A prefix hit **aliases**: `DecodeEngine.alias_prefix` appends the
shared block ids to the fresh slot's table — zero device reads, zero
K/V copies, zero compiled programs (the whole
`read_region`/`restore_prefix` capture/restore dispatch family
disappears; on a paged engine those methods *raise*).
`DecodeEngine.fork_slot` shares a live stream's whole table the same
way (the parallel-sampling branch point).  Any **write** into a block
whose refcount exceeds one triggers **copy-on-write**: the writer gets
a private copy (one compiled block-copy program, run before the write
lands) and the sharers keep the original bytes — streams sharing a
tail block stay bit-isolated both ways.  A block returns to the pool
only when its last reference drops.

**The exactness argument for gather-based reads.** Attention reads a
slot's K/V as the fixed-extent gather
`pool[table[slot]] → [max_len, kv_heads, head_dim]` — one static shape
for every slot state.  Valid rows hold bit-for-bit the values the dense
cache holds at the same positions (same writes, routed); rows past the
committed length — whatever blocks they land in — are masked at the
same exact `-1e30`, carrying exactly zero weight; and the reduction
extents are identical to the dense read.  Same values, same extents,
same op sequence ⇒ **bit-identical logits**: tier-1
(`tests/test_serving_paged.py`) pins paged greedy streams f32-exact
against the dense engine *and* the uncached shape-stable forward,
across prefill, decode, speculation, and prefix hits.  The dense
layout stays available (the `paged=None` default) so every guarantee
remains provable side by side.

## What a model keeps a slot: other state than K/V rows

The engine builds its cache from what the model declares, sublayer by
sublayer (`model.cache_layers()`), through one `init_cache`, and a model's
layers reach it through the seam's functions and name no cache class:

| declaration | what a slot keeps | the seam | served families |
|---|---|---|---|
| `KVRows(kv_heads, head_dim)` | `[max_len, kv_heads, head_dim]` K and V | `decode_attend` / `prefill_attend` (a chunk's read is one of three, chosen once a trace from what is in hand: on a TPU, a dense float cache in the queries' dtype with heads, key blocks and chunk in whole tiles is walked a visible block at a time by the Pallas kernel `ops.kv_chunk_attention` over the slot's rows cut head-major once a call, at any extent - a 2,048-row slot's chunks as a 32,768-row slot's; everything else - the CPU, int8 rows, a block table, 64-wide heads, a verify's odd row count - attends the whole masked extent while its float32 scores stay within 128 MiB - 32 heads x 512 x 2,048 - and past that walks the visible blocks in a loop; the `kernel_dispatch` event `kv_chunk_attention` says kernel or not, the `read_dispatch` event `blocked_walk` or `full_extent`) | `models.llama`, `models.nemotron_h` (`*` layers), `models.mellum` (`full_attention` layers) |
| `KVWindowRows(kv_heads, head_dim, window)` | a ring of `window` K and V rows in whole 16-row tiles, position `p` at row `p mod rows`, whatever `max_len` | `window_decode_attend` (append at `position mod rows`, then the in-place decode kernel on the ring buffers where the ring is exactly the window, else the ring under a mask) / `window_prefill_attend` (the `window - 1` rows before the chunk from the ring, the chunk's own from the ones in hand, then the chunk's last real rows into the ring) | `models.mellum` (`sliding_attention` layers) |
| `RecurrentRows(ssm, conv)` | a float32 state and a convolution tail of fixed size | `slot_state` / `write_slot_state` / `write_lane_state` | `models.nemotron_h` (`M` layers) |
| `LatentRows(width, index_width, top_k)` | `[max_len, width]` latent rows (the compressed K/V and the shared rope key, stored in whole lane tiles) and `[max_len, index_width]` selector keys | `latent_decode_attend` (append, score the live rows' keys, `top_k`, gather, absorbed read) / `latent_prefill_attend` (chunk-write, blocked scores, the selection as a mask, blocked explicit read: on a TPU one Pallas kernel, `ops.latent_chunk_attention`, over the rows in place with a block's scores in fast memory, elsewhere a loop; the `kernel_dispatch` event `latent_chunk_attention` says which) | `models.dots3` (`full_attention` layers) |
| `RingRows(width, window)` | a ring of `window` rows in whole 16-row tiles, position `p` at row `p mod rows`, whatever `max_len` | `ring_decode_attend` / `ring_prefill_attend` | `models.dots3` (`sliding_attention` layers) |
| `CallCounters(names)` | int32 counts a decode step adds (`engine.moe_stats()`) | `add_counts` | both routed-expert layers (`transformer.moe.LatentMoE`, `GatedMoE`, the latter sigmoid- or softmax-routed) |

`models.dots3.Dots3NoteForCausalLM` (latent attention in every layer: a
learned top-`index_topk` key selector on the full layers, a
`sliding_window_size` window on the others, a gate a head; sigmoid-routed
gated experts beside a shared expert, the layer told which experts it
holds) serves through `DecodeEngine` and the scheduler at their defaults
up to the `max_len` asked for (32,768 in the benchmark's cell): one decode
program, one prefill program a bucket.  A decode step touches, in
proportion to `max_len`, the selector's keys and one float32 score a row
only; a chunk walks blocks of rows up to its own end.  `engine.decode`'s
span carries `index_rows`, `attended_rows` and `window_rows` (host counts;
`engine.rows_read()` sums them), `engine.prefill_chunk`'s its `offset`.

`models.mellum.MellumForCausalLM` (grouped-query attention in every layer:
a `sliding_window` of K/V rows under plain rope on the `sliding_attention`
layers, every row under YaRN-scaled rope on the `full_attention` ones;
softmax-routed gated experts without a shared expert, every expert held
or a share of them) keeps both kinds of rows in ONE cache
(`WindowKVCache`: `[max_len, kv_heads, head_dim]` rows for the full layers,
rings for the others, call counters): at 16 slots x 32,768 rows the
benchmark's 6 window layers keep 0.20 GB where full extents would be 6.44.
A decode step reads each full layer up to the lane's length and each
window layer's ring, both through `ops.cached_decode_attention`; a chunk
walks a full layer's visible blocks and reads a window layer's ring once.
`engine.decode`'s span carries `kv_tokens` (a full layer's rows) and
`window_rows` / `window_live_rows` (what the window layers read, and what
a read at full extent would have walked).

Everything that pages, shards, quantizes, copies, shares or rolls back
K/V rows knows nothing of the other declarations and is **refused by
name** for a model that has any (`engine.other_state` lists them): `paged=`,
`tp=`, `QuantConfig(kv=True)` at the engine's construction;
`speculation=`, `prefix_caching=` and `policy=` with preemption at the
scheduler's; `capture_slot`, `read_region`, `restore_prefix`, `fork_slot`
and `verify_draft` by the method.  The message names the option and the
declarations (ROADMAP Queue R says what lifting each would take).

## The prefill bucket table

`DecodeEngine(prefill_len=..., prefill_buckets=None)` derives a
power-of-two chunk-size table (`default_prefill_buckets`: 16, 32, …,
`prefill_len`; pass an explicit ascending tuple to override).  A prompt
chunk is padded to the *smallest covering bucket*, so a 20-token prompt
rides a 32-row dispatch instead of a `prefill_len`-row one — and the
number of compiled prefill programs is bounded by `len(buckets)`
(logarithmic in `prefill_len`), exposed as
`DecodeEngine.prefill_compiles()` and **asserted** by tier-1 and the
bench regression guard, not hoped.  Which bucket a prompt lands in
never changes a bit of its logits (see below).

## Chunked cached prefill (prompts past `prefill_len`)

A prompt longer than `prefill_len` (up to cache capacity `max_len`) is
split into `prefill_len`-sized chunks plus a bucketed tail.  Each
chunk's causal block attends the **whole masked cache** — its own rows
under `idx <= offset + row`, plus every previously cached token —
through the same fixed-`max_len`-extent attention the decode step uses,
then writes its K/V at the slot's offset.  Because every reduction runs
at the same static extent as the shape-stable uncached forward, chunked
prefill is **bit-identical** to prefilling in one shot *and* to the
uncached forward: chunk boundaries are scheduling, not numerics
(tier-1 pins a 70-token prompt through a 16-token chunk engine,
bit-for-bit, prefill and the whole greedy decode stream).

Cost model, stated honestly: a chunk's attention reads the **full
`max_len` cache axis** (that fixed extent *is* the bit-exactness and
no-recompile mechanism, shared with decode), so per-chunk attention is
`O(bucket x max_len)` where the old single-program prefill paid
`O(prefill_len^2)` causal.  What it moves: the slot's stored K/V rows
once (`max_len x kv_heads x head_dim` in the cache's dtype — not
repeated per query head, not upcast) and the `[heads, bucket, max_len]`
float32 scores.  The projections/MLP/LM-head — the dominant
cost at transformer widths — scale with the *bucket*, which is what
bucketing shrinks.  At `max_len >> prefill_len` the attention term
grows; a length-bucketed cache *read* window would recover it but
changes reduction extents (= forfeits bit-exactness vs the
shape-stable forward) and multiplies the compile table — deliberately
out of scope here.

## Slot lifecycle and the prefill budget

`QUEUED → PREFILL → DECODE → DONE`.  The scheduler admits queued
requests into free slots at each step boundary (FIFO — a request's wait
is bounded by the streams ahead of it, so no starvation), spends at
most `prefill_budget` prompt tokens on prefill chunks (oldest admitted
request first; default = `engine.prefill_len`, one full-size chunk),
runs one shared batched decode step for every decoding slot, and
evicts on EOS or `max_new_tokens` with **O(1)** slot release (zero the
length, reuse immediately; the next prefill overwrites).  The budget is
the head-of-line-blocking knob: a long admission advances chunk-by-chunk
*between* decode steps instead of stalling live streams for its whole
prefill, and the deferred remainder is exported as the
`apex_serving_prefill_backlog` gauge.  Admission, eviction, and
sampling bookkeeping are host-side work at step boundaries — the device
only ever sees the compiled programs, and the decode step compiles
**exactly once** (asserted via `utils.compat.compile_count` in tier-1:
no per-request retraces, the recompile tax the slotted cache exists to
eliminate).

## The step's contract: enqueue all, read once (decode-ahead)

A step enqueues **all** of its device work and then waits for the
device **once**, at its end, for what the device finished *before* this
step's decode:

1. admit (no device read: a request's PRNG key words are made on the
   host, `request_key_bits`, the same bits as `PRNGKey(seed)`);
2. prefill chunks; a completed prompt's first token is sampled on the
   device and **stays there** (`DecodeEngine.keep_sampled` writes it into
   the engine's `[slots] int32` vector of last sampled tokens), and the
   lane joins this step's decode;
3. the shared decode step for every decoding lane whose *issued* count
   (tokens whose computation has been enqueued) is below
   `max_new_tokens`.  The program reads each lane's input token from the
   kept vector, or from the host's vector for a lane whose newest token
   only the host has (`engine.decode(tokens, active, on_device=mask)`;
   one compiled program either way).  The sampler's result replaces the
   active lanes of the kept vector; keys and indices follow the issued
   count, so sampled streams are bit-identical to a serial scheduler's;
4. **one read** (`serving.readback`): this step's first tokens and the
   *previous* step's decoded tokens.  The host blocks at most until this
   step's prefill is done; the decode is queued behind it, so the device
   stays busy while the host appends tokens, finishes requests,
   publishes, and enqueues the next step.

What a caller can observe: **a decoded token is delivered one step after
the step that computed it**, and a request is reported finished by the
step that reads its last token (one step later than a serial scheduler
would; `ttft_s` is stamped in the same step as before).  A stream that
ends on EOS has one more lane in flight: its token is dropped when read
(the slot was released in order behind it) and counted in
`overlap_stats()["dropped_tokens"]`.  No token of any stream changes.

**Settle points.**  Anything that needs a stream's newest token or its
slot's rows on the host reads everything in flight first (one
`_settle(reason)`; `overlap_stats()["settled_early"]` counts them by
reason):

| where | why |
|---|---|
| `speculation=` configured: after the prefill budget and again after the decode, every step (`speculation`) | drafting reads each stream's token history on the host, so such a scheduler is the serial one |
| `policy=` preemption, before a victim is chosen (`preempt`) | the victim is captured with its newest token and its slot's rows; the read may finish it instead |
| `cancel` of an active request (`cancel`) | the partial output is every token that was computed; the one in flight may be the last, and then it is too late to cancel |
| `export_streams(capture=True)` (`export`) | a stream moves with its tokens and rows (`capture=False`, a killed replica, discards what is in flight unread) |
| `swap_weights` (`swap_weights`) | every token of the displaced weights is delivered before the buffer goes back to the caller |
| `close` (`close`), and the end of `run()` (`drain`) | what is left belongs to streams that ended: read and dropped |

`adopt_stream` settles nothing: the adopted stream's newest token is
the host's, and its lane is host-fed beside the lanes the device feeds.
`overlap_stats()` returns `steps` (steps that enqueued a decode),
`steps_ahead` (of them, enqueued while the previous step's tokens were
still unread), `settled_early` and `dropped_tokens`; the
`serving.decode` span carries `ahead` and `serving.readback` carries
`lag` (1 when a newer decode had been enqueued before this one's tokens
were read).

## Speculative decoding (exact-greedy prompt lookup)

Plain decode pays one full weight read and one full-`max_len`-extent
cache read **per token per step** — the dominant cost of the decode
phase.  `ContinuousBatchingScheduler(...,
speculation=SpeculationConfig(...))` amortizes that dispatch over
several tokens without changing a single emitted bit:

- **Drafting** (`serving.draft.propose`) is *prompt lookup*: the
  longest suffix (n-gram, `ngram_max` down to `ngram_min`) of the
  request's own prompt + generated history that re-occurred earlier
  predicts its continuation — up to k candidate tokens, purely host
  side, no draft model, zero device cost.  No match → empty proposal →
  the slot simply rides the plain batched decode step that round.
- **Verification** (`DecodeEngine.verify_draft`) scores the slot's
  pending token plus all k candidates in ONE cached multi-token
  forward — the chunked-prefill machinery, but keeping every row's
  logits instead of slicing the last.  Row i is **bit-identical** to
  the single-token decode logits at that depth (same masked
  fixed-extent reductions), so "does the target's argmax equal the
  drafted token" is an exact test, not a heuristic.  Acceptance and
  rollback run inside the same dispatch: the slot's length commits to
  `offset + accepted + 1`, which makes every rejected row's K/V
  unreadable (the same O(1) length move as eviction) — the emitted
  stream `draft[:accepted] + [bonus]` is exactly what `accepted + 1`
  plain decode steps would have produced, bit for bit, including
  across mid-stream rejections (tier-1:
  `tests/test_serving_spec.py`).
- **Bounded compiles**: drafts are padded to a small power-of-two
  `draft_buckets` table (`default_draft_buckets`; verify width =
  bucket + 1), so `verify_compiles() <= len(draft_buckets)` — the same
  asserted budget discipline as the prefill buckets.  The decode step
  still compiles exactly once; an engine that never verifies never
  compiles a verify program.
- **Adaptive draft length** (`serving.draft.adapt_k`): full acceptance
  doubles the next draft (up to `max_draft`), any rejection halves it
  (down to `min_draft`) — per request, deterministic, so
  incompressible streams stop paying for wide verifies within a couple
  of steps.  A rejected verify still emits one true token (the bonus
  row *is* the plain decode output), so the speculative path never
  emits fewer tokens per dispatch than plain decode.
- **The escape hatch is byte-for-byte**: sampled (`temperature > 0`)
  requests never enter the drafting path — same token stream, same
  event and metric sequence, zero verify compiles, with speculation
  enabled or disabled (tier-1 pins the equality).

Honest accounting: a verify of width w costs ~w× the projections/MLP
FLOPs of a decode step plus the same fixed-extent attention read (the
K/V bytes of the slot's `max_len` rows once, in the cache's dtype,
whatever w is: the w rows join the grouped query block), so
the win is `(accepted + 1)` tokens per dispatch *minus* that wider
dispatch — large when traffic is repetitive (summarization, code edit,
RAG with quoted context, self-repeating generations), ≈ 1.0x when the
drafter never matches (the adversarial bar `bench.py serving_spec`
records).

## Cross-request prefix caching (shared prompts served once)

Production traffic is dominated by requests sharing long common
prefixes — system prompts, few-shot templates, chat history — yet a
plain scheduler re-runs full prefill over every admitted prompt.
Because chunked cached prefill is bit-identical at ANY split point
(above), a previously computed prefix's K/V can be reused *verbatim*
and prefill resumed mid-prompt with zero numerical cost.
`ContinuousBatchingScheduler(..., prefix_caching=PrefixCacheConfig())`
turns this on (default off: every existing path stays byte-for-byte
untouched — same tokens, same event/metric sequences, same compile
counts).

- **Block hashing** (`serving.prefix_cache`): a prompt is hashed as a
  chain of fixed-size token blocks (`block_size`, default = the
  engine's smallest prefill bucket); each entry's key is
  `H(parent_hash, block_tokens)`, so equal hashes mean an equal WHOLE
  prefix — position is encoded by the chain, and there are no false
  hits.  Admission takes the longest matching chain, capped at
  `len(prompt) - 1` tokens: the final prompt token is always
  recomputed, because the resume chunk must produce the next-token
  logits the first sampled token comes from.
- **Hits are zero-copy on a paged engine.**  With
  `paged=PagedCacheConfig(...)` the cache entry for a block records
  the **pool block id** the prompt's K/V already lives in (capture is
  by reference: `DecodeEngine.slot_block_ids` plus one allocator
  refcount per entry — zero device reads, zero copies, pure host
  hashing), and a hit **aliases**: `DecodeEngine.alias_prefix` appends
  the shared ids to the fresh slot's table.  No K/V bytes move in
  either direction and no compiled program runs — the copy-based
  capture/restore dispatch cost below simply does not exist.  The
  slot's later writes into a shared block copy-on-write first, so the
  cached bytes are immutable while any entry references them.
- **Capture on a dense engine** is deterministic and insert-on-miss:
  immediately after the prefill chunk that completes a block, the
  scheduler snapshots exactly the rows prefill wrote
  (`DecodeEngine.read_region` — a fixed-extent gather into owned
  buffers; one dispatch covers all of a chunk's new blocks, which
  share one *span* buffer and slice out of it lazily on the hit path).
- **Restore on a dense engine** (`DecodeEngine.restore_prefix`) writes
  the matched chain back through the same per-row `mode="drop"`
  scatter prefill uses (`kv_cache.write_slot_region`) in bucket-padded
  chunks — restore compiles are bounded by the prefill bucket table
  (`restore_compiles()`).  Either way, `prefill(slot, tokens,
  resume=n)` resumes the prompt over the reused state (the
  offset-prefill rejection is lifted ONLY for engine-verified
  restored/aliased slots).
- **The exactness argument**: the entry's bytes ARE prefill's output
  for that exact token prefix — snapshotted and written back
  bit-for-bit on the dense path, or *the very same physical block*
  read through the table gather on the paged path — and the resumed
  chunk reads the whole masked cache through the same fixed-extent,
  grouped, stored-dtype attention read as always.  Nothing in the
  pipeline rounds differently, re-orders, or
  approximates — so a hit changes *nothing*: logits, tokens, and
  greedy streams are bit-identical to the cold path (tier-1 pins the
  full trajectory, `tests/test_serving_prefix.py` dense,
  `tests/test_serving_paged.py` paged).
- **Eviction and memory accounting**: LRU under a configurable
  `max_tokens` budget, leaf-first along chains (a parent with live
  children is never evicted, so every cached chain stays reachable —
  no orphaned entries leaking budget; an insert whose parent is gone
  is refused).  Entries feeding a live slot are **ref-count pinned**:
  a request pins its matched + self-inserted chain until its prompt
  is fully cached, and a pinned entry is never evicted (the store may
  transiently exceed the budget instead).  `cached_tokens` is exact;
  `cached_bytes` reports live span buffers honestly — a span's bytes
  free only when its last block is evicted, so one surviving block
  can transiently pin up to a chunk's span.
- **Lifecycle**: a caching scheduler owns its `PrefixCache` for the
  engine's lifetime.  Before discarding one (e.g. building a fresh
  caching scheduler over the same engine), call
  `ContinuousBatchingScheduler.close()` — on a paged engine it derefs
  every cached pool block and unhooks the allocator's reclaim
  callback; an abandoned cache would pin its blocks forever and leave
  the allocator reclaiming into a dead store.

Telemetry: `serving_prefix_hit` / `serving_prefix_miss` events at
admission (hits carry `saved_tokens` + restore/alias wall time),
feeding `apex_serving_prefix_{hit,miss}_total` and the
`apex_serving_prefix_saved_tokens` histogram, plus the
`apex_serving_prefix_cached_tokens` gauge refreshed each scheduler
step while caching is enabled.  A paged engine adds
`serving_block_alias` (per hit; feeds
`apex_serving_block_alias_hits_total`) and `serving_block_cow` (per
copy-on-write pass; feeds `apex_serving_block_cow_total`) events, and
the `apex_serving_block_pool_utilization` gauge.  `bench.py`'s
`serving_prefix` block measures 8 requests sharing a long system
prompt — warm-cache admissions ≥ 2× the cold pass on aggregate prefill
tokens/s, and no regression on a zero-overlap workload *within the
harness's own measured noise floor* (dense capture is copy-based, so
its true cost is real but sub-noise — ~0.5–1% of a prefill-only drain
at bench scale; a regression beyond the measured noise fails the bar),
streams asserted token-identical, restore compiles bounded; the
`serving_paged` block repeats the shared-prompt workload on a paged
engine, where hits alias instead of copy.

## Tensor-parallel serving (`tp=TPConfig(size=N)`)

`DecodeEngine(..., tp=TPConfig(size=N))` shards every serving program
over a 1-D `N`-chip mesh (`utils.compat.serving_mesh`); the default
`tp=None` leaves the single-chip engine byte-for-byte untouched (the
tier-1 identity test pins the event stream and metric snapshot).  The
wiring is deliberately thin — the *same* program bodies, wrapped in
`shard_map` inside the same donating `jax.jit`:

- **Params** lay out with the training stack's Megatron column/row
  split (`models.llama.tp_param_spec`): q/k/v/gate/up kernels are
  column-split `P(None, "tp")`, o/down kernels row-split
  `P("tp", None)`, the vocab-parallel embedding and LM head
  `P("tp", None)`; norms replicate.  The `tensor_parallel` layers probe
  the mapped axis via `tp_world_size("tp")` — bound inside the
  shard_map they shard automatically, so the model needs no
  serving-specific branches.
- **KV cache** shards head-wise: dense
  `[layers, slots, max_len, kv_heads/tp, head_dim]` and the paged block
  pool `[layers, blocks, block_size, kv_heads/tp, head_dim]` alike
  (each rank attends its own kv-head group locally — attention needs
  no collective).  Slot lengths and block tables replicate: every rank
  must mask and route identically, and the host mirrors flush to a
  replicated `NamedSharding` so placement never forks an extra
  compiled variant.
- **Collective cost model**: one psum pair per layer (after the
  attention's row-parallel o_proj and the MLP's down_proj) plus one
  psum in the vocab-parallel embedding — exactly the training
  forward's collectives, `2L + 1` allreduces of `[tokens, hidden]` per
  dispatch.  At decode (1 token/slot) the payload is tiny and latency-
  bound: this is the new hot path the `apex_serving_collective_seconds`
  histogram watches, and the quantized-allreduce literature (EQuARX)
  is the compression playbook when it dominates.
- **Bit-exactness**: greedy token *streams* at tp=2 and tp=4 are
  asserted identical to the single-chip engine, and all cache-layout
  invariants (chunk splits, speculation, prefix restore, CoW
  isolation, preempt/resume) hold sharded.  Raw *logits* are
  argmax-tier (~1e-7 abs at test scale), not bit-equal: the
  row-parallel psum splits each contraction into `tp` partial sums, so
  floating-point reduction order genuinely differs — the documented
  deviation class, pinned by tolerance + exact-argmax assertions.
  Within one mesh width everything stays bit-exact: verify all_gathers
  the vocab shards before acceptance argmaxes, so rollback depths are
  rank-identical, and capture → restore → resume on the same tp engine
  reproduces the stream bit-for-bit.
- **Weights land on the mesh directly**:
  `weights.load_serving_params(..., shardings=
  engine.tp_param_shardings(params_like, mesh))` annotates the
  restore template so both the v1 and v2 loaders place every leaf via
  `leaf_from_numpy` onto its `NamedSharding` — a tp=8 server never
  materializes a host-replicated copy of a model that only fits
  sharded.

## Quantized serving (`quant=QuantConfig(...)`)

`DecodeEngine(..., quant=QuantConfig(weights=True, kv=True,
allreduce=False))` turns on int8 serving leg by leg; the default
`quant=None` leaves every path **byte-for-byte** untouched — same
token streams, same event/metric sequences, same compile counts
(tier-1 pins the identity).  All three legs use ONE int8 convention,
spelled exactly once in `apex_tpu.amp.quant`: symmetric, `scale =
amax / 127` fp32 per group, zero-amax groups take scale 1.0 (so
all-zero rows roundtrip to exact zeros, never NaN).

- **Weight int8** (`weights=True`): at engine construction (or ahead
  of time via `load_serving_params(..., quantize=True)` /
  `serving.quant.quantize_params`) the seven projection kernels and
  the LM head become `QTensor` leaves — int8 payload + one fp32 scale
  per **output channel** (reduce axis 0 for `[in, out]` kernels, axis
  1 for the `[vocab, hidden]` tied head).  Embedding, norms, and
  biases stay high-precision: they are small, and norm numerics
  gate stability.  Dequantization happens *inside* the existing
  jitted program bodies (`dequant_params` at trace time), so the
  program-family budget is unchanged — same prefill bucket table, one
  decode program, `compile_count`-asserted.  ~4× less HBM per kernel
  read; the per-channel scale keeps greedy streams at agreement tier.
- **KV int8** (`kv=True`): the dense cache and the paged block pool
  store int8 payloads with one fp32 scale per cached **(position,
  kv-head)** (`QuantKVCache` / `QuantPagedKVCache`; scale pools are
  indexed by the same slot rows / pool block ids as the payload, so
  aliasing, CoW, fork, and release move payload and scales together
  *by construction*).  Every drop-safe-scatter / null-block /
  fixed-extent-gather invariant holds unchanged; unallocated rows
  dequantize to exact finite zeros (scales initialize to 1.0), so
  masked reads stay NaN-free.  Capture (`capture_slot` /
  `read_region`) returns **dequantized fp32** — the prefix cache,
  preemption, fleet failover, and every other host-side byte path stay
  quantization-oblivious — and restore requantizes in-program; because
  a group's amax element requantizes to exactly ±127, capture →
  restore reproduces the stored payload bit-for-bit.  The cache
  footprint drops from `2 · head_dim · 4` to `2 · (head_dim + 4)`
  bytes per (position, kv-head) — ≥ 1.8× more streams per GB at
  transformer head widths (3.84× at head_dim 96), the `serving_quant`
  bench bar.
- **Quantized tp allreduce** (`allreduce=True`, requires `tp=`): the
  per-layer psum pair (row-parallel o_proj + down_proj) runs as
  quantize → all_gather(int8 payload + per-group fp32 scales) →
  dequant-sum, EQuARX-style — the compression playbook for the
  latency-bound decode collective.  Scoped by construction to exactly
  those reduces (`override_forward_allreduce(...,
  kinds=("row_linear",))`): the vocab-parallel embedding psum and the
  logits path stay exact, so the argmax tier is disturbed as little
  as possible.  This is the one knowingly *lossy-per-step* leg and is
  off by default inside `QuantConfig`.

**Accuracy contract — agreement tier, not bit tier.**  Quantization
is a real rounding step, so the fp-exactness ladder above does not
apply; the pinned claim is **greedy token-stream agreement** against
the fp32 reference (`serving.quant.stream_agreement`, bench bar on a
pinned workload) plus bounded per-position logit error
(`serving.quant.max_logit_error`).  *Within* the quantized
configuration every structural guarantee still holds bit-for-bit:
chunked prefill ≡ one-shot, paged ≡ dense, speculation ≡ plain decode,
capture/restore ≡ uninterrupted — the same argument as fp32 (same
bytes, same extents, same op sequence), just over int8 bytes.
`serving.quant.evaluate_quant` packages the acceptance measurement and
emits `serving_quant_eval`, feeding the
`apex_serving_quant_agreement_ratio` gauge, the
`apex_serving_quant_logit_error` histogram, and the
`apex_serving_quant_bytes_per_token` gauge; engines log a one-shot
`serving_quant_enabled` config echo at boot.  `bench.py`'s
`serving_quant` block records decode ms/token fp32 vs int8, KV
bytes/token, streams-per-GB capacity ratio (bar ≥ 1.8×), greedy
agreement (bar ≥ 0.98), and the compile counts (zero tolerance on
regression, graded direction-aware by `tools/bench_compare.py`).

## Determinism guarantees

- **Prefill and greedy decode are bit-identical to the uncached
  model**: the acceptance tests decode 64+ tokens through the cache on
  a GQA config — after both one-shot and chunked prefill — and prove
  every step's f32 logits exactly equal to the shape-stable uncached
  forward (context padded to `max_len`), and the greedy stream
  identical to the unpadded forward.
- **Speculation is scheduling, not numerics**: greedy decode with
  drafting + multi-token verification emits the identical token stream
  — and identical f32 logits at every emitted position — as plain
  one-token decode, including across rejections/rollbacks and with
  neighbor slots mid-chunked-prefill (tier-1 pins the 40+-token run).
- **Chunk splits are invisible**: the same prompt through one-shot
  prefill, even chunks, or uneven manual chunks yields the same logits
  bit-for-bit.
- **Sampling is a pure function** of `(logits, key, temperature,
  top_k)`: per-request PRNG keys derive as
  `fold_in(PRNGKey(seed), token_index)`, the clock feeds telemetry
  only, and a replay with the same seeds reproduces every stream
  bit-for-bit regardless of arrival timing or slot assignment.
- **Streams are isolated**: evicting a neighbor slot, admitting a new
  request into it mid-flight, or prefilling a long prompt chunk-by-chunk
  next door does not move any other stream's logits by a single bit
  (tier-1 pins all three).

## Telemetry

Structured `emit_event` lines ride the `apex_tpu.events` logger:
`serving_request_queued` / `serving_request_admitted` (queue depth),
`serving_prefill_chunk` (bucket size, chunk tokens, dispatch wall
time — feeding the `apex_serving_prefill_duration_seconds{bucket}`
histogram), `serving_spec_verify` (drafted/accepted counts + dispatch
wall time — feeding the speculation counters and the
`apex_serving_spec_accepted_tokens` acceptance-length histogram),
`serving_first_token` (TTFT), `serving_request_finished`
(tokens/s, per-token latency, finish reason), `serving_prefix_hit` /
`serving_prefix_miss` (admission-time prefix-cache outcome; hits
carry `saved_tokens` + restore wall time), a periodic
`serving_step` sample (queue depth, active slots, prefill backlog,
mesh width), and — on a tensor-parallel engine only — a
`serving_tp_step` per decode dispatch (mesh width + wall time,
feeding the `apex_serving_tp_size` gauge and the
`apex_serving_collective_seconds` histogram; a `tp=None` engine emits
nothing new).
`bench.py` captures a `serving` block — prefill tokens/s, steady-state
decode ms/token, continuous-batching aggregate throughput at 1/4/8
concurrent streams with staggered arrivals (4 concurrent streams ≥ 2×
four sequential runs), and a mixed-prompt-length workload where
bucketed chunked prefill must beat the padded single-program baseline
by ≥ 1.5× with `prefill_compiles` ≤ the bucket count and
`decode_compiles == 1` (the compile-count regression guard) — and a
`serving_spec` block: best-of-N spec-vs-plain greedy decode tokens/s
on an acceptance-friendly repetitive workload (bar ≥ 1.8×) and on an
adversarial random-token workload (bar ≥ 1.0× — no regression), with
`verify_compiles` bounded by the draft bucket table and
`decode_compiles == 1` preserved — and a `serving_prefix` block:
cold-vs-warm prefix-cache admissions for 8 shared-prompt streams
(warm ≥ 2× cold on aggregate prefill tokens/s; no regression without
overlap, asserted against the harness's own measured noise
floor; streams token-identical; restore compiles bounded by
the prefill bucket table).

## The serving control plane (`serving.policy`)

`ContinuousBatchingScheduler(..., policy=SchedulingPolicy(...))` turns
arrival-order FIFO into policy.  Everything below is host-side
*selection* at step boundaries; the compiled-program set never grows
(preempt/resume rides the existing region-read / restore / alias
program families, asserted via `utils.compat.compile_count`), and a
scheduler **without** `policy=` is byte-for-byte the FIFO scheduler —
identical event stream, identical metric snapshot (tier-1 pins the
identity with policy-annotated requests through a policy-less
scheduler).

- **Priority classes** (`Request.priority`, higher wins): admission
  always serves the highest class with an admissible request; within a
  class, previously preempted streams resume first, then tenants by
  weighted round-robin, then FIFO.  Priority also orders the per-step
  prefill budget, so a high-priority first token never waits behind an
  earlier low-priority long prompt.
- **Lossless preemption** (`preemption=True`): when no slot is free, a
  queued request may evict a *strictly* lower-priority DECODE stream
  (equal classes never preempt each other — no thrash; mid-PREFILL
  streams are never victims).  The eviction is **lossless**, which
  almost no serving stack can claim, and the argument is mechanical:
  the victim's cache rows `[0, len)` are snapshotted verbatim (dense:
  `DecodeEngine.capture_slot`, bucket-decomposed region reads; paged:
  the slot's block ids gain a pool reference — zero bytes move), its
  host stream state (tokens, PRNG base key, draft length) is frozen,
  and resume writes the *same bytes* back
  (`restore_prefix` / `alias_prefix`).  Attention over identical cache
  bytes at identical reduction extents produces identical f32 logits,
  and the sampler keys by `(seed, token_index)` — which suspension
  never rewinds — so the resumed stream emits exactly the tokens the
  uninterrupted stream would have (tier-1 pins exact logits across the
  boundary).  A finished-after-preemption result reports
  `finish_reason="preempted-resumed"` and its cycle count.
- **Cancellation** (`scheduler.cancel(rid)`, works with or without a
  policy): removes a request wherever it lives — queued, active, or
  suspended — releasing its slot, paged blocks, and prefix-cache pins
  without disturbing neighbors (tier-1 pins neighbor bit-identity and
  the pin-release).  Partial output is kept
  (`finish_reason="cancelled"`); cancelling a finished request returns
  `False`, an unknown rid raises `KeyError`.
- **Deadline shedding** (`Request.deadline_s`, relative to
  submission; `deadline_shedding=True`): at every step boundary — so
  both at admission time and mid-queue — a queued (or suspended)
  request whose completion deadline has already passed is shed before
  it wastes prefill budget (`finish_reason="shed"`, zero/partial
  tokens).  Goodput accounting charges sheds and cancellations as
  misses everywhere (`SERVED_REASONS` in the loadgen,
  `build_report` in obs): finishing early by giving up is not
  goodput.
- **Tenant fairness** (`Request.tenant`): within a priority class,
  queued requests are drawn by smooth weighted round-robin
  (`tenant_weights` / `default_tenant_weight`; nginx-style smooth
  interleaving, deterministic, credits persist while a tenant is
  ineligible so starvation earns priority), and
  `max_inflight_per_tenant` caps one tenant's concurrently active
  streams so a burst cannot occupy every slot.
- **Progress guard**: `run()` derives a step bound from the queued
  work and raises `SchedulerStalled` (queue/active/suspended/backlog
  state in the message) instead of spinning forever on an engine bug.

Chaos drivers (`resilience.fault_injection`, wired through
`LoadGenerator(step_hook=...)`): `SlowDecodeStep` inflates chosen
steps on the virtual clock (latency/deadline pressure moves, token
streams must not), `StallStream` cancels a stream after N tokens (the
client that stopped reading), `CancelStorm` cancels a seed-chosen
subset at chosen steps (the gateway-restart burst).  The tier-1
acceptance run drives 2x-overload bursts with priorities + deadlines +
slow steps and asserts every survivor token-identical to its
unperturbed run, with high-priority p99 TTFT and goodput strictly
better than same-workload FIFO.  Control-plane activity rides
`apex_serving_{preempted,cancelled,shed}_total` and the per-tenant
`apex_serving_tenant_inflight` gauge.

## Open-loop load generation (`serving.loadgen`)

The bench's staggered streams are *closed-loop* (a new request submits
only when the driver is ready) — they measure drain rate, never
queueing.  Serving comparisons in the literature drive the system at a
controlled **offered load** instead; `serving.loadgen` is that driver,
deterministic end to end:

- **Arrival processes**: `uniform_arrivals(n, rate)`,
  `poisson_arrivals(n, rate, seed)` (seeded exponential gaps — the
  same seed is the same schedule, bit for bit), and
  `burst_arrivals(n, burst, period_s, spacing_s)` (burst trains, the
  workload SLO scheduling is graded by).
- **Prompt mixes**: `shared_prefix_prompts` (one system prompt + unique
  tails — the prefix-cache hit class), `zero_overlap_prompts` (its
  no-regression class), `mixed_length_prompts` (the bench's
  short-skewed `LENGTH_SKEW_FRACTIONS` recipe).
- **`OpenLoopWorkload`** zips requests + arrival offsets + per-request
  completion deadlines; `schedule_fingerprint()` digests the whole
  schedule (offsets, token ids, generation config) — equal
  fingerprints ⇒ identical token streams, the bit-reproducibility
  witness `bench.py serving_slo` asserts.
- **`LoadGenerator(scheduler, workload)`** submits each request the
  moment its offset comes due on the *scheduler's own clock*, sheds
  arrivals at `QueueFull` (open-loop: the arrival process never slows
  down for the system; shed requests are charged against goodput), and
  steps the scheduler until the workload drains.  With
  `clock=VirtualClock()` on the scheduler and `step_time_s=` on the
  generator the run is sleep-free and fully deterministic — every
  latency an exact multiple of the virtual step (the tier-1 timing
  tests).  A deadline-carrying run publishes
  `apex_serving_goodput_ratio`; without deadlines the metric stream is
  untouched.

Pair with `apex_tpu.obs.RequestTraceRecorder` (per-request lifecycle
records off the event stream) and `apex_tpu.obs.build_report`
(p50/p95/p99 TTFT / TPOT / queue-wait + goodput) — the measurement
layer the ROADMAP's SLO-aware-scheduling work is graded by.
`bench.py`'s `serving_slo` block drives a seeded bursty workload at
~1× and ~2× the measured sustainable rate and records p99 TTFT, TPOT
and goodput at both loads in `PERF_NOTES.md`.

## Hot weight reload & shadow/A-B (`serving.reload`)

A fleet that "serves while you train" cannot drain and restart every
engine each time training commits a checkpoint.  `serving.reload`
closes the loop — **default off**: a scheduler that never constructs
these objects is byte-for-byte the scheduler of the previous section
(identical event stream, identical metric snapshot, zero new
compiles — tier-1 pins it).

- **`WeightWatcher`** polls for newer *committed* steps from exactly
  one source: an in-process `AsyncCheckpointer`'s `last_committed`
  (set strictly after the atomic commit rename), a supervisor
  heartbeat file's `ckpt_path` pointer (the cross-process contract —
  written after commit, so the pointed-at step is always whole), or a
  raw root walk that skips steps the live-writer registry marks
  in flight (`resilience.checkpoint.in_flight_steps` — a re-save swaps
  the committed dir aside mid-commit, and selecting it would race the
  writer).  A refused candidate is re-offered every poll until
  repaired or superseded; the watcher never wedges on a bad step.
- **`HotReloader.reload()`** is restore → validate → swap,
  **double-buffered**: the candidate restores through the same
  validated path as boot (`load_serving_params` — v1 + v2 manifests,
  fused CRC, `shardings=` mesh-direct placement for tp engines,
  optional `RetryPolicy` on transient I/O) into a fresh buffer that
  never aliases the serving params.  Corrupt bytes, truncation, or a
  structure/shape/dtype mismatch against the served tree refuse the
  swap (`ok=False` + a `serving_reload_failed` event) with serving
  bit-exactly untouched.  The swap itself
  (`scheduler.swap_weights`) happens at a step boundary: in-flight
  streams keep their KV cache and sampler state and continue under
  the new weights — post-swap tokens are bit-identical to a fresh
  engine booted on the new weights and fed the same state — and the
  prefix cache is **version-bumped** so old-weights K/V can never
  resume a new-weights stream.  The same-spec contract means every
  compiled program family re-dispatches unchanged: a swap adds zero
  compiles.
- **`HotReloader.prefetch()`** (restore-ahead): stage the next
  candidate — restore + validate into a side buffer — at any time,
  off the serving path; the later step-boundary `reload()` whose
  target matches the staged step consumes the stage and pays only the
  pointer swap (~1 ms instead of a restore-dominated pause).  A stale
  stage (target moved on) is discarded and the full path runs; a
  failed prefetch stages nothing and is not a refusal — nothing was
  offered for serving.
- **`HotReloader.rollback()`**: the displaced buffer is retained (one
  previous version), and rollback swaps it back through the identical
  mechanism — prefix-cache invalidation included, bit-exact to the
  pre-reload engine.
- **Shadow/A-B** (`ShadowABScheduler`): two weight versions behind one
  serving facade.  `assign_arm` (a seeded rid hash — deterministic
  across runs, processes, and submission order) mirrors a traffic
  fraction: originals keep serving from the incumbent (users only ever
  see incumbent output) while copies run on a shadow scheduler holding
  candidate weights, both on one shared (virtual) clock.  A full
  shadow queue drops only the mirror copy — shadow traffic never
  degrades incumbent service.  `arm_reports()` builds per-arm
  `SLOReport`s over the *same* mirrored traffic — candidate vs
  incumbent on identical requests, the promotion comparison.

Observability: boot load and every swap/rollback set
`apex_serving_weights_step`; phase timings land in
`apex_serving_reload_duration_seconds{phase=restore|validate|swap}`
(`swap` is the only phase the serving loop ever waits on).  Chaos
coverage drives corrupt/truncated candidates mid-reload, a simulated
writer crash racing the watcher, and a reload storm under 2x overload
— every perturbation must leave the engine serving the last-good
weights with all streams intact.  `bench.py`'s `serving_reload` block
measures the swap pause (p99 step-time inflation during reload vs
steady state), reload wall time, the restore-ahead contrast, and the
A/B mirror overhead.

## Fault-tolerant fleet serving (`serving.fleet`)

`FleetRouter` fronts N scheduler+engine replicas behind the scheduler
surface `LoadGenerator` already drives (`submit` / `step` / `run` /
`results` / `clock`), so one workload serves a fleet unchanged — and
a fleet of one is **byte-for-byte** the bare scheduler (same tokens,
same `schedule_fingerprint`, tier-1-pinned).

- **Placement**: prefix-affinity first — each prefix-caching
  replica's cache is probed **read-only** (`PrefixCache.probe`; a
  placement decision must never mutate hit/miss/LRU state) and the
  deepest coverage wins; ties and cold prompts fall back to
  smooth-weighted-round-robin over the healthy replicas
  (`FleetConfig(weights=...)`).  A full replica (`QueueFull`) is
  retried against the next-best candidate; only when every healthy
  queue refuses does the router shed.
- **Health**: a completed replica step is a heartbeat on the fleet's
  one shared clock.  Beat age ≥ `suspect_after_s` ⇒ SUSPECT (takes no
  new placements, keeps serving); ≥ `dead_after_s` ⇒ DEAD, and the
  watchdog drains the replica via preempt-capture.  A completed beat
  while SUSPECT recovers to HEALTHY with WRR credits reset (a
  returning replica must not be flooded by its accumulated deficit).
- **Failover fidelity is tiered and honest**: a watchdog-detected
  death (host state intact) captures live DECODE streams — cache
  bytes travel, and the stream resumes on a survivor **bit-exactly**
  (`finish_reason="preempted-resumed"`).  A hard `kill()` (device
  memory lost) re-queues victims from their host-side request
  records with their ORIGINAL submit time; deterministic sampling
  (explicit keys folded per token index) makes the replay
  token-identical for greedy and seeded-temperature streams.
  Captured bytes cannot cross into a paged engine (block references
  are pool-local), so a mixed fleet degrades such victims to replay
  rather than deadlock.  Priority classes survive first; with
  `failover=False` victims are shed — the measured contrast is the
  machinery's value.
- **Ops**: `drain(name)` (rolling reload: move streams off, replica
  stays open and empty), `rejoin(name)` after drain/recovery,
  `replace(name, sched)` for a dead replica rebuilt on a fresh
  scheduler.  A killed or closed replica releases its prefix-cache
  pins and paged-pool holds (`scheduler.close()`) — fleet teardown
  leaks nothing (the pin-leak regression covers it).
- **Chaos**: `resilience.fault_injection` grows `KillReplica` /
  `WedgeReplica` / `SlowReplica`, wired through the same
  `LoadGenerator(step_hook=)` as every other serving fault.  The
  acceptance run kills a replica mid-stream under 2x overload and
  requires victims token-identical to an unperturbed isolated run
  and strictly better goodput than the same chaos without failover.

Observability: `apex_serving_fleet_replicas_healthy`,
`..._routed_total{replica}`, `..._transitions_total{state}`,
`..._failovers_total{mode}`, `..._resumes_total`, `..._shed_total`,
and `..._failover_seconds` (failure → survivor landing, per stream).
`FleetRouter.replica_reports(records)` splits a
`recording_requests` run into per-replica `SLOReport`s (a failover
victim reports on the survivor that finished it) plus the fleet
aggregate.
`bench.py`'s `serving_fleet` block records the failover latency, the
replica-loss throughput ratio, and the failover-on vs -off goodput
delta on identical chaos.

## Rolling upgrades & canary (`serving.rollout`)

`RollingReloadController` orchestrates the fleet-wide weight upgrade
the reload + fleet primitives were built for, with zero dropped
streams — per replica: `prefetch()` the candidate off the serving
path → `drain()` (lossless evacuation to survivors) → `reload()`
consuming the stage (swap-only pause) → `rejoin()`, K replicas per
wave.

- **Health-gate semantics**: between waves the rejoined replicas must
  be HEALTHY for `health_window_steps` **consecutive** clean router
  steps — a SUSPECT beat resets the count (clean-eventually is not
  clean), and a replica death anywhere mid-rollout aborts.  The gate
  bounds the blast radius: at most one wave is ever unproven.
- **Canary**: the first upgraded replica serves a seeded
  deterministic `canary_fraction` of new traffic
  (`FleetRouter.pin_traffic`, the shadow/A-B `assign_arm` rid hash —
  an exact reproducible split, not a statistical one) for
  `canary_window_steps`; the router's pin log then splits the
  window's request records into arms and `CanaryGate` compares the
  canary's `SLOReport` against the old-version baseline (tpot/ttft
  p95 ratios, completion rate, goodput when deadlines are known).
  The gate **fails closed**: a canary that served too few samples
  fails.  Pass promotes the rollout to the remaining replicas;
  fail — or a refused/corrupt candidate — halts it.
- **Rollback exactness**: abort rolls every upgraded replica back
  newest-first via `HotReloader.rollback()`, which swaps back the
  *displaced buffer itself* — the very arrays that were serving
  before the upgrade, retained in the double buffer, never copied
  through a checkpoint round-trip — so a halted rollout leaves the
  fleet serving **bit-identical** weights to the pre-rollout state
  (chaos-pinned).  `rollback()` also discards any staged prefetch
  from the abandoned version (`stats["discarded_stages"]`), so a
  later reload cannot silently re-promote it.
- **Mixed-version caveats**: mid-rollout the fleet serves two
  versions.  `weights_step` rides every routed/finished event and
  `StreamExport`, and the router refuses to resume a captured
  (KV-intact) stream on a *different-version* survivor — it degrades
  to a bare requeue whose deterministic replay re-earns the tokens
  end-to-end on ONE version.  No stream is ever a hybrid of two
  models; the cost is honest (re-decode), the consistency is
  absolute.
- **Chaos**: `CorruptCandidateMidRollout` (candidate bytes rot after
  commit → reload refuses → halt), `RegressingWeights` (validates
  clean, serves measurably worse — only the canary gate catches it),
  and `KillCanary` (canary dies mid-window → halt + rollback), all
  riding `LoadGenerator(step_hook=)`.

Observability: `serving_rollout_{started,replica_upgraded,
canary_verdict,halted,rolled_back,promoted}` events feed
`apex_serving_rollout_*` metrics (in-flight gauge, upgrade/verdict/
halt/rollback/promotion counters, swap-pause + verdict-latency +
rollout-wall histograms).  `bench.py`'s `serving_rollout` block
records rollout wall, per-replica swap pause, dropped streams (must
be 0), and verdict latency; the gate-on vs gate-off goodput delta
under a regressing candidate is the gate's measured value.
""",
    "observability": """\
Answer "what is my p99 step time, queue depth, or TTFT right now"
in-process: a dependency-free metrics registry + span tracer that the
training supervisor, checkpoint manager, serving scheduler/engine and
pipeline timers all publish into, with Prometheus text / JSON / Chrome
trace-event exporters.  Every path below runs under tier-1
(`tests/test_obs.py`), including fault-injected counter-exactness runs
for both training and serving.

## Metric naming conventions

Enforced at registration (`obs.metrics`) **and** statically by
`tools/check_metrics.py` (tier-1: `tests/test_lint_metrics.py`):

- every name matches `^apex_[a-z0-9_]+$`;
- counters end in `_total`; histograms carry a unit suffix
  (`_seconds` / `_bytes` / `_tokens`); gauges are free-form;
- each name is registered at exactly **one** call site (declare the
  instrument once at module level, import the object everywhere else);
- each name appears in the inventory below (the lint cross-checks this
  page, so the table cannot rot);
- a labeled metric's inventory row spells its label names inside the
  backticks (`apex_events_total{event}`), matching the registration's
  `labelnames` + `scope_labels` exactly, and every label in use has a
  row in the "Label cardinality" table below stating its bound — both
  cross-checked both ways by the lint, so a new label cannot ship
  without a documented cardinality budget.

Label names match `[a-z_][a-z0-9_]*`; keep cardinality bounded (label
by event kind or call site, never by request id or step number).
Histograms default to fixed log-spaced latency buckets
(`LATENCY_BUCKETS_S`: 4/decade, 100 µs – 100 s) so two processes — or
two rounds of a benchmark — aggregate bucket-to-bucket.

## Metric inventory

| Metric | Kind | Source |
|---|---|---|
| `apex_events_total{event}` | counter | every `emit_event`, via the bridge |
| `apex_step_duration_seconds` | histogram | supervisor step loop |
| `apex_supervisor_steps_total` | counter | supervisor step loop |
| `apex_heartbeat_age_seconds` | gauge (scrape-time fn) | step watchdog (−1 before the first beat) |
| `apex_supervisor_failures_total{failure}` | counter | `supervisor_failure` events |
| `apex_watchdog_stalls_total` | counter | `watchdog_stall` events |
| `apex_retry_attempts_total{what}` | counter | `retry_attempt` events |
| `apex_retry_exhausted_total{what}` | counter | `retry_exhausted` events |
| `apex_batches_skipped_total` | counter | `batch_skipped` events |
| `apex_replica_desync_total` | counter | `replica_desync` events |
| `apex_faults_injected_total{fault}` | counter | `fault_injected` events |
| `apex_checkpoint_duration_seconds{op}` | histogram | save/validate/restore wall time, plus the async split: `snapshot` (step-loop blocking) vs `write` (background) |
| `apex_checkpoint_inflight` | gauge | `AsyncCheckpointer` (at most one write in flight per pipeline; concurrent pipelines sum) |
| `apex_checkpoint_backpressure_total` | counter | async saves that joined a still-running previous write |
| `apex_checkpoints_rejected_total` | counter | `checkpoint_rejected` events |
| `apex_serving_ttft_seconds{replica}` | histogram | `serving_first_token` events |
| `apex_serving_queue_wait_seconds{replica}` | histogram | `serving_request_admitted` events (submit → slot admission; the queueing component of TTFT) |
| `apex_serving_goodput_ratio` | gauge | `serving.loadgen` (requests meeting their deadline / offered, for the most recent deadline-carrying open-loop run) |
| `apex_serving_prefill_duration_seconds{bucket}` | histogram | `serving_prefill_chunk` events: host wall time of the chunk's **enqueue** — `engine.prefill_chunk` returns an un-awaited array, so on a chip this is tens of µs, not the chunk's device time (read that from a profile: `jit__prefill` on `XLA Modules`) (label = bucket size; bounded by the engine's bucket table) |
| `apex_serving_decode_per_token_seconds{replica}` | histogram | `serving_request_finished` events |
| `apex_serving_tokens_per_second{replica}` | gauge | last finished request |
| `apex_serving_queue_depth{replica}` | gauge | scheduler, every step |
| `apex_serving_slot_occupancy{replica}` | gauge | scheduler, every step |
| `apex_serving_cache_utilization{replica}` | gauge | `DecodeEngine.cache_utilization()`, every step |
| `apex_serving_decode_compiles{replica}` | gauge | `DecodeEngine.decode_compiles()` (1 == shape-stable) |
| `apex_serving_prefill_backlog{replica}` | gauge | scheduler, every step (prompt tokens deferred by the prefill budget) |
| `apex_serving_prefix_hit_total` | counter | `serving_prefix_hit` events (admissions that restored a cached prompt prefix) |
| `apex_serving_prefix_miss_total` | counter | `serving_prefix_miss` events (admissions with no cached prefix to reuse) |
| `apex_serving_prefix_saved_tokens` | histogram | `serving_prefix_hit` events (prompt tokens restored per hit — prefill work not re-run; token-count buckets) |
| `apex_serving_prefix_cached_tokens{replica}` | gauge | scheduler, every step while prefix caching is enabled (tokens of K/V held by the cross-request prefix cache) |
| `apex_serving_spec_drafted_total` | counter | `serving_spec_verify` events (draft tokens proposed by prompt lookup) |
| `apex_serving_spec_accepted_total` | counter | `serving_spec_verify` events (drafted tokens the verify argmax accepted) |
| `apex_serving_spec_rejected_total` | counter | `serving_spec_verify` events (drafted − accepted; rolled back, never emitted) |
| `apex_serving_spec_accepted_tokens` | histogram | `serving_spec_verify` events (accepted draft length per verify; token-count buckets) |
| `apex_serving_spec_speedup{replica}` | gauge | scheduler, per step once a verify has run (tokens emitted per verify dispatch; 1.0 == plain decode) |
| `apex_serving_block_pool_utilization{replica}` | gauge | scheduler, every step while a paged engine serves (allocated KV pool blocks / allocatable blocks) |
| `apex_serving_block_alias_hits_total` | counter | `serving_block_alias` events (prefix-cache blocks reused by table aliasing — zero-copy hits) |
| `apex_serving_block_cow_total` | counter | `serving_block_cow` events (copy-on-write block copies — a write hit a shared block) |
| `apex_serving_preempted_total{replica}` | counter | `serving_request_preempted` events (DECODE streams losslessly evicted by a higher-priority admission; each resumes bit-exactly) |
| `apex_serving_cancelled_total{replica}` | counter | `serving_request_cancelled` events (caller-cancelled requests; slot/blocks/pins released) |
| `apex_serving_shed_total{replica}` | counter | `serving_request_shed` events (expired-deadline evictions before further prefill spend; charged against goodput) |
| `apex_serving_tenant_inflight{tenant}` | gauge | scheduler, every step while a scheduling policy is enabled (active streams per tenant) |
| `apex_serving_tp_size` | gauge | `serving_tp_step` events (tensor-parallel mesh width the decode programs run over; 1 == single-chip) |
| `apex_serving_collective_seconds` | histogram | `serving_tp_step` events (tp decode step wall time, dispatch → completion — an upper bound on per-step collective cost) |
| `apex_serving_weights_step` | gauge | `serving_weights_loaded` / `serving_weights_swapped` events (training step of the weights currently serving — boot load, hot swap, and rollback all set it) |
| `apex_serving_reload_duration_seconds{phase}` | histogram | `serving_weights_loaded` (phase=`restore`) and `serving_weights_swapped` (phase=`validate`\\|`swap`) events — hot-reload phase wall time; `swap` is the only phase the serving loop waits on |
| `apex_serving_fleet_replicas_healthy` | gauge | fleet router step (replicas currently HEALTHY; suspect/draining/dead do not count) |
| `apex_serving_fleet_routed_total{replica}` | counter | `serving_fleet_routed` events — placements by the fleet router (affinity or WRR; label cardinality bounded by fleet size) |
| `apex_serving_fleet_transitions_total{state}` | counter | `serving_fleet_replica_state` events — health transitions by destination state |
| `apex_serving_fleet_failovers_total{mode}` | counter | `serving_fleet_failover` events — streams evacuated from a dead/draining replica (mode=`capture-resume`\\|`requeue`) |
| `apex_serving_fleet_resumes_total` | counter | `serving_fleet_resumed` events with mode=`capture-resume` — victims landed on a survivor with captured cache intact (bit-exact mid-stream) |
| `apex_serving_fleet_shed_total` | counter | `serving_fleet_shed` events — requests the fleet shed (all healthy queues full, no replica, or unabsorbed failover victims) |
| `apex_serving_fleet_failover_seconds` | histogram | `serving_fleet_resumed` events — replica failure (or drain) to survivor landing, per stream, on the fleet's shared clock |
| `apex_serving_rollout_active` | gauge | 1 while a rolling fleet upgrade is in flight (`serving_rollout_started` sets, the promoted/halted terminal clears) |
| `apex_serving_rollout_replicas_upgraded_total` | counter | `serving_rollout_replica_upgraded` events — replicas that completed drain → reload → rejoin |
| `apex_serving_rollout_verdicts_total{verdict}` | counter | `serving_rollout_canary_verdict` events — canary gate decisions (`pass` promotes, `fail` halts) |
| `apex_serving_rollout_halts_total` | counter | `serving_rollout_halted` events — rollouts halted before promotion (gate failure, refused candidate, replica death) |
| `apex_serving_rollout_rollbacks_total` | counter | `serving_rollout_rolled_back` events — replicas rolled back byte-exact from their retained previous buffer |
| `apex_serving_rollout_promotions_total` | counter | `serving_rollout_promoted` events — rollouts that converged the whole fleet on the new `weights_step` |
| `apex_serving_rollout_swap_pause_seconds` | histogram | `serving_rollout_replica_upgraded` events — per-replica serving pause (pointer swap only; restore/validate ran off-path via prefetch) |
| `apex_serving_rollout_verdict_latency_seconds` | histogram | `serving_rollout_canary_verdict` events — canary window open (traffic pinned) to gate verdict, shared clock |
| `apex_serving_rollout_wall_seconds` | histogram | `serving_rollout_halted`/`serving_rollout_promoted` events — rollout start to terminal, shared clock |
| `apex_serving_quant_bytes_per_token` | gauge | `serving_quant_eval` events — KV bytes pinned per cached token under the active quant config (int8 payload + fp32 scales; the streams-per-GB denominator) |
| `apex_serving_quant_logit_error` | histogram | `serving_quant_eval` events — max \\|fp32 − quantized\\| logit distance per evaluation window (dimensionless) |
| `apex_serving_quant_agreement_ratio` | gauge | `serving_quant_eval` events — greedy token-stream agreement vs the fp32 reference over the latest window (1.0 == identical stream) |
| `apex_serving_alerts_firing{rule}` | gauge | `serving_alert_{firing,resolved}` events — 1 while the named alert rule is firing, 0 after it resolves |
| `apex_serving_alert_transitions_total` | counter | `serving_alert_{firing,resolved}` events — alert lifecycle edges (each firing and each resolution counts once) |
| `apex_timer_seconds{region}` | gauge | `Timers.publish_metrics()` |

## Label cardinality

Every label in use, with the vocabulary that bounds it.  Ordinary
labels are part of a metric's `labelnames` and appear on every series;
**scope labels** (`replica` today) are declared via
`scope_labels=` + `MetricsRegistry.declare_scope(label, bound)` and
attach only to series that opt in — the unlabeled series keeps
rendering byte-identically, and the registry rejects a value that
would push the label past its declared bound.

| Label | Bound |
|---|---|
| `event` | `emit_event` kind vocabulary — string literals only, linted by `tools/check_events.py` |
| `what` | retryable-operation names — one per `retrying(what=...)` call site |
| `failure` | supervisor failure-classification enum |
| `fault` | fault-injection plan vocabulary (`tests/`/bench chaos plans) |
| `op` | checkpoint phase enum: `save`/`validate`/`restore`/`snapshot`/`write` |
| `bucket` | engine prefill bucket table (compile-guard-bounded shape set) |
| `tenant` | scheduling-policy tenant ids — bounded by the policy's configured tenant set |
| `phase` | hot-reload phase enum: `restore`/`validate`/`swap` |
| `state` | fleet health-state enum: `healthy`/`suspect`/`draining`/`dead` |
| `mode` | failover mode enum: `capture-resume`/`requeue` |
| `verdict` | canary gate enum: `pass`/`fail` |
| `rule` | alert-rule names — unique per `AlertEngine`, bounded by the configured rule list |
| `replica` | scope label — scheduler `name=` values, bound declared as the fleet size (`declare_scope("replica", n)`; widen-only) |
| `region` | named timer regions — one per `Timers` call site |

## Exposition formats

`prometheus_text()` renders the Prometheus text format (0.0.4),
deterministically ordered: `# HELP` / `# TYPE` headers, one sample per
labeled series, histograms as cumulative `_bucket{le=...}` +
`_sum`/`_count`.  Serve it from any HTTP handler or dump it for a
node-exporter textfile collector.  `write_json(path)` atomically
(temp + `os.replace`) writes `{"time": ..., "metrics": snapshot()}`;
`snapshot()` is the structured point-in-time read tests assert against.
Updates are thread-safe; with no exporter attached the per-update cost
is one lock + one dict write (`bench.py`'s `obs` block pins
counter-inc/gauge-set/histogram-observe ns/op and exposition ms at 1k
series).

## Span semantics

`with span("train_step", step=i) as s:` times a region on the
**monotonic** clock.  With no recorder installed and no `jax.profiler`
session active the span is a near-no-op (one global read and one
`TraceAnnotation.is_enabled()` call — the always-on default; it yields
`None`).  While a `jax.profiler` session is active each span is also a
`jax.profiler.TraceAnnotation`: it is written to the `/host:CPU` plane
of the same `.xplane.pb` as the device's lines, on the profiler's clock
(it aligns the two planes to about a millisecond on a v5e), with its
attributes (plain ints and short strings) as the event's stats.  Under
`install_recorder()` / `with recording() as rec:` each span records a
Chrome trace-event `"X"` entry (`ts`/`dur` in µs, `pid`/`tid`, `args`
carrying attributes + `span_id`/`parent_id`); parent linkage rides
contextvars, so nesting is lexical per thread and survives
context-copying executors.  `current_span()` exposes the innermost live
span — the event bridge stamps every `emit_event` kind onto it, so a
trace of a slow step shows the retries/skips that fired inside it.
`rec.to_chrome_trace()` / `rec.export(path)` produce the
`{"traceEvents": [...]}` JSON that `chrome://tracing` and
[Perfetto](https://ui.perfetto.dev) load directly.  For device-side truth, `start_jax_profiler(logdir)` /
`stop_jax_profiler()` wrap `jax.profiler`, and
`profile_on_stall(logdir)` adapts them to `StepWatchdog(on_stall=...)`
so the first stall of a run captures a device profile on demand.

### The serving step's spans

`ContinuousBatchingScheduler` and `DecodeEngine` open these spans on
the dispatching thread (no option turns them on: they cost ~1.5 µs each
while nothing records).  Nesting is by interval; `rid` is the
identifier a request's spans share.  `serving.readback` wraps the
blocking device read and nothing else, so it is the one span under
which the host *waits*; everything else under `serving.step` is the
host *working*.  A step holds one `serving.readback`, after everything
it enqueues (a scheduler with `speculation=` holds two: it settles
before drafting and again after its decode).

| span | where | attributes |
|---|---|---|
| `serving.submit` | all of `ContinuousBatchingScheduler.submit` | `rid` |
| `serving.step` | all of `step()` | `step`, `active`, `queued` |
| `serving.admit` | deadline shedding + admission (policy path included) | — |
| `serving.prefill` | the prefill budget's chunks | `chunks` (set at exit) |
| `serving.spec` | speculative verifies, only when speculation is on | — |
| `serving.decode` | building the step's inputs through the enqueue of decode, sampler and the kept vector's update | `lanes`; `ahead` = 1 when the previous step's tokens were still unread |
| `serving.readback` | THE blocking device read: this step's first tokens and the previous step's decoded tokens (everything in flight, at a settle point) | `what` = `decode` / `first_token` / `decode+first_token`; `lag` = 1 when a newer decode had already been enqueued |
| `serving.finish` | token append + finish checks of what was just read | `finished` (set at exit) |
| `serving.publish` | step counter, gauges, the `serving_step` event | — |
| `engine.prefill_chunk` | `DecodeEngine.prefill_chunk`: input build and enqueue | `slot`, `bucket`, `tokens` |
| `engine.decode` | all of `DecodeEngine.decode`: checks, paging, enqueue | `lanes`, `kv_tokens` (cached tokens the active lanes attend, before the append) |
| `engine.sample` | `DecodeEngine.sample` | — |
| `engine.verify_draft` | `DecodeEngine.verify_draft` (its two reads included) | `slot`, `drafted` |

**Profile a slow serving step.**  Start a profiler session around a few
steps — `obs.start_jax_profiler(logdir)` … `obs.stop_jax_profiler()`, or
`jax.profiler.trace(logdir)` — and open the result in TensorBoard /
Perfetto or read it with `jax.profiler.ProfileData`: the spans above
appear on the host's Python thread beside the device's `XLA Modules`
and `XLA Ops` lines, so a gap on the device can be laid against the
span the host was in (`benchmark/lib/program_spans.py` is such a
reader).  Without a profiler, `obs.install_recorder()` (or
`with obs.recording() as rec:`) records the same spans as a Chrome
trace, host clock only: `rec.export("step.json")`.

### Device time by component (`obs.scopes`)

The spans above are the host's side of a step.  The device's side is
named by `apex_tpu.obs.scopes`: one vocabulary of `jax.named_scope`s,
`component(name)` = the scope `apex.<name>` (a context manager and a
decorator; a name outside the vocabulary raises), opened around each
part of every served model, of the cache's seam and of the expert
layers.  XLA keeps the scope path as each instruction's `op_name`
(a fused instruction carries its root's), and a `jax.profiler` capture
carries the compiled modules, so the device's own `XLA Ops` line reads
back by component.  A scope is metadata: paid when a program is traced,
never when it runs; the lowered program is the same text with and
without it; nothing turns it on.  Scopes nest under flax's module path
and the innermost `apex.<name>` of an instruction is its component.

| scope | what is inside it | opened in |
|---|---|---|
| `apex.embed` | token embedding | each served model's `__call__` |
| `apex.norm` | a layer's input / post-attention norms | each decoder layer |
| `apex.attn_proj` | q, k, v (or the latent down / up projections, a selector's or a Mamba mixer's two products), rope / YaRN, gates, the output projection, the residual add that closes the branch | `LlamaAttention`, `NemotronHAttention`, `MellumAttention`, `LatentAttention`, `ViTSelfAttention`, `Mamba2Mixer`'s `in_proj` / `out_proj` |
| `apex.cache_write` | append / chunk-write, ring writes, `write_slot_state` / `write_lane_state`, a slot's length | `serving/kv_cache.py`: inside every `*_attend` of the seam; `commit_slot_length` |
| `apex.cache_read` | the read whichever path was chosen: the Pallas call **with its glue** (slot cut, head-major cut, casts, expansion), `cached_attention`, the blocked loops, the masked reads | the same functions; inside the chunk kernel's own `jit` too |
| `apex.select` | a selector's scores, thresholds / `lax.top_k`, the gather of the selected rows | `latent_decode_attend`, `latent_prefill_attend` |
| `apex.state` | Mamba-2: convolution tail, chunked scan, one-token state update, gated norm, `slot_state` | `Mamba2Mixer` |
| `apex.mlp` | dense MLP, a shared expert, the residual add that closes the branch | `LlamaMLP`, `GatedMLP`, `LatentMoE` / `GatedMoE` |
| `apex.router` | router logits, top-k routing, `held_pairs` (the dispatch sort), `add_counts` | `transformer/moe.py`, `kv_cache.add_counts` |
| `apex.experts` | the grouped products (`grouped_matmul`), activation, weighting and combine, `LatentMoE`'s latent down / up | `transformer/moe.py` |
| `apex.head` | final norm, the LM-head product, the logits the engine hands back | each served model's `__call__`, `DecodeEngine`'s programs |
| `apex.sample` | the sampler | `serving/engine.py::_sample_one` |

**Where a running engine's device time goes.**  Capture a few steps -
`obs.start_jax_profiler(logdir)` ... `obs.stop_jax_profiler()`, or
`StepWatchdog(on_stall=obs.profile_on_stall(logdir))` for the first
stall - then

    python3 benchmark/tools/scope_table.py <logdir>

prints program x component: ms an execution (self time: a `while` and
the ops of its body count once) and share, the scoped share of the
decode and prefill programs, and what is under no scope by op name
(`benchmark/lib/device_scopes.py` is the reader; it needs `jax` to read
the file and no device).  Three things bound what the split can mean: a
fused instruction carries its root's path, so a norm fused into the
product after it counts with the product; an instruction XLA made
itself (the prefetch of the next matrices: `copy-start` / `slice-start`
and their `-done`) has no path and is given to what consumes it; a
callee under a `jit` of its own is lowered once and carries the first
call site's path - read the component, never `layers_N`.  A persistent
compilation cache is keyed without metadata by default: a program it
kept from before the scopes (or from before a scope moved) is served
with the names it was compiled with.

## The event bridge

`apex_tpu._logging.emit_event` fans out to a sink registry
(`add_event_sink` / `remove_event_sink`); the default sink is the
original JSON log line — **byte-identical** with or without extra
sinks.  `obs.bridge` (installed when `apex_tpu.obs` imports, which
every instrumented subsystem does) subscribes a sink that counts every
event kind, stamps the active span, and runs per-kind handlers for
payloads carrying real measurements.  Zero call-site churn: existing
`emit_event` callers became metrics sources without edits.

## Request-level serving traces (`obs.request_trace`)

`RequestTraceRecorder` is a second event sink (same registry, same
zero call-site churn) that folds the serving event stream back into
**one lifecycle record per request**: queued → admitted →
prefix-hit/restore → each prefill chunk → first token → decode →
finished, with exact phase boundaries on an injectable clock
(`queue_wait_s` / `prefill_s` / `decode_s` sum to `total_s` within
1 µs — the four stamps are shared), slot id, and
speculation / prefix-cache / paged-aliasing annotations matched from
the event payloads.  Control-plane terminals close records too: a
cancelled or shed request keeps whatever stamps it earned
(`finish_reason` says why it died; incomplete records are counted,
never distributed), and preemption cycles annotate the record
(`preemptions` + per-gap `t_preempted`/`t_resumed` stamps, rendered
as `preempted` slices inside the decode track).  Default-off like spans: with no recorder
installed nothing runs and the event/metric stream is untouched
(tier-1 pins the identity **and** an instrumented-vs-bare scheduler
step bound ≤ 1.10× with a recorder installed).  Exports follow the
`TraceRecorder` conventions — bounded memory (`max_requests`, drops
counted in `otherData`), `export(path)` writes a Perfetto-loadable
Chrome trace with **one named track per request** (phases and
chunk/verify slices nested by containment), `export_jsonl(path)`
writes one JSON record per request for offline analysis, both through
the shared atomic-write + non-finite-sanitizing machinery.

## Fleet observability

Three opt-ins turn the single-replica story into a fleet one; all
three are default-off, and with all three off the event stream and
metric snapshot are **byte-identical** to an uninstrumented run.

**Per-replica metric attribution.**  Give a scheduler a name
(`ContinuousBatchingScheduler(..., name="r0")`) and every serving
event it emits carries `replica="r0"`; the bridge then dual-writes
each measurement — the unlabeled fleet-aggregate series exactly as
before, plus a `{replica="r0"}` series for every instrument marked
`{replica}` in the inventory.  The label is a *scope label*:
cardinality is bounded by `declare_scope("replica", fleet_size)`
(the `FleetRouter` declares it at construction; `register_replica`
widens it as names appear), and an unnamed scheduler produces zero
labeled series.  Because the labeled series are written from the same
events as the aggregates, the per-replica sums reconcile **exactly**:
summing `apex_serving_preempted_total{replica=...}` over replicas
equals the unlabeled counter, and each replica's histogram counts
match its `replica_reports()` sample counts.

**Cross-replica hop trails.**  With a `RequestTraceRecorder`
installed, the fleet router's `serving_fleet_{routed,failover,
resumed,shed}` events append to each record's `hops` list — a
placement trail with the schema:

    {"kind": "placed",   "replica": str, "retries": int,
     "weights_step": int|None, "t": float}
    {"kind": "failover", "replica": str (the donor), "mode":
     "capture-resume"|"requeue", "new_tokens": int, "t": float}
    {"kind": "resumed",  "replica": str (the survivor),
     "from_replica": str, "mode": str, "duration_s": float, "t": float}
    {"kind": "shed",     "reason": str, "t": float}

`record.replica` always names the replica currently holding the
stream.  `to_chrome_trace()` grows **one lane per replica** (tids from
`REPLICA_TID_BASE`, sorted by name) showing each request's residency
span on the replica that held it, plus health-state instants, reload
swap-pause slices, and a fleet control lane carrying rollout
started/verdict/promoted/halted/rolled-back marks — a `KillReplica`
chaos drain exports a single Perfetto timeline showing the victim's
streams migrating to survivors.  Fleet control events are bounded
separately (`max_fleet_events`, drops counted in `otherData`), and a
recorder with no fleet content exports byte-identically to before.

**Deterministic alerts (`obs.alerts`).**  `AlertEngine(rules)` is
handed to the router (`FleetRouter(..., alerts=engine)`) and
evaluates every rule against a registry snapshot at each fleet step
boundary **on the fleet's own clock** — no scrape thread, no wall
time.  Three rule types share one evaluation core (`Condition`, the
same comparator object `CanaryGate` gates rollouts with):
`ThresholdRule` (compare a series value — histograms select their
cumulative count at a bucket edge via `le=`), `AbsenceRule` (a series
absent or unchanged for `stale_after_s`), and `BurnRateRule`
(multi-window SLO burn: `bad_fraction / (1 − objective)` computed
over a long and a short window of snapshot deltas, firing only when
**both** exceed `factor` — fast to fire on a real burn, fast to
resolve when it stops).  Rules carry `for_duration_s` hysteresis
(ok → pending → firing), and each transition appends a ledger entry
`{step, t, rule, transition, value}` and emits
`serving_alert_{firing,resolved}` — which the bridge folds into
`apex_serving_alerts_firing{rule}` /
`apex_serving_alert_transitions_total`.  The determinism contract:
rule evaluation touches only the snapshot and the injected clock, so
the same workload + seed + virtual clock yields a **bit-identical
ledger** across reruns (tier-1 pins this, firing `replica_down` and
`goodput_burn` under a scripted chaos drain twice and diffing the
ledgers).  No engine installed ⇒ no evaluation, no events.

## SLO reports (`obs.slo`)

`build_report(records, offered=..., deadlines=..., duration_s=...)`
folds a recorder's records into an `SLOReport`: **nearest-rank**
p50/p95/p99 (+ mean/min/max) over the exact per-request samples for
TTFT (submit → first token), TPOT (decode seconds per generated token
past the first), queue wait, and end-to-end latency, plus goodput
(requests meeting their deadline / requests *offered* — shed,
cancelled, and unfinished requests count against it; full service is
required, so a record whose `finish_reason` is `cancelled`/`shed`
can never count as met) and throughput.
`SLOReport.to_dict()` is a stable rounded JSON-ready dict (the
`bench.py serving_slo` block's payload; diffable by
`tools/bench_compare.py`).  `Histogram.quantile(q)` gives the
scrape-side bucket-interpolated estimate (exact at bucket edges,
error bounded by one bucket width), and
`crosscheck_quantiles(samples, histogram)` proves the two views agree
bucket-for-bucket — the in-process dashboard and the offline report
cannot silently diverge.
""",
}


def render_page(key: str) -> str:
    title, modules = PAGES[key]
    out = [f"# {title}\n"]
    if key in PAGE_PROLOGUE:
        out.append(PAGE_PROLOGUE[key])
    for modname in modules:
        try:
            mod = importlib.import_module(modname)
        except Exception as e:  # pragma: no cover - import errors are bugs
            out.append(f"## `{modname}` — IMPORT FAILED: {e}\n")
            continue
        out.append(f"## `{modname}`\n")
        d = _doc_first_block(mod)
        if d:
            out.append(d + "\n")
        for name in _public_names(mod):
            obj = getattr(mod, name, None)
            if obj is None:
                continue
            # skip re-exports documented under their home module's page
            home = getattr(obj, "__module__", modname)
            if (home != modname and home in sum(
                    (m for _, m in PAGES.values()), [])
                    and modname.count(".") >= 2):
                continue
            out.extend(_render_symbol(name, obj))
    return "\n".join(out) + "\n"


def render_index() -> str:
    lines = [
        "# apex_tpu API reference\n",
        "TPU-native counterpart of the reference's sphinx tree "
        "(`docs/source/index.rst`: amp, parallel, optimizers, layernorm, "
        "fp16_utils), extended to every public package.  Generated from "
        "the live modules by `tools/gen_api_docs.py` — signatures cannot "
        "drift from the code.\n",
        "| Page | Covers |",
        "|---|---|",
    ]
    for key, (title, modules) in PAGES.items():
        mods = ", ".join(f"`{m.removeprefix('apex_tpu.')}`" for m in modules)
        lines.append(f"| [{title}](api/{key}.md) | {mods} |")
    lines.append(QUICKSTART)
    lines.append(
        "\nSee also: [README](../README.md) (design map), "
        "[PARITY.md](../PARITY.md) (component-by-component reference "
        "parity), [PERF_NOTES.md](../PERF_NOTES.md) (measured performance "
        "log), [BASELINE.md](../BASELINE.md) (targets and captured "
        "numbers).\n")
    return "\n".join(lines)


QUICKSTART = """
## Quickstart — amp → fused optimizer → TP → PP

```python
import jax, jax.numpy as jnp
from apex_tpu import amp
from apex_tpu.optimizers import FusedLAMB

# 1. mixed precision: O2 casts the body to bf16, keeps fp32 masters
amped = amp.initialize(model.apply, params, opt_level="O2",
                       half_dtype=jnp.bfloat16)
opt = FusedLAMB(lr=1e-3, master_weights=amped.policy.master_weights,
                state_dtype=jnp.bfloat16)          # bf16 moments: ~7% MFU
opt_state, sstate = opt.init(amped.params), amped.scaler_state

@jax.jit
def train_step(params, opt_state, sstate, batch):
    def scaled_loss(p):
        return amped.scaler.scale_loss(loss_fn(p, batch), sstate)
    grads = jax.grad(scaled_loss)(params)
    grads, found_inf = amped.scaler.unscale(grads, sstate)  # overflow skip
    params, opt_state = opt.step(grads, params, opt_state,
                                 found_inf=found_inf)
    return params, opt_state, amped.scaler.update(sstate, found_inf)
```

Tensor parallelism (Megatron-style, with sequence parallelism) — build
layers from `transformer.tensor_parallel` and run them under `shard_map`
on a mesh from `parallel_state`:

```python
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.tensor_parallel import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding)

mesh = parallel_state.initialize_model_parallel(tp, pp)   # ("dp","pp","tp")
col = ColumnParallelLinear(h, 4 * h, gather_output=False,
                           sequence_parallel_enabled=True, axis_name="tp")
row = RowParallelLinear(4 * h, h, input_is_parallel=True,
                        sequence_parallel_enabled=True, axis_name="tp")
```

Pipeline parallelism — describe the per-stage compute once and hand it to
a schedule (`examples/gpt/pretrain.py --pp`, `examples/llama/pretrain.py`):

```python
from apex_tpu.transformer.pipeline_parallel import (
    PipelineStageSpec, forward_backward_pipelining_1f1b)

spec = PipelineStageSpec(stage_fn=block_fn, first_fn=embed_fn,
                         last_fn=loss_fn)
loss, grads = forward_backward_pipelining_1f1b(spec, stage_params, batches)
```

Resilient training — validated checkpoints every K steps, automatic
fallback past a corrupt latest, anomaly-aware skipping
([full page](api/resilience.md)):

```python
from apex_tpu import resilience as rz

mgr = rz.CheckpointManager("/ckpts/run7", keep=3)
gstate = rz.init_guard_state(scaler)
step = jax.jit(rz.make_guarded_step(loss_fn, opt, scaler))

state = {"params": params, "opt": opt_state,
         "scaler": sstate, "guard": gstate, "rng": rng}
try:                                     # restart-safe entry
    state, last = mgr.restore(like=state)   # newest VALID checkpoint
    start = last + 1
except rz.CheckpointError:
    start = 0
for i in range(start, num_steps):
    out = step(state["params"], state["opt"], state["scaler"],
               state["guard"], next_batch(state["rng"], i))
    state.update(zip(("params", "opt", "scaler", "guard"), out[:4]))
    mgr.save(i, state)                   # atomic write + keep-last-K
```

A checkpoint root assumes a **single writer**: in multi-controller runs
gate `mgr.save` on `jax.process_index() == 0` (or give each process its
own root) — concurrent saves into one root race the temp-dir sweep.

Surviving hangs and flaky input — the supervised loop puts a deadline on
every step, retries transient fetch/save I/O, skips corrupt batches
within a budget, and degrades gracefully (emergency checkpoint + clean
abort) when failures persist ([full page](api/resilience.md)):

```python
from apex_tpu import resilience as rz

mgr = rz.CheckpointManager("/ckpts/run7", keep=3,
                           retry=rz.RetryPolicy())      # transient-I/O retry
sup = rz.TrainingSupervisor(mgr, rz.SupervisorConfig(
    step_deadline_s=1800.0,              # watchdog: stall -> diagnostics
    max_consecutive_failures=3,          # then emergency ckpt + clean abort
    heartbeat_path="/ckpts/run7/heartbeat.json"))       # orchestrator probe

batches = rz.GuardedIterator(                            # validate every batch
    make_batches(), spec=rz.spec_of(exemplar_batch),
    skip_budget=8, stall_timeout_s=120.0)

def step_fn(state, batch, step):                         # step_fn(state, batch, step)
    return train_step(state, batch)                      # any jitted update

try:
    state, start = mgr.restore(like=state)               # restart-safe entry
    start += 1
except rz.CheckpointError:
    start = 0
try:
    state, last = sup.run(step_fn, state, batches,
                          num_steps=num_steps, start_step=start)
except rz.TrainingAborted as abort:                      # resumable by design
    orchestrator_requeue(resume_from=abort.checkpoint_path)
```

A slow-but-finished step keeps its result and counts one failure; a hung
step is reported mid-stall by the watchdog's monitor thread (structured
`watchdog_stall` event + `stalled` heartbeat marker) so the orchestrator
can kill and requeue with evidence.  Every path above is driven
deterministically in tier-1 by the fault injectors (`SlowStep`,
`FlakyIterator`, `CorruptBatch`).

Take the save off the hot path — once steps are fast, the periodic
checkpoint's serialize+CRC+fsync wall time is the dominant stall left.
`SupervisorConfig(async_save=True)` makes the step loop block only on a
device→host **snapshot** (≈ a memcpy, donation-safe) while a background
thread runs the existing write machinery — same bytes on disk, same
restores, bit-identical ([full page](api/resilience.md)):

```python
sup = rz.TrainingSupervisor(mgr, rz.SupervisorConfig(
    checkpoint_every=50,
    async_save=True))      # snapshot on the step, write in the background
```

At most one write is in flight (the *next* save joins it first —
backpressure never blocks the step); a failed write surfaces at the next
step boundary into the same retry/escalation ladder; emergency
checkpoints and shutdown join the in-flight write; a failed consistency
pass vetoes an in-flight commit.  `async_save=False` (the default) is
the synchronous escape hatch.  Standalone use:
`rz.AsyncCheckpointer(mgr).save(step, state)` returns a `SaveFuture`.

Resize the pod mid-training — a preempted job rarely gets the same slice
back.  *Sharded* checkpoints (manifest v2) record one CRC'd shard per
(leaf, mesh-coordinate block) and reshard on restore onto whatever mesh
the templates live on, bit-identically; periodic `verify_replicas`
catches silent dp divergence before it spreads
([full page](api/resilience.md)):

```python
from apex_tpu import resilience as rz
from apex_tpu.transformer import parallel_state

# ---- before the resize: train on (dp=4, tp=2), save SHARDED
mesh = parallel_state.initialize_model_parallel(2)       # dp=4, tp=2
mgr = rz.ShardedCheckpointManager("/ckpts/run7", keep=3,
                                  mesh=mesh, retry=rz.RetryPolicy())
sup = rz.TrainingSupervisor(
    mgr, rz.SupervisorConfig(consistency_check_interval=50),
    consistency=rz.ReplicaConsistency(mesh=mesh),        # verify+resync
    persist_transform=rz.collapse_replicas)  # EVERY checkpoint the
    # supervisor writes (periodic and emergency) stores the mesh-shape-
    # free logical copy, never the dp-world-size-dependent stacked form
logical = rz.collapse_replicas(state)                    # mesh-shape-free
mgr.save(step, logical)                                  # per-shard CRCs

# ---- after the resize: SAME root, different slice (dp=2, tp=4)
mesh = parallel_state.initialize_model_parallel(4)       # dp=2, tp=4
template = init_state(mesh)          # leaves carry the NEW shardings
logical, last = mgr.restore(like=rz.collapse_replicas(template))
state = rz.expand_replicas(logical, mesh)  # re-stack at the new dp size
```

The restore walk validates per-shard CRCs as it reassembles each global
leaf, falls back past a damaged step (`checkpoint_rejected` event), and
never runs arithmetic on the bytes — resuming on `(dp=2, tp=4)` or
`dp=8` is bit-identical to the `(dp=4, tp=2)` save.  A **v1**
(whole-tree) checkpoint cannot reshard: restoring one onto a different
mesh raises `CheckpointError` instead of silently resharding wrong.

Serve a trained checkpoint — start from the SAME resilience checkpoint
root the training loop wrote (v1 whole-tree and v2 sharded both load;
the newest *valid* step wins, exactly like a training restart), cast
for bf16 serving through the amp policy, and run KV-cached continuous
batching with bucketed chunked prefill
([full page](api/serving.md)):

```python
from apex_tpu import amp, serving as sv
from apex_tpu.models import LlamaConfig, LlamaForCausalLM

model = LlamaForCausalLM(LlamaConfig.llama2_7b())
template = {"params": params_template, "opt": opt_template,
            "scaler": sstate, "rng": rng}          # the SAVED structure
params, step = sv.load_serving_params(
    "/ckpts/run7", like=template, params_key="params",
    policy=amp.policy.O2())                        # bf16, norms fp32

eng = sv.DecodeEngine(model, params, slots=8, max_len=2048,
                      prefill_len=256)   # buckets (16, 32, 64, 128, 256):
                                         # a short prompt costs a short
                                         # dispatch; prompts up to 2048
                                         # serve via chunked prefill
sched = sv.ContinuousBatchingScheduler(
    eng, max_queue=64,
    prefill_budget=256)      # tokens of prefill per step: long
                             # admissions advance chunk-by-chunk between
                             # decode steps instead of stalling them
sched.submit(sv.Request("r0", prompt_ids, max_new_tokens=128, eos_id=2,
                        temperature=0.7, top_k=40, seed=7))
results = sched.run()          # rid -> RequestResult (tokens, TTFT, tps)
```

Serve a model too big for one chip — opt the same engine onto a
tensor-parallel mesh: params restore column/row-split directly onto
the mesh (no host-replicated copy of a model that only fits sharded),
the KV cache shards head-wise, and every serving feature — prefix
caching, speculation, paged CoW, lossless preemption — runs unchanged
over it.  Greedy streams stay token-identical to a single-chip engine;
the per-layer psum pair is the new hot path, watched by
`apex_serving_collective_seconds` ([full page](api/serving.md)):

```python
from apex_tpu.utils.compat import serving_mesh

mesh = serving_mesh(8)                     # 1-D "tp" mesh, 8 chips
params, step = sv.load_serving_params(
    "/ckpts/run7", like=template, params_key="params",
    policy=amp.policy.O2(),
    shardings=sv.tp_param_shardings(template["params"], mesh))
eng = sv.DecodeEngine(model, params, slots=8, max_len=2048,
                      prefill_len=256, tp=sv.TPConfig(size=8))
sched = sv.ContinuousBatchingScheduler(eng, max_queue=64,
                                       prefill_budget=256)
# (on CPU, export XLA_FLAGS=--xla_force_host_platform_device_count=8
#  before jax initializes to rehearse the mesh without TPUs)
```

Serve in int8 — when HBM, not FLOPs, caps how many streams fit, opt
the same engine into quantized serving: per-output-channel int8
projection kernels (norms/embedding stay high-precision), a
per-(position, head)-scaled int8 KV cache (dense or paged — ≥ 1.8×
more streams per GB), and optionally an EQuARX-style int8 tp
allreduce for the latency-bound decode collective.  The default
`quant=None` is byte-for-byte off; on, the claim is greedy-stream
*agreement* with fp32 (measured, not assumed), and every structural
guarantee — chunked prefill, speculation, capture/restore, CoW —
still holds bit-for-bit *within* the quantized engine
([full page](api/serving.md)):

```python
params, step = sv.load_serving_params(
    "/ckpts/run7", like=template, params_key="params",
    quantize=True)                       # int8 QTensor kernels at load
eng = sv.DecodeEngine(model, params, slots=32, max_len=2048,
                      prefill_len=256,
                      quant=sv.QuantConfig(weights=True, kv=True))
report = sv.evaluate_quant(ref_tokens, quant_tokens,
                           bytes_per_token=sv.kv_bytes_per_token(
                               eng.cache))   # -> agreement gauge et al.
```

Slots admit from the bounded FIFO queue at every step boundary and free
on EOS/max-tokens with immediate reuse; the decode step compiles once
and never retraces, and prefill compiles are bounded by the bucket
table (both asserted through `utils.compat.compile_count`) no matter
how requests arrive.  Prefill — one-shot, bucketed, or chunked past
`prefill_len` — and greedy decode through the cache are bit-identical
to the uncached forward (the tier-1 acceptance tests), sampling replays
exactly from its explicit seeds, and deferred admission work is visible
as the `apex_serving_prefill_backlog` gauge.

Speed up decode with speculation — plain decode reads every weight once
per token; when the output repeats content the stream has already seen
(summarization, code edit, RAG quoting its context), prompt-lookup
speculative decoding amortizes that read over several tokens **without
changing a single emitted bit**: a host-side n-gram match over the
request's own history drafts up to k tokens (no draft model, zero
device cost), one bucketed multi-token *verify* dispatch scores all
k+1 positions through the chunked-prefill machinery, and the longest
draft prefix the target's own greedy argmax agrees with is emitted
plus a free bonus token ([full page](api/serving.md)):

```python
sched = sv.ContinuousBatchingScheduler(
    eng, max_queue=64,
    speculation=sv.SpeculationConfig(
        max_draft=8,         # widest draft (verify compiles stay
                             # bounded by the engine's draft_buckets)
        ngram_max=4))        # longest suffix the lookup tries
sched.submit(sv.Request("r0", prompt_ids, max_new_tokens=128, eos_id=2))
results = sched.run()        # bit-identical tokens, fewer dispatches
```

Greedy requests adapt their draft length to the measured acceptance
(double on full accept, halve on rejection); streams with no n-gram
match and all `temperature > 0` requests ride the existing decode path
— the latter byte-for-byte (no drafting, no verify compiles, identical
events and metrics).  Acceptance telemetry rides
`apex_serving_spec_{drafted,accepted,rejected}_total`, the
`apex_serving_spec_accepted_tokens` histogram, and the
`apex_serving_spec_speedup` gauge (tokens emitted per verify
dispatch); `bench.py`'s `serving_spec` block records the honest
speedup on both a repetitive and an adversarial workload.

Serve a fleet of chatbots off one system prompt — when every request
opens with the same long system prompt (or few-shot template, or chat
history), re-running prefill over the shared prefix is the dominant
admission cost.  Cross-request prefix caching eliminates it **without
changing a single bit**: completed prompt blocks are snapshotted into
a chain-hashed store, and each new admission restores the longest
cached chain verbatim and prefills only its own suffix
([full page](api/serving.md)):

```python
sched = sv.ContinuousBatchingScheduler(
    eng, max_queue=64,
    prefix_caching=sv.PrefixCacheConfig(
        max_tokens=1 << 20))   # cached-K/V budget (LRU past it;
                               # entries feeding live slots are
                               # ref-count pinned, never evicted)

system = load_system_prompt()            # say, 1500 tokens
for i, user_turn in enumerate(traffic):  # the fleet
    sched.submit(sv.Request(f"u{i}", system + user_turn,
                            max_new_tokens=256, eos_id=2))
results = sched.run()
```

The first admission prefills the whole prompt and populates the cache
(insert-on-miss, deterministic capture right after each chunk); every
later admission restores the shared 1500 tokens in a handful of
bucketed writes and spends its prefill budget on the user turn alone —
time-to-first-token drops by roughly the shared fraction.  Because the
restored K/V are bit-for-bit what prefill would have written, token
streams, logits, and greedy choices are identical to a cold cache
(tier-1 pins the full trajectory).  Hits and saved tokens ride
`apex_serving_prefix_{hit,miss}_total` and
`apex_serving_prefix_saved_tokens`; the
`apex_serving_prefix_cached_tokens` gauge tracks store occupancy; and
`prefix_caching=None` (the default) leaves every serving path
byte-for-byte untouched.  `bench.py`'s `serving_prefix` block records
the measured ≥ 2× aggregate prefill throughput on a shared-prompt
fleet and the no-regression bar without overlap (asserted against
the harness's own measured noise floor — capture is copy-based, so
its true cost is real but sub-noise at bench scale).

Watch a training job live — the supervisor, checkpoint manager, and
serving scheduler already publish into the default metrics registry
(every `emit_event` increments a counter via the sink bridge; step
latency, checkpoint durations, TTFT and queue depth are first-class
series), so observing a run is export-only
([full page](api/observability.md)):

```python
from apex_tpu import obs

# 1. metrics: scrape or dump — no server required
print(obs.prometheus_text())          # Prometheus text exposition
obs.write_json("/ckpts/run7/metrics.json")   # atomic JSON snapshot
hist = obs.REGISTRY.get("apex_step_duration_seconds")
print(hist.count(), hist.sum())       # step count + total seconds

# 2. spans: record a window, open it in Perfetto (ui.perfetto.dev)
rec = obs.install_recorder()
state, last = sup.run(step_fn, state, batches, num_steps=n)
obs.uninstall_recorder()
rec.export("/ckpts/run7/trace.json")  # chrome://tracing-loadable

# 3. a stall? capture a device profile the moment it happens (opt-in)
wd = rz.StepWatchdog(deadline_s=120.0,
                     on_stall=obs.profile_on_stall("/ckpts/run7/prof"))
```

Every step is ONE `supervisor_step` span covering fetch → step →
commit: fetch retries and batch skips stamp it as events, and the
`train_step` and `checkpoint_save` spans nest inside it — the trace of
a slow step is also its causal story.  `apex_heartbeat_age_seconds`
evaluates at scrape time, so a wedged host shows a growing age, not a
stale sample (a stopped watchdog reports the `-1` no-live-beat
sentinel).  With
no exporter attached the whole layer costs a lock + dict write per
update (`bench.py` `obs` block).

Load-test your server and read the SLO report — throughput at drain
rate says nothing about latency under load; drive the scheduler
**open-loop** at a controlled offered load, record every request's
lifecycle, and read the percentiles
([serving page](api/serving.md), [obs page](api/observability.md)):

```python
from apex_tpu import obs, serving as sv

# 1. a deterministic bursty workload: 64 shared-prefix requests in
#    bursts of 4, ~8 requests/s offered, 2 s completion deadline
wl = sv.make_workload(
    sv.shared_prefix_prompts(64, shared_len=96, suffix_len=16,
                             vocab=cfg.vocab_size, seed=7),
    sv.burst_arrivals(64, burst=4, period_s=0.5),
    max_new_tokens=32, deadline_s=2.0)

# 2. record request lifecycles off the event stream (an event sink —
#    no scheduler changes; omit it and nothing runs at all)
with obs.recording_requests() as rec:
    out = sv.LoadGenerator(sched, wl).run()     # sheds at QueueFull

# 3. the SLO report: exact nearest-rank percentiles per phase
#    (deadlines enforced from ARRIVAL — pass out.arrivals)
report = obs.build_report(rec.records(), offered=out.offered,
                          deadlines=out.deadlines,
                          arrivals=out.arrivals,
                          duration_s=out.duration_s)
print(report.to_dict())   # p50/p95/p99 ttft_s / tpot_s /
                          # queue_wait_s, goodput, throughput

# 4. where did a slow request's time go?  one named track per request
rec.export("/tmp/requests.trace.json")   # open in ui.perfetto.dev
rec.export_jsonl("/tmp/requests.jsonl")  # offline analysis
```

Same seed, same schedule, bit for bit
(`wl.schedule_fingerprint()` digests offsets + token ids + generation
config); under a `VirtualClock` + `step_time_s=` the whole run is
sleep-free and every latency deterministic — the tier-1 tests assert
exact TTFT values.  Goodput (met deadlines / offered) rides the
`apex_serving_goodput_ratio` gauge, queue wait feeds
`apex_serving_queue_wait_seconds`, and `Histogram.quantile(q)`
cross-checks the scrape-side estimates against the exact samples.
`bench.py`'s `serving_slo` block runs this recipe at ~1× and ~2× the
measured sustainable load; compare rounds with
`python tools/bench_compare.py OLD.json NEW.json` (exit 1 on any
metric regression beyond tolerance).

Keep p99 for paying tenants under overload — a 2x burst doubles
everyone's p99 under FIFO; the serving control plane protects the
tier that paid for latency, losslessly
([serving page](api/serving.md)):

```python
from apex_tpu import serving as sv

sched = sv.ContinuousBatchingScheduler(
    eng, max_queue=256,
    policy=sv.SchedulingPolicy(
        tenant_weights={"paid": 3.0},      # smooth WRR within a class
        max_inflight_per_tenant=6,         # no tenant owns every slot
        preemption=True,                   # evict lower priority...
        deadline_shedding=True))           # ...and shed the expired

# the paying tier: high priority, tight completion deadline
sched.submit(sv.Request("chat-1", prompt, max_new_tokens=128, eos_id=2,
                        priority=10, deadline_s=2.0, tenant="paid"))
# batch traffic: default priority, loose deadline
sched.submit(sv.Request("batch-7", doc, max_new_tokens=512,
                        deadline_s=60.0, tenant="batch"))

results = sched.run()   # raises SchedulerStalled on a wedged engine
sched.cancel("batch-7") # a disconnected client frees its slot/blocks
```

When `chat-1` arrives with every slot busy, the lowest-priority DECODE
stream is **preempted losslessly**: its cache bytes are captured
(dense: bucketed region reads; paged: block references — zero copies),
the slot serves the paying request, and the victim later resumes
**bit-exactly** — same f32 logits, same tokens, reported as
`finish_reason="preempted-resumed"`.  Queued requests whose deadline
already passed are shed before they waste prefill budget, and both
sheds and cancellations are charged against goodput (full service or
it didn't count).  A scheduler without `policy=` stays byte-for-byte
FIFO.  `bench.py`'s `serving_slo.policy` block runs the same
overloaded workload FIFO-vs-policy and records the honest
high-priority p99 TTFT and goodput deltas in `PERF_NOTES.md`; chaos
drivers (`SlowDecodeStep`, `StallStream`, `CancelStorm`) let tier-1
prove every surviving stream is token-identical under fire.

Serve while you train — training keeps committing checkpoints; the
server picks each one up **without dropping a stream**: a watcher
polls for newer committed steps, the candidate restores
double-buffered through the same validated path as boot (a corrupt
candidate refuses the swap with serving untouched), and the swap
happens at a step boundary with in-flight streams preserved, the
prefix cache version-invalidated, and the previous weights retained
for one-step rollback ([full page](api/serving.md)):

```python
from apex_tpu import resilience as rz, serving as sv

# training side (possibly another process): AsyncCheckpointer commits
# steps under root; the supervisor heartbeat points at the last commit
reloader = sv.HotReloader(
    sched, "/ckpts/run7", like=template, params_key="params",
    watcher=sv.WeightWatcher("/ckpts/run7",
                             heartbeat_path="/ckpts/run7/heartbeat"),
    retry=rz.RetryPolicy(max_attempts=4))   # transient I/O only

while serving:                     # the serving loop, unchanged...
    sched.step()
    out = reloader.maybe_reload()  # ...plus one cheap poll per step
    if out is not None and not out.ok:
        log.warning("candidate %s refused: %s", out.step, out.reason)
if regression_detected:
    reloader.rollback()            # bit-exact one-step undo

# A/B the candidate before promoting: mirror 10% of traffic onto a
# shadow engine holding the new weights (users see incumbent output)
ab = sv.ShadowABScheduler(sched, shadow_sched,
                          sv.ABConfig(fraction=0.1, seed=7))
with obs.recording_requests() as rec:
    sv.LoadGenerator(ab, wl).run()
reports = ab.arm_reports(rec.records())   # candidate vs incumbent
```

Post-swap tokens are bit-identical to a fresh engine booted on the
new weights and fed the same state; a refused candidate (corrupt,
truncated, wrong shape) leaves serving bit-exactly on the old
weights; a swap adds **zero** new compiles (same-spec contract).  The
step being served rides `apex_serving_weights_step`, phase timings
ride `apex_serving_reload_duration_seconds{phase}`, and `bench.py`'s
`serving_reload` block records the honest swap pause (p99 step-time
inflation during a mid-traffic reload) in `PERF_NOTES.md`.  Call
`reloader.prefetch()` whenever the server is idle and the restore is
paid off the serving path — the boundary `reload()` consumes the
staged candidate and the pause drops to the pointer swap alone.

Survive a replica crash without dropping a stream — one engine is one
blast radius; a fleet router in front of N replicas turns a replica
death into a per-stream failover instead of N×slots dropped requests
([full page](api/serving.md)):

```python
from apex_tpu import serving as sv

replicas = {f"r{i}": sv.ContinuousBatchingScheduler(
                engines[i], max_queue=64,
                prefix_caching=sv.PrefixCacheConfig())
            for i in range(3)}
router = sv.FleetRouter(replicas, config=sv.FleetConfig(
    suspect_after_s=1.0,   # missed beats -> no new placements
    dead_after_s=3.0,      # -> declared dead, streams evacuated
    weights={"r0": 2.0}))  # smooth WRR when affinity has no opinion

out = sv.LoadGenerator(router, wl).run()   # the scheduler surface,
                                           # fleet-wide

router.drain("r1")      # rolling reload: move streams off, replica
...                     # stays open — reload it idle, then
router.rejoin("r1")     # WRR credits reset, takes traffic again
```

Placement is prefix-affinity first (a replica already holding the
prompt's cached blocks wins — probed read-only, never mutating cache
state), smooth WRR otherwise, with `QueueFull` retried on the
next-best replica before anything is shed.  Health is a heartbeat on
the fleet's shared clock: a wedged replica walks HEALTHY → SUSPECT →
DEAD and the watchdog evacuates its streams by preempt-capture — a
victim resumes on a survivor **bit-exactly** mid-stream
(`finish_reason="preempted-resumed"`); a hard kill re-queues victims
and deterministic sampling replays them token-identically.  A killed
replica releases every prefix pin and paged block it held.  Chaos
rides the same hooks (`KillReplica`, `WedgeReplica`, `SlowReplica`
from `resilience.fault_injection`); the tier-1 acceptance run kills a
replica mid-stream under 2x overload and requires token-identical
victims plus strictly better goodput than the same chaos without
failover.  The fleet publishes `apex_serving_fleet_*` metrics
(healthy-replica gauge, per-replica routing, failovers by mode, the
failure→resume latency histogram); `bench.py`'s `serving_fleet` block
records the measured failover latency and the failover-on vs -off
goodput delta in `PERF_NOTES.md`.

Upgrade the fleet with zero dropped streams — a rolling, health-gated
weight upgrade with a canary replica and automatic fleet rollback
([full page](api/serving.md)):

```python
from apex_tpu import serving as sv
from apex_tpu import obs

reloaders = {name: sv.HotReloader(sched, ckpt_root, like=state,
                                  params_key="params",
                                  current_step=100)
             for name, sched in replicas.items()}
with obs.recording_requests(clock=clock) as rec:
    ctl = sv.RollingReloadController(
        router, reloaders,
        config=sv.RolloutConfig(
            health_window_steps=2,     # clean steps between waves
            canary_fraction=0.25,      # pinned to the first upgrade
            canary_window_steps=16,    # then the gate decides
            gate=sv.CanaryGate(tpot_ratio=1.5)),
        recorder=rec)
    ctl.start(step=200)                # newest committed by default
    out = sv.LoadGenerator(router, wl, step_hook=ctl).run()

assert ctl.state == "promoted"         # or "aborted" + abort_reason
assert set(router.weights_steps.values()) == {200}
```

Per replica the controller runs `prefetch()` (restore+validate
off-path) → `drain()` (streams move to survivors losslessly) →
`reload()` (swap-only pause) → `rejoin()`, waiting for consecutive
clean HEALTHY steps between waves.  The canary serves a seeded exact
traffic fraction and must beat the old-version arms' SLO report; a
gate failure, refused candidate, or replica death halts the rollout
and rolls every upgraded replica back **bit-exactly** from its
retained previous buffer.  Mid-rollout the fleet is mixed-version:
`weights_step` rides every routed/finished event, and a captured
stream never resumes across versions (it degrades to a deterministic
same-version replay) — no hybrid streams, ever.  Chaos coverage:
`CorruptCandidateMidRollout`, `RegressingWeights` (validates clean,
serves worse — only the gate catches it), `KillCanary`.

Watch a fleet live and page on burn rate — name each replica and its
serving metrics split per replica (the unlabeled aggregates stay
byte-identical); install a request recorder and the fleet's failovers
become hop trails on a per-replica Perfetto timeline; hand the router
a deterministic alert engine and SLO burn pages at the step boundary,
on the serving clock, reproducibly
([full page](api/observability.md)):

```python
from apex_tpu import obs, serving as sv

replicas = {f"r{i}": sv.ContinuousBatchingScheduler(
                engines[i], max_queue=64, name=f"r{i}")  # replica label
            for i in range(3)}
engine = obs.AlertEngine([
    # page when a replica dies and stays down
    obs.ThresholdRule("replica_down",
                      "apex_serving_fleet_replicas_healthy",
                      "<", 3, for_duration_s=0.5),
    # page when TTFT > 250 ms burns the 99% objective at 14.4x —
    # long window confirms, short window de-flaps the resolution
    obs.BurnRateRule("goodput_burn",
                     good=obs.Selector("apex_serving_ttft_seconds",
                                       le=0.25),
                     total=obs.Selector("apex_serving_ttft_seconds"),
                     objective=0.99, long_window_s=30.0,
                     short_window_s=5.0, factor=14.4),
], clock=clock.monotonic)
router = sv.FleetRouter(replicas, alerts=engine)

with obs.recording_requests(clock=clock.monotonic) as rec:
    out = sv.LoadGenerator(router, wl).run()

print(obs.prometheus_text())     # ...{replica="r1"} series + alerts
rec.export("/tmp/fleet.trace.json")   # per-replica lanes in Perfetto
for entry in engine.ledger:      # {step, t, rule, transition, value}
    print(entry)                 # bit-identical across reruns
```

Per-replica sums reconcile exactly against the aggregates (same
events, dual-written), a killed replica's streams render as residency
spans migrating to the survivor lane, and the firing→resolved ledger
is pinned bit-identical across reruns in tier-1.  `bench.py`'s
`obs_fleet` block keeps the whole layer honest: instrumented-vs-bare
chaos-drain overhead ≤ 1.10×, alert evaluation µs/step at 32 rules,
and trace-export wall.

End-to-end runnable versions: `examples/simple/main.py` (amp + FusedAdam),
`examples/imagenet/main.py` (DDP + SyncBatchNorm + checkpointing),
`examples/gpt/pretrain.py` (tp × pp × dp GPT), `examples/llama/pretrain.py`
(3-D Llama), `examples/dcgan/main_amp.py` (two-model amp).
"""


def generate() -> dict[str, str]:
    files = {os.path.join(REPO, "docs", "index.md"): render_index()}
    for key in PAGES:
        files[os.path.join(OUT, f"{key}.md")] = render_page(key)
    return files


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="exit 1 if docs on disk are stale")
    args = ap.parse_args()

    files = generate()
    stale = []
    for path, content in files.items():
        on_disk = ""
        if os.path.exists(path):
            with open(path) as f:
                on_disk = f.read()
        if on_disk != content:
            stale.append(os.path.relpath(path, REPO))
            if not args.check:
                os.makedirs(os.path.dirname(path), exist_ok=True)
                with open(path, "w") as f:
                    f.write(content)
    if args.check and stale:
        print("stale docs (re-run tools/gen_api_docs.py):", *stale, sep="\n  ")
        sys.exit(1)
    print(f"{'checked' if args.check else 'wrote'} {len(files)} pages"
          + (f" ({len(stale)} updated)" if not args.check else ""))


if __name__ == "__main__":
    main()
