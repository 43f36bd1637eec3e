"""chip_smoke.py — does the train -> checkpoint -> serve path start on the chip?

One process, no arguments: at the full width of the one model both halves
of the repo support (``bench.py``'s ``llama-1b`` card: 22 layers, hidden
2048, 32 query / 8 KV heads, SwiGLU 5632, vocab 32000, seq 2048, batch 4,
bf16 weights, FusedAdam with bf16 moments; seeded random weights,
synthetic tokens) it runs

- *device*:   a TPU backend, or exit non-zero (JAX falls back to the CPU
              with a warning when TPU init fails — asserted, not trusted);
- *kernels*:  each training-path Pallas kernel (flash attention, RMSNorm,
              fused LM head) against its jnp reference, fwd and bwd, at
              the card's block shapes;
- *train*:    ``bench.build_training``'s jitted init + donated step, a few
              steps on a fixed batch (loss finite and falling, on the
              chip, through ``tpu_custom_call``s of all three families);
- *hand-off*: ``resilience.checkpoint.save_checkpoint`` of the trained
              params, then ``serving.load_serving_params`` — the repo's
              own route from trainer to server; one 2.2 GB checkpoint
              where the machine lets a file grow that large, consecutive
              smaller ones where it does not (``phase_handoff``);
- *serve*:    ``DecodeEngine`` + ``ContinuousBatchingScheduler`` driven by
              ``LoadGenerator`` over 6 greedy requests (one chunks, a freed
              slot is re-admitted), first-token logits against the plain
              uncached forward.

``--chips 4`` runs the same two halves on a four-chip host: the
``examples/llama/pretrain.py`` dp 2 x tp 2 step, the hand-off restored
straight onto a tp = 4 serving mesh, and the tp = 4 engine on the same
requests against a one-chip engine in the same process.  Fewer than four
chips is an error.

Any failed phase, any call site that took a jnp reference where a kernel
is expected, ``APEX_TPU_KERNELS=interpret`` on a TPU backend, or no chip
exits non-zero with no result line.  ``--rehearse`` (tiny preset,
``JAX_PLATFORMS=cpu``, Pallas interpreter) runs the same phases on the CPU
and stamps its result ``"platform": "cpu", "rehearsal": true``; it is a
flag the caller passes, never something the script falls into.

The last line of stdout is ``{"ok": true, "device": {...}}`` — the only
line with an ``ok`` key; one ``{"phase": ..., "passed": true}`` line per
phase precedes it, and ``<out>/result.json`` keeps them all.  Every
figure printed is a bring-up observation, not a benchmark.
"""

from __future__ import annotations

import argparse
import collections
import errno
import json
import os
import re
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

# the serving half of the full-width run (the training half is the card)
_FULL = dict(card="llama-1b", steps=4, slots=4, max_len=2048,
             prefill_len=512, new_tokens=32,
             # one prompt > prefill_len (chunks), 6 > slots (a freed slot
             # is re-admitted), tails on four of the six prefill buckets
             prompt_lens=(64, 200, 512, 700, 1100, 1500))
# CPU rehearsal: head dim 64, lane-aligned hidden and batch*seq % 512 == 0
# keep every kernel's shape predicate true, as at full width; heads and
# vocab divide by 4 for the --chips 4 rehearsal
_TINY = dict(card=dict(metric="llama_tiny", family="llama", layers=2,
                       hidden=512, heads=8, kv_heads=4, intermediate=1024,
                       vocab=512, seq=128, batch=4, remat=None,
                       state_dtype="bfloat16", optimizer="adam"),
             steps=3, slots=4, max_len=128, prefill_len=32, new_tokens=6,
             prompt_lens=(8, 14, 32, 40, 70, 90))


def _reference_expected(event) -> bool:
    """A call site whose shape predicate is known to fail on the smoke's
    cards: their heads are 64 wide, and the K/V read kernels of the decode
    step and of a prompt chunk want whole lane tiles
    (``serving/kv_cache.py::decode_attend`` / ``prefill_attend``; at 64
    XLA:TPU keeps ``max_len`` in the lanes and the decode kernel's operand
    would be a copy of the whole cache)."""
    return (event["op"] in ("cached_decode_attention", "kv_chunk_attention")
            and event["hd"] % 128 != 0)


_FAMILIES = {"flash_attention": "flash_attention_",
             "rms_norm": "rms_norm_",
             "fused_lm_head": "fused_lm_head_"}


class Smoke:
    """State the phases hand to each other, plus the reporting."""

    def __init__(self, args, preset, out_dir):
        self.args = args
        self.preset = preset
        self.out_dir = out_dir
        self.lines = []
        self.dispatch = []           # kernel_dispatch events, this phase

    def emit(self, line: dict, file=None) -> None:
        self.lines.append(line)
        print(json.dumps(line), file=file or sys.stdout, flush=True)

    def phase(self, name, fn) -> None:
        """Run one phase; any exception ends the run non-zero, and so does
        a call site that took a reference where a kernel is expected."""
        self.dispatch.clear()
        t0 = time.perf_counter()
        try:
            obs = fn(self) or {}
            paths = collections.Counter(
                (e["op"], e["path"]) for e in self.dispatch)
            refs = [e for e in self.dispatch if e["path"] != "pallas"
                    and not _reference_expected(e)]
            if refs:
                raise AssertionError(
                    f"call sites took the jnp reference where a kernel "
                    f"is expected: {refs[:4]}")
        except (Exception, SystemExit) as e:
            traceback.print_exc(file=sys.stderr)
            # stderr: a failed run prints no result on stdout
            self.emit({"phase": name, "passed": False,
                       "error": f"{type(e).__name__}: {e}"[:2000]},
                      file=sys.stderr)
            self.save()
            sys.exit(1)
        self.emit({"phase": name, "passed": True,
                   "seconds": round(time.perf_counter() - t0, 2),
                   "kernel_dispatch": {f"{op}:{path}": n for (op, path), n
                                       in sorted(paths.items())},
                   **obs})

    def save(self) -> None:
        os.makedirs(self.out_dir, exist_ok=True)
        with open(os.path.join(self.out_dir, "result.json"), "w") as f:
            json.dump(self.lines, f, indent=1)


def _rel_err(got, want) -> float:
    """max |got - want| over max |want|, in fp32 on the host (the two
    sides may live on different devices)."""
    import numpy as np

    got = np.asarray(got).astype(np.float32)
    want = np.asarray(want).astype(np.float32)
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-30))


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------


def phase_device(sm: Smoke) -> dict:
    import importlib.metadata as md

    import jax

    from apex_tpu.ops import _dispatch

    dev = jax.devices()[0]
    want = "cpu" if sm.args.rehearse else "tpu"
    if dev.platform != want:
        raise RuntimeError(
            f"jax found platform {dev.platform!r}, this run needs {want!r} "
            f"(no accelerator: pass --rehearse to run the CPU rehearsal)")
    if len(jax.devices()) < sm.args.chips:
        raise RuntimeError(
            f"--chips {sm.args.chips} on a machine with "
            f"{len(jax.devices())} device(s)")
    # on a TPU backend APEX_TPU_KERNELS=interpret raises here
    if not _dispatch.kernels_enabled():
        raise RuntimeError("Pallas kernels are disabled (APEX_TPU_KERNELS=0)")

    def version(pkg):
        try:
            return md.version(pkg)
        except md.PackageNotFoundError:
            return "not installed"

    sm.device = {"platform": dev.platform, "kind": dev.device_kind,
                 "count": len(jax.devices())}
    return {"device": sm.device,
            "versions": {p: version(p) for p in ("jax", "jaxlib", "libtpu")},
            "compile_cache_dir": sm.cache_dir,
            "kernels": "interpret" if _dispatch.use_interpret() else "mosaic"}


def phase_kernels(sm: Smoke) -> dict:
    """Each training-path kernel against its jnp reference, fwd and bwd.

    Shapes are the card's block shapes: one batch row of the attention
    (grid extent does not change what Mosaic compiles), the full
    ``batch * seq`` token count for the norm and the LM head.  References
    run at ``highest`` matmul precision (on a TPU the default fp32 matmul
    is a single bf16 pass — not a reference).

    Tolerance, relative to the reference's largest magnitude: the kernels
    feed bf16 operands to the MXU (the softmax tile ``p`` and the LM
    head's ``dlogits`` are rounded to bf16 before their second matmul)
    and round outputs to bf16 — 2^-9 relative per rounding, a handful of
    roundings compounding — so 2e-2 bounds them with margin, and a wrong
    tile, mask or accumulator shows up at order 1.
    """
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops import flash_attention as fa
    from apex_tpu.ops import fused_lm_head as lh
    from apex_tpu.ops import layer_norm as ln

    card = sm.card
    dtype = jnp.bfloat16
    tol = 2e-2
    hd = card["hidden"] // card["heads"]
    keys = jax.random.split(jax.random.PRNGKey(1), 8)
    errs = {}

    def check(name, kernel_fn, ref_fn, args, argnums):
        def both(fn):
            def scalar(*a):
                out = fn(*a)
                # a fixed random cotangent exercises every output element
                w = jax.random.normal(keys[7], out.shape, jnp.float32)
                return jnp.sum(out.astype(jnp.float32) * w), out
            (_, out), grads = jax.jit(jax.value_and_grad(
                scalar, argnums=argnums, has_aux=True))(*args)
            return out, grads

        out_k, g_k = both(kernel_fn)
        with jax.default_matmul_precision("highest"):
            out_r, g_r = both(ref_fn)
        errs[f"{name}_fwd"] = _rel_err(out_k, out_r)
        for i, (gk, gr) in enumerate(zip(g_k, g_r)):
            errs[f"{name}_bwd{i}"] = _rel_err(gk, gr)

    # flash attention: [1, heads, seq, hd], GQA already repeated (the
    # model repeats kv heads before the kernel)
    shape = (1, card["heads"], card["seq"], hd)
    q, k, v = (jax.random.normal(keys[i], shape, jnp.float32).astype(dtype)
               for i in range(3))
    check("flash_attention",
          lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
          lambda q, k, v: fa.mha_reference(q, k, v, causal=True),
          (q, k, v), (0, 1, 2))

    # RMSNorm: [batch * seq, hidden] activations, fp32 scale
    rows = card["batch"] * card["seq"]
    x = jax.random.normal(keys[3], (rows, card["hidden"]),
                          jnp.float32).astype(dtype)
    w = 1.0 + 0.1 * jax.random.normal(keys[4], (card["hidden"],),
                                      jnp.float32)

    def rms_ref(x, w):
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True)
                                + 1e-5) * w
        return y.astype(x.dtype)

    check("rms_norm",
          lambda x, w: ln.fused_rms_norm_affine(x, w, (card["hidden"],),
                                                1e-5),
          rms_ref, (x, w), (0, 1))

    # fused LM head: hidden [batch * seq, h] x embedding [vocab, h]
    h = (0.5 * jax.random.normal(keys[5], (rows, card["hidden"]),
                                 jnp.float32)).astype(dtype)
    e = (0.02 * jax.random.normal(keys[6], (card["vocab"], card["hidden"]),
                                  jnp.float32)).astype(dtype)
    labels = jax.random.randint(keys[2], (rows,), 0, card["vocab"])
    check("fused_lm_head",
          lambda h, e: lh.fused_lm_head_loss(h, e, labels),
          lambda h, e: lh.lm_head_loss_reference(h, e, labels),
          (h, e), (0, 1))

    bad = {k: v for k, v in errs.items() if not v <= tol}
    if bad:
        raise AssertionError(f"kernel/reference mismatch beyond {tol}: "
                             f"{bad} (all: {errs})")
    return {"tolerance": tol,
            "rel_err": {k: float(f"{v:.3g}") for k, v in errs.items()}}


def phase_train(sm: Smoke) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bench

    card = sm.card
    model, ids, labels, init_all, train_step = bench.build_training(
        card, jnp.bfloat16)
    sm.model = model
    t0 = time.perf_counter()
    params, opt_state = init_all(ids)
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0

    lowered = train_step.lower(params, opt_state, ids, labels)
    names = collections.Counter(
        re.findall(r'kernel_name = "([^"]+)"', lowered.as_text()))
    families = {fam: sum(n for k, n in names.items() if k.startswith(pre))
                for fam, pre in _FAMILIES.items()}
    if not sm.args.rehearse:
        # the interpreter lowers to plain HLO; on the chip a family with
        # no tpu_custom_call means a reference path ran
        missing = [fam for fam, n in families.items() if n < 1]
        if missing:
            raise AssertionError(
                f"no tpu_custom_call in the lowered step for {missing} "
                f"(found {dict(names)})")
    t0 = time.perf_counter()
    step = lowered.compile()
    compile_s = time.perf_counter() - t0

    losses = []
    t0 = time.perf_counter()
    params, opt_state, loss = step(params, opt_state, ids, labels)
    losses.append(float(loss))
    first_step_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(sm.preset["steps"] - 1):
        params, opt_state, loss = step(params, opt_state, ids, labels)
        losses.append(float(loss))
    steady_s = (time.perf_counter() - t0) / (sm.preset["steps"] - 1)

    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    on = {d.platform for d in loss.devices()}
    if on != {sm.device["platform"]}:
        raise AssertionError(f"loss lives on {on}, not on the "
                             f"{sm.device['platform']}")
    stats = jax.devices()[0].memory_stats() or {}
    # the allocator's peak counts live buffers; the step's scratch is in
    # the compiled program's own accounting
    mem = step.memory_analysis()
    sm.params = params
    sm.trained_steps = sm.preset["steps"]
    del opt_state
    return {"card": card,
            "params_m": round(sum(int(np.prod(p.shape)) for p in
                                  jax.tree.leaves(params)) / 1e6, 1),
            "tpu_custom_calls": families,
            "init_seconds": round(init_s, 2),
            "compile_seconds": round(compile_s, 2),
            "first_step_seconds": round(first_step_s, 3),
            "steady_step_seconds": round(steady_s, 4),
            "losses": [round(x, 4) for x in losses],
            "loss_device": sorted(str(d) for d in loss.devices()),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "compiled_step_bytes": {
                k: getattr(mem, f"{k}_size_in_bytes", None)
                for k in ("argument", "output", "alias", "temp")}}


def phase_train4(sm: Smoke) -> dict:
    """The ``examples/llama/pretrain.py`` dp x tp step on four chips."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, os.path.join(ROOT, "examples", "llama"))
    import bench
    import pretrain

    c = sm.card
    devices = jax.devices()
    argv = ["--layers", c["layers"], "--hidden", c["hidden"],
            "--heads", c["heads"], "--kv-heads", c["kv_heads"],
            "--ffn", c["intermediate"], "--vocab", c["vocab"],
            "--seq", c["seq"], "--batch", c["batch"],
            "--tp", 2, "--steps", sm.preset["steps"], "--lr", 1e-3,
            "--bf16"]
    t0 = time.perf_counter()
    params, first, last = pretrain.train_2d(
        pretrain.parse_args([str(a) for a in argv]))
    wall = time.perf_counter() - t0
    mem = pretrain.device_memory(devices)
    peaks = [peak for _, peak in mem.values()]
    # equal shards, equal work: per-device peaks within a stated factor
    factor = 1.25
    if peaks and max(peaks) > factor * min(peaks):
        raise AssertionError(
            f"per-device peak memory spread beyond {factor}x: {mem}")
    # the serving half wants the unsharded model object of the same card
    sm.model = bench.build_training(c, jnp.bfloat16)[0]
    sm.params = params
    sm.trained_steps = sm.preset["steps"]
    return {"mesh": {"dp": len(devices) // 2, "tp": 2},
            "loss_first": round(first, 4), "loss_last": round(last, 4),
            "wall_seconds_with_compile": round(wall, 2),
            "peak_factor_bound": factor,
            "peak_factor": (round(max(peaks) / min(peaks), 3)
                            if peaks else None),
            "per_device_peak_bytes": {str(k): v[1] for k, v in mem.items()},
            "params_m": round(sum(int(np.prod(p.shape)) for p in
                                  jax.tree.leaves(params)) / 1e6, 1)}


def _file_bound(out_dir: str) -> dict:
    """What one file under ``out_dir`` may hold.  ``RLIMIT_FSIZE`` is the
    process's own cap — a write past it is ``EFBIG``, which is how the
    one-file 2.2 GB hand-off first failed on a checking machine (PR 21) —
    and a checkpoint also has to leave room on the disk for the compile
    cache, hence half the free space."""
    import resource

    soft, _ = resource.getrlimit(resource.RLIMIT_FSIZE)
    os.makedirs(out_dir, exist_ok=True)
    free = shutil.disk_usage(out_dir).free
    rlimit = None if soft == resource.RLIM_INFINITY else int(soft)
    return {"rlimit_fsize": rlimit, "disk_free": free,
            "bound": free // 2 if rlimit is None else min(rlimit, free // 2)}


def _next_group(sizes, start, bound) -> list:
    """The longest run of leaves from ``start`` whose bytes together stay
    within ``bound`` (a checkpoint is ONE ``data.bin`` of exactly its
    leaves' bytes); empty when leaf ``start`` alone exceeds it."""
    idx, total = [], 0
    for i in range(start, len(sizes)):
        if total + sizes[i] > bound:
            break
        idx.append(i)
        total += sizes[i]
    return idx


def _subtree(paths, leaves, idx) -> dict:
    """The nested-dict tree holding ``leaves[i] for i in idx`` at their
    own paths: with every index it is the params tree itself."""
    out = {}
    for i in idx:
        node = out
        for k in paths[i][:-1]:
            node = node.setdefault(k.key, {})
        node[paths[i][-1].key] = leaves[i]
    return out


# the machine's answer to "this file does not fit", as against a broken write
_NO_ROOM = (errno.EFBIG, errno.ENOSPC, errno.EDQUOT)


def phase_handoff(sm: Smoke) -> dict:
    """Trained params -> ``save_checkpoint`` -> ``load_serving_params``.

    Where the machine lets one file hold them, that is one checkpoint of
    ``{"params": params}``, as a trainer writes it.  Where it does not
    (``_file_bound``, or the write itself refused for want of room: the
    bound then halves), the same tree goes over in consecutive checkpoints
    of as many whole leaves as fit, each restored and removed before the
    next is written; a leaf that no file here can hold is handed over in
    memory and named in the phase line.  Nothing is dropped without a
    word, and a run that could checkpoint nothing fails.
    """
    import jax
    import jax.numpy as jnp

    from apex_tpu import serving as sv
    from apex_tpu.resilience import checkpoint as ckpt

    root = os.path.join(sm.out_dir, "ckpt")
    shutil.rmtree(root, ignore_errors=True)
    trained = sm.params
    flat, treedef = jax.tree_util.tree_flatten_with_path(trained)
    paths = [p for p, _ in flat]
    leaves = [x for _, x in flat]
    keys = [jax.tree_util.keystr(p) for p in paths]
    sizes = [x.nbytes for x in leaves]
    shardings = None
    if sm.args.chips > 1:
        from apex_tpu.utils.compat import serving_mesh
        shardings = jax.tree.leaves(sv.tp_param_shardings(
            jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype),
                         trained), serving_mesh(sm.args.chips)))
    limit = _file_bound(sm.out_dir)
    bound = limit["bound"]

    served, in_memory, refused, file_bytes = {}, [], [], []
    save_s = load_s = 0.0
    start = 0
    try:
        while start < len(leaves):
            idx = _next_group(sizes, start, bound)
            if not idx:
                in_memory.append(start)
                start += 1
                continue
            part = os.path.join(root, f"part_{len(file_bytes):02d}")
            sub = _subtree(paths, leaves, idx)
            t0 = time.perf_counter()
            try:
                path = ckpt.save_checkpoint(part, sm.trained_steps,
                                            {"params": sub}, keep=1)
            except OSError as e:
                if e.errno not in _NO_ROOM:
                    raise
                nbytes = sum(sizes[i] for i in idx)
                refused.append({"bytes": nbytes, "error": str(e)})
                bound = nbytes // 2
                continue
            save_s += time.perf_counter() - t0
            file_bytes.append(os.path.getsize(os.path.join(path, "data.bin")))
            like = {"params": jax.tree.map(
                lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), sub)}
            t0 = time.perf_counter()
            got, step = sv.load_serving_params(
                part, like, params_key="params",
                shardings=(None if shardings is None
                           else _subtree(paths, shardings, idx)))
            jax.block_until_ready(got)
            load_s += time.perf_counter() - t0
            if step != sm.trained_steps:
                raise AssertionError(f"restored step {step}, saved "
                                     f"{sm.trained_steps}")
            for p, x in jax.tree_util.tree_flatten_with_path(got)[0]:
                served[jax.tree_util.keystr(p)] = x
            # 2.2 GB at full width: never left behind, never copied back
            shutil.rmtree(part, ignore_errors=True)
            start = idx[-1] + 1
    finally:
        shutil.rmtree(root, ignore_errors=True)
    if not file_bytes:
        raise RuntimeError(f"no leaf of the params fits a file here: "
                           f"{limit}, refused {refused}")
    for i in in_memory:
        served[keys[i]] = (leaves[i] if shardings is None
                           else jax.device_put(leaves[i], shardings[i]))
    served = jax.tree_util.tree_unflatten(treedef, [served[k] for k in keys])
    same = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)),
                        trained, served)
    if not all(jax.tree.leaves(same)):
        raise AssertionError("restored params differ from the trained ones")
    sm.params = served
    return {"bytes": sum(file_bytes), "checkpoints": len(file_bytes),
            "largest_file_bytes": max(file_bytes), "file_bound": limit,
            "refused_writes": refused,
            "in_memory_leaves": [keys[i] for i in in_memory],
            "in_memory_bytes": sum(sizes[i] for i in in_memory),
            "save_seconds": round(save_s, 2),
            "load_seconds": round(load_s, 2),
            "leaves": len(leaves),
            "restored_onto": ("one device" if shardings is None else
                              f"tp={sm.args.chips} serving mesh")}


def _requests(sm: Smoke):
    import numpy as np

    from apex_tpu import serving as sv

    rng = np.random.default_rng(0)
    vocab = sm.card["vocab"]
    prompts = [[int(t) for t in rng.integers(0, vocab, n)]
               for n in sm.preset["prompt_lens"]]
    return prompts, sv.make_workload(
        prompts, (0.0,) * len(prompts),
        max_new_tokens=sm.preset["new_tokens"], rid_prefix="smoke")


def _serve(sm: Smoke, eng) -> dict:
    """Drain the six requests through scheduler + load generator on
    ``eng`` and check them; returns observations."""
    from apex_tpu import serving as sv

    prompts, wl = _requests(sm)
    sched = sv.ContinuousBatchingScheduler(eng)
    t0 = time.perf_counter()
    out = sv.LoadGenerator(sched, wl).run()
    wall = time.perf_counter() - t0
    vocab = sm.card["vocab"]
    if out.completed != len(prompts) or out.rejected:
        raise AssertionError(
            f"{out.completed}/{len(prompts)} requests served, "
            f"{len(out.rejected)} rejected")
    for rid, res in out.results.items():
        if res.finish_reason not in sv.SERVED_REASONS:
            raise AssertionError(f"{rid}: finish_reason "
                                 f"{res.finish_reason!r}")
        if len(res.tokens) != sm.preset["new_tokens"]:
            raise AssertionError(f"{rid}: {len(res.tokens)} tokens")
        if not all(0 <= t < vocab for t in res.tokens):
            raise AssertionError(f"{rid}: token id outside [0, {vocab})")
    if eng.decode_compiles() != 1:
        raise AssertionError(f"decode compiled {eng.decode_compiles()}x")
    if eng.prefill_compiles() > len(eng.prefill_buckets):
        raise AssertionError(
            f"prefill compiled {eng.prefill_compiles()}x for "
            f"{len(eng.prefill_buckets)} buckets")
    sched.close()
    return {"requests": len(prompts), "scheduler_steps": out.steps,
            "wall_seconds_with_compile": round(wall, 2),
            "decode_compiles": eng.decode_compiles(),
            "prefill_compiles": eng.prefill_compiles(),
            "prefill_buckets": list(eng.prefill_buckets),
            "first_tokens": {rid: res.tokens[0]
                             for rid, res in sorted(out.results.items())}}


# first-token logits, engine (fp32 cached attention over the max_len
# extent, bf16 weights) against another evaluation of the same function:
# relative to the largest |logit|.  Every matmul feeds bf16 operands to the
# MXU and activations round to bf16 between layers (2^-9 relative each);
# the two sides differ in attention algorithm (flash tiles vs a
# materialized fp32 softmax) and reduction extent (prompt length vs
# max_len), or in psum order under tp, so roundings land differently and
# compound over 22 layers.  5e-2 bounds that with margin; a wrong mask,
# rope offset or cache row moves logits at order 1.
_LOGIT_TOL = 5e-2


def phase_serve(sm: Smoke) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu import serving as sv

    p = sm.preset
    eng = sv.DecodeEngine(sm.model, sm.params, slots=p["slots"],
                          max_len=p["max_len"],
                          prefill_len=p["prefill_len"])
    obs = _serve(sm, eng)

    # every request's first-token logits — the chunked prompts included —
    # against the plain uncached forward.  One compiled program serves all
    # six: the prompt is padded to max_len (causal attention: row n-1 does
    # not see the padding), which also keeps the flash kernel's shape
    # predicate true at every prompt length.
    forward = jax.jit(lambda params, ids: sm.model.apply(params, ids))
    errs, agree = [], []
    for i, prompt in enumerate(_requests(sm)[0]):
        eng.reset()
        first = eng.prefill(0, prompt)
        if not bool(jnp.all(jnp.isfinite(first))):
            raise AssertionError(f"request {i}: non-finite logits")
        if int(jnp.argmax(first)) != obs["first_tokens"][f"smoke{i}"]:
            raise AssertionError(
                f"request {i}: the scheduler's first token is not the "
                f"argmax of the engine's first-token logits")
        ids = np.zeros((1, p["max_len"]), np.int32)
        ids[0, :len(prompt)] = prompt
        ref = forward(sm.params, ids)[len(prompt) - 1, 0]
        errs.append(_rel_err(first, ref))
        agree.append(int(jnp.argmax(first)) == int(jnp.argmax(ref)))
    if not max(errs) <= _LOGIT_TOL:
        raise AssertionError(
            f"first-token logits differ from the uncached forward by "
            f"{errs} of max |logit| (tolerance {_LOGIT_TOL})")
    return {**obs,
            "logit_rel_err_vs_uncached": [float(f"{e:.3g}") for e in errs],
            "logit_tolerance": _LOGIT_TOL, "argmax_agrees": agree}


def phase_serve4(sm: Smoke) -> dict:
    """The tp = chips engine on the same requests, and its first-token
    logits against a one-chip engine over the same weights."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import serving as sv

    p = sm.preset
    kw = dict(slots=p["slots"], max_len=p["max_len"],
              prefill_len=p["prefill_len"])
    eng = sv.DecodeEngine(sm.model, sm.params,
                          tp=sv.TPConfig(size=sm.args.chips), **kw)
    obs = _serve(sm, eng)
    # the longest prompt: it chunks, and its chunks all pad to the full
    # prefill_len bucket — one compile on the one-chip side
    prompt = _requests(sm)[0][-1]
    eng.reset()
    first = eng.prefill(0, prompt)
    one = sv.DecodeEngine(
        sm.model, jax.device_put(sm.params, jax.devices()[0]), **kw)
    ref = one.prefill(0, prompt)
    err = _rel_err(first, ref)
    if not (err <= _LOGIT_TOL and bool(jnp.all(jnp.isfinite(first)))):
        raise AssertionError(
            f"tp={sm.args.chips} first-token logits differ from the "
            f"one-chip engine by {err:.3g} of max |logit| "
            f"(tolerance {_LOGIT_TOL})")
    mem = {str(d.id): (d.memory_stats() or {}).get("peak_bytes_in_use")
           for d in jax.devices()}
    return {**obs, "tp": sm.args.chips,
            "logit_rel_err_vs_one_chip": float(f"{err:.3g}"),
            "logit_tolerance": _LOGIT_TOL,
            "argmax_agrees": int(jnp.argmax(first)) == int(jnp.argmax(ref)),
            "per_device_peak_bytes": mem}


_PHASES = {
    1: (("device", phase_device), ("kernels", phase_kernels),
        ("train", phase_train), ("hand-off", phase_handoff),
        ("serve", phase_serve)),
    4: (("device", phase_device), ("train", phase_train4),
        ("hand-off", phase_handoff), ("serve", phase_serve4)),
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny preset on the CPU with the Pallas "
                    "interpreter; the result is stamped as a rehearsal")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: dp 2 x tp 2 training and tp = 4 serving; "
                    "fewer than four devices is an error")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the card's depth (debugging; the standing "
                    "proof runs the card as it is)")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "chip_smoke"),
                    help="directory for result.json and the checkpoint(s) "
                    "of the hand-off phase (removed after the restore)")
    args = ap.parse_args(argv)

    if args.rehearse:
        # before jax is imported: the rehearsal owns its platform
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["APEX_TPU_KERNELS"] = "interpret"
        if args.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.chips}")

    sys.path.insert(0, ROOT)
    import bench
    from apex_tpu import _logging
    from apex_tpu.utils.compile_cache import enable_compile_cache

    preset = dict(_TINY if args.rehearse else _FULL)
    sm = Smoke(args, preset, args.out)
    sm.cache_dir = enable_compile_cache(ROOT)
    card = preset["card"]
    sm.card = dict(card if isinstance(card, dict) else bench._CONFIGS[card])
    if args.layers:
        sm.card["layers"] = args.layers

    def sink(event):
        if event.get("event") == "kernel_dispatch":
            sm.dispatch.append(event)

    _logging.add_event_sink(sink)
    try:
        for name, fn in _PHASES[args.chips]:
            sm.phase(name, fn)
    finally:
        _logging.remove_event_sink(sink)

    native = sys.modules.get("apex_tpu.utils._native")
    sm.emit({"native_packing": ("not loaded (csrc/packing.cpp is off this "
                                "path: packed optimizers are opt-in)"
                                if native is None or native._lib is None
                                else "loaded")})
    result = {"ok": True, "device": sm.device}
    if args.rehearse:
        result["rehearsal"] = True
    sm.emit(result)
    sm.save()


if __name__ == "__main__":
    main()
