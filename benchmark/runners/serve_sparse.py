"""Serving runner for the dots3-note family (latent attention with a learned
key selector on the full layers and a window on the others, sigmoid-routed
gated experts): ``runners/serve.py``'s closed loop, window and checks, run
on ``Dots3NoteForCausalLM`` and compared with ``reference/dots3.py``.

Cells: ``dots3-note-ep8-l5.longdoc-closed`` (and the tests'
``tiny-dots3-note.tiny-longdoc-closed``).  ``serve.py`` and
``serve_hybrid.py`` are the yardstick and are not edited; as
``serve_hybrid.py`` says of itself, ``run`` repeats ``serve.py``'s body with
this family's model, weights and reference check (PERF.md section 7 asks a
``benchmark`` PR to make them arguments of one ``run``).  The loop, the
warm-up, the error measure, the percentile, the removal of the shared
direction and the expert counters are imported.

Counters it adds to ``serve_hybrid.py``'s (``moe_*``, ``itl_p95_ms``):
``index_rows``, ``attended_rows``, ``window_rows`` -
``engine.rows_read()`` after the drain less after the warm-up: host-side
sums, over every decode step of ramp, window and drain, of the selector keys
scored, the latent rows the selection left to attend and the window rows
read (the same counts ride each ``engine.decode`` span).
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.lib import traffic as tf
from benchmark.lib.stats import percentile
from benchmark.runners.serve import SPANS, rel_err, warm_up
from benchmark.runners.serve_hybrid import (
    BALANCE_STEPS,
    BALANCE_TOKENS,
    moe_counts,
    without_shared_direction,
)

# the published keys Dots3NoteConfig takes under their own names
KEYS = ("vocab_size", "hidden_size", "intermediate_size",
        "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "rope_theta", "index_n_heads", "index_head_dim", "index_topk",
        "swa_num_attention_heads", "swa_q_lora_rank", "swa_kv_lora_rank",
        "swa_qk_nope_head_dim", "swa_qk_rope_head_dim", "swa_v_head_dim",
        "swa_rope_theta", "sliding_window_size", "num_experts_per_tok",
        "moe_intermediate_size", "n_shared_experts", "routed_scaling_factor",
        "rms_norm_eps", "apply_mla_qkv_lora_rescale")


def build_model(config: dict):
    import jax.numpy as jnp

    from apex_tpu.models.dots3 import Dots3NoteConfig, Dots3NoteForCausalLM

    for key in ("attention_gate_type", "swa_attention_gate_type"):
        if config[key] != "headwise":
            raise ValueError(f"{key} {config[key]!r}: the model gates by head")
    return Dots3NoteForCausalLM(Dots3NoteConfig(
        **{k: config[k] for k in KEYS},
        layer_types=tuple(config["layer_types"]),
        # the router is as wide as the published model; the file's
        # n_routed_experts counts the experts held here
        n_routed_experts=config["published"]["n_routed_experts"],
        experts_held=tuple(config["experts_held"])),
        params_dtype=jnp.dtype(config["assumed"]["weights_dtype"]))


def make_params(model, config: dict, seed: int):
    """Seeded weights drawn on the device in one jitted call, as the
    configuration's ``assumed.weights`` says: matrices normal x 0.02 in the
    type the model declares them in (the router's float32), norm scales 1,
    biases 0; then routers, selection biases and the head as
    :func:`route_as_trained` leaves them.  The key is an argument: a seed
    baked into the program would compile a new one for every seed."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def draw(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name = str(getattr(path[-1], "key", path[-1]))
            if name == "scale":
                out.append(jnp.ones(leaf.shape, leaf.dtype))
            elif "bias" in name:
                out.append(jnp.zeros(leaf.shape, leaf.dtype))
            else:
                out.append(0.02 * jax.random.normal(
                    jax.random.fold_in(key, i), leaf.shape, leaf.dtype))
        return jax.tree.unflatten(treedef, out)

    params = draw(jax.random.key(seed % (2 ** 31 - 1), impl="rbg"))
    return route_as_trained(params, config, seed)


def route_as_trained(params, config: dict, seed: int):
    """``serve_hybrid.route_as_trained`` for this family's 256-wide routers:
    in one walk of the plain float32 reference over a seeded calibration
    sequence, each expert layer's router loses the direction that the rows
    it reads share and its selection bias takes ``BALANCE_STEPS`` sign steps
    towards equal loads; the head loses the shared direction of the final
    normed rows.  The selector is left as drawn.  System and reference read
    the same tree."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import dots3 as ref

    k = config["num_experts_per_tok"]
    held = config["experts_held"][0]
    ids = np.random.default_rng(seed).integers(
        0, config["vocab_size"], BALANCE_TOKENS).astype(np.int32)

    @jax.jit
    def balance(scores):
        tokens, experts = scores.shape

        def step(i, bias):
            _, chosen = jax.lax.top_k(scores + bias, k)
            load = jnp.zeros((experts,), jnp.float32).at[
                chosen.reshape(-1)].add(1.0)
            return bias + 0.05 * 0.955 ** i * jnp.sign(
                tokens * k / experts - load)

        return jax.lax.fori_loop(0, BALANCE_STEPS, step,
                                 jnp.zeros((experts,), jnp.float32))

    tree = dict(params["params"])
    with jax.default_matmul_precision("highest"):
        x = ref.embed(params, ids)
        for i, kind in enumerate(config["layer_types"]):
            layer = tree[f"layers_{i}"]
            x = x + ref.attention_out(x, layer, config, kind)
            h = ref.normed(x, layer["post_attention_layernorm"], config)
            if i >= config["first_k_dense_replace"]:
                kernel = without_shared_direction(
                    layer["mlp"]["router_kernel"], h, axis=0)
                bias = balance(ref.router_scores(h, kernel))
                layer = tree[f"layers_{i}"] = dict(layer, mlp=dict(
                    layer["mlp"], router_kernel=kernel, router_bias=bias))
            x = x + ref.mlp_out(h, layer, i, config, held=held)
        tree["lm_head"] = without_shared_direction(
            tree["lm_head"], ref.normed(x, tree["norm"], config), axis=1)
    return {"params": tree}


def check_positions(traffic: dict) -> list:
    """The positions whose next-token logits the check compares: the last
    row of each chunk of the check's prompt (the engine hands back a
    chunk's last logits: the last of them is the first token's), then each
    greedy token."""
    spec, chunk = traffic["check"], traffic["engine"]["prefill_len"]
    n = spec["prompt_len"]
    ends = [min(start + chunk, n) - 1 for start in range(0, n, chunk)]
    return ends + [n + i for i in range(spec["decode_tokens"])]


def check_against_reference(engine, config, traffic, seed) -> dict:
    """``serve.py``'s check with this family's reference, a long prompt and
    every logit the engine hands back on the way: ``prompt_len`` tokens
    (twice ``index_topk``, eight windows, four chunks at the real size)
    through the timed engine's chunk programs, then ``decode_tokens`` greedy
    steps through its cache, against the plain float32 forward over prompt +
    decoded tokens with the same experts held.

    ``reference_ok`` is decided by ONE number, the norm of the difference
    over the norm of the reference **over all those logits stacked**
    (:func:`check_positions`: each chunk's last row and each decode step,
    twelve vectors at the real size).  The two readings the other serving
    cells decide by (the first token's, the last decode step's) are printed
    beside it and decide nothing here: with seeded weights the selector is
    no guide to the attention, so one key that flips at the margin of a
    top-2,048 can carry a tenth of a head's weight: single positions read
    2-44 % over thirty-seven seeds on the chip, the stacked number 6.9-15.3 %
    (``tolerance_why`` in ``traffic/longdoc-closed.json``; PERF.md section
    6, PR 31)."""
    import jax.numpy as jnp

    from benchmark.reference import dots3

    spec = traffic["check"]
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, config["vocab_size"],
                          spec["prompt_len"]).tolist()
    # engine.prefill's loop, keeping every chunk's logits (slot 0 is free
    # after the drain)
    got = [engine.prefill_chunk(0, prompt[start:start + engine.prefill_len])
           for start in range(0, len(prompt), engine.prefill_len)]
    seq = list(prompt)
    active = np.zeros((engine.slots,), bool)
    active[0] = True
    for _ in range(spec["decode_tokens"]):
        seq.append(int(jnp.argmax(got[-1])))
        tokens = np.zeros((engine.slots,), np.int32)
        tokens[0] = seq[-1]
        got.append(engine.decode(tokens, active)[0])
    at = check_positions(traffic)
    want = dots3.logits_at(engine.params, np.asarray(seq, np.int32), at,
                           config, held=config["experts_held"][0])
    engine.release(0)
    each = [rel_err(g, w) for g, w in zip(got, want)]
    whole = rel_err(np.stack([np.asarray(g) for g in got]), want)
    first = at.index(len(prompt) - 1)
    return {"reference_rel_err": whole,
            "reference_rel_err_first_token": each[first],
            "reference_rel_err_after_decode": each[-1],
            "reference_rel_err_each": [float(f"{e:.3g}") for e in each],
            "reference_tolerance": spec["tolerance"],
            "reference_ok": bool(whole <= spec["tolerance"])}


def run(ctx) -> dict:
    import jax

    from apex_tpu import serving as sv

    config, traffic = ctx.config, ctx.traffic
    if traffic["loop"] != "closed":
        raise ValueError(f"traffic loop {traffic['loop']!r}: this runner "
                         f"drives closed loops")
    vocab = config["vocab_size"]
    model = build_model(config)
    params = make_params(model, config, ctx.seed)
    jax.block_until_ready(params)
    ctx.setup.mark("init")
    engine = sv.DecodeEngine(model, params, **traffic["engine"])
    sched = sv.ContinuousBatchingScheduler(engine, clock=time.perf_counter)
    ctx.setup.mark("engine")

    def make_request(rid, spec):
        return sv.Request(rid, spec.prompt, spec.max_new_tokens)

    warm_up(sched, engine, make_request)
    counts_before = {**moe_counts(engine), **engine.rows_read()}
    ctx.setup.mark("warmup_trace_compile_or_cache_load")
    compiles_before = (engine.decode_compiles(), engine.prefill_compiles())

    tracer = ctx.tracer(SPANS)

    def on_open():
        ctx.setup.mark("ramp")
        ctx.setup.window_opens()
        tracer.window_opens(time.perf_counter())
        ctx.compiles.active = True

    rec = tf.run_closed_loop(
        sched, tf.request_stream(traffic, vocab, ctx.seed),
        clients=traffic["clients"], clock=time.perf_counter,
        window_s=ctx.seconds, make_request=make_request, on_open=on_open,
        on_step=tracer.poll, span=tracer.span)
    ctx.compiles.active = False
    tracer.stop()
    device = ctx.device_report()

    window_steps = [n for t, n, _ in rec.steps if rec.in_window(t)]
    decode_lanes = [d for t, _, d in rec.steps if rec.in_window(t)]
    window_s = rec.t_close - rec.t_open
    done_in = [r for r in rec.served if rec.in_window(r.t_done)]
    whole = [r for r in done_in if rec.in_window(r.t_submit)]
    attempted = [r for r in rec.served if rec.in_window(r.t_submit)]

    def failed(r) -> bool:
        res = r.result
        return (res.finish_reason not in sv.SERVED_REASONS
                or len(res.tokens) != r.spec.max_new_tokens
                or not all(0 <= t < vocab for t in res.tokens))

    n_failed = sum(failed(r) for r in attempted)
    # a request here takes tens of seconds (a long prompt, a few hundred
    # tokens): a window may hold none that was both submitted and finished
    # in it, and then the finished ones' first tokens are what there is
    ttfts = ([r.result.ttft_s for r in whole]
             or [r.result.ttft_s for r in done_in])
    times = [t for t, _, _ in rec.steps if rec.in_window(t)]
    step_ms = [1e3 * (b - a) for a, b in zip(times, times[1:])]
    gaps = [g for r in done_in for g in r.gaps()]
    checks = {
        "decode_compiles_is_1": engine.decode_compiles() == 1,
        "prefill_compiles_within_buckets":
            engine.prefill_compiles() <= len(engine.prefill_buckets),
        "no_compile_in_window": (
            not ctx.compiles.events
            and (engine.decode_compiles(), engine.prefill_compiles())
            == compiles_before),
        "no_request_failed": n_failed == 0,
        "gaps_match_tokens": all(
            len(r.gaps()) == len(r.result.tokens) - 1 for r in rec.served),
    }
    ctx.setup.mark("window_and_drain")
    # before the check, whose own decode steps are not the traffic's
    counts = {k: v - counts_before.get(k, 0)
              for k, v in {**moe_counts(engine), **engine.rows_read()}.items()}
    checks.update(check_against_reference(engine, config, traffic, ctx.seed))
    sched.close()
    ctx.setup.mark("checks")
    ok = all(v for v in checks.values() if isinstance(v, bool))
    slots = engine.slots
    itl_p95_ms = 1e3 * percentile(gaps, 0.95)
    return {
        "correct": ok, "attempted": len(attempted), "failed": n_failed,
        "end_to_end": {
            "serve_tok_s": sum(window_steps) / window_s,
            "itl_p95_ms": itl_p95_ms},
        "counters": {
            # recorded as the per-layer itl_p95_ms.serve_tok_s, not judged:
            # a step with a 1,024-token chunk beside one without, so the
            # tail follows the order of lengths
            "itl_p95_ms": itl_p95_ms,
            "batch_occupancy": float(np.mean(decode_lanes)) / slots,
            "ttft_p90_ms": 1e3 * percentile(ttfts, 0.90),
            "steps": len(window_steps), **counts},
        "tracer": tracer, "device": device,
        "notes": {
            "checks": checks, "window_s": window_s,
            "steps_in_window": len(window_steps),
            "requests_finished_in_window": len(done_in),
            "requests_whole_in_window": len(whole),
            "gaps": len(gaps), "tokens_in_window": sum(window_steps),
            "ttft_ms": {"p50": 1e3 * percentile(ttfts, 0.5),
                        "p90": 1e3 * percentile(ttfts, 0.9),
                        "mean": 1e3 * float(np.mean(ttfts)),
                        "max": 1e3 * max(ttfts)},
            "host_step_ms": {"median": float(np.median(step_ms)),
                             "max": max(step_ms)},
            "itl_p50_ms": 1e3 * percentile(gaps, 0.5),
            "itl_p95_ms": itl_p95_ms,
            "prefill_buckets": list(engine.prefill_buckets),
            "prefill_compiles": engine.prefill_compiles(),
            "compiles_in_window": ctx.compiles.events,
            # an untraced line prints no counters: the experts' and the
            # rows' here
            "counters": counts},
    }
