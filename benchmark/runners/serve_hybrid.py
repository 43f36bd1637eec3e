"""Serving runner for the Nemotron-H family (Mamba-2 + latent routed experts
+ attention): ``runners/serve.py``'s closed loop, window and checks, run on
``NemotronHForCausalLM`` and compared with ``reference/nemotron_h.py``.

Cells: ``nemotron3-super-ep4-l11.chat-closed-64`` (and the tests'
``tiny-nemotron-h.tiny-chat-closed-hybrid``).  ``serve.py`` is the
yardstick and is not edited.  Its ``run`` builds a Llama and offers no
argument for another family, so ``run`` here repeats its body line for
line with this family's model, weights and reference check (PERF.md
section 7 asks a ``benchmark`` PR to make them arguments of one ``run``);
the loop, the warm-up, the error measure and the percentile are imported.

Counters it adds to ``serve.py``'s: ``itl_p95_ms`` (the end-to-end number
again, for a cell whose manifest entry records it per layer), and for
``reducers/counter_ratio.py`` and ``reducers/hybrid_decode_roofline.py``:
``moe_steps``, ``moe_tokens``,
``moe_pairs``, ``moe_touched``, ``moe_max_load`` - ``engine.moe_stats()``
summed over the expert layers, after the drain less after the warm-up, so
over every decode step of ramp, window and drain (the counts ride the cache
pytree on the device and are read back twice a run, never in the window).
Each is a sum over decode steps: tokens routed (active lanes), token-expert
pairs that landed on the experts held here, held experts with at least one
pair, the largest number of pairs on one expert.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.lib import traffic as tf
from benchmark.lib.stats import percentile
from benchmark.runners.serve import SPANS, rel_err, warm_up


def build_model(config: dict):
    import jax.numpy as jnp

    from apex_tpu.models.nemotron_h import (
        NemotronHConfig,
        NemotronHForCausalLM,
    )

    return NemotronHForCausalLM(NemotronHConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        hybrid_override_pattern=config["hybrid_override_pattern"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        mamba_num_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"], n_groups=config["n_groups"],
        ssm_state_size=config["ssm_state_size"],
        conv_kernel=config["conv_kernel"], chunk_size=config["chunk_size"],
        # the router is as wide as the published model; the file's
        # n_routed_experts counts the experts held here
        n_routed_experts=config["published"]["n_routed_experts"],
        experts_held=tuple(config["experts_held"]),
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_latent_size=config["moe_latent_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=config[
            "moe_shared_expert_intermediate_size"],
        routed_scaling_factor=config["routed_scaling_factor"],
        layer_norm_epsilon=config["layer_norm_epsilon"]),
        params_dtype=jnp.dtype(config["assumed"]["weights_dtype"]))


def make_params(model, config: dict, seed: int):
    """Seeded weights drawn on the device in one jitted call, as the
    configuration's ``assumed.weights`` says: matrices normal x 0.02 in the
    type the model declares them in (the router's float32), norm scales 1,
    the convolution's bias 0, Mamba-2's published initialisation for
    ``A_log``, ``dt_bias`` and ``D``; then routers, selection biases and
    the head as :func:`route_as_trained` leaves them.  The key is an
    argument: a seed baked into the program would compile a new one for
    every seed."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.nemotron_h import mamba2_init

    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    steps = {k: config[f"time_step_{k}"] for k in ("min", "max", "floor")}

    @jax.jit
    def draw(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name = str(getattr(path[-1], "key", path[-1]))
            k = jax.random.fold_in(key, i)
            if name in ("A_log", "dt_bias", "D"):
                out.append(mamba2_init(
                    k, leaf.shape, leaf.dtype, what=name,
                    dt_min=steps["min"], dt_max=steps["max"],
                    dt_floor=steps["floor"]))
            elif name == "scale":
                out.append(jnp.ones(leaf.shape, leaf.dtype))
            elif "bias" in name:
                out.append(jnp.zeros(leaf.shape, leaf.dtype))
            else:
                out.append(0.02 * jax.random.normal(k, leaf.shape,
                                                    leaf.dtype))
        return jax.tree.unflatten(treedef, out)

    params = draw(jax.random.key(seed % (2 ** 31 - 1), impl="rbg"))
    return route_as_trained(params, config, seed)


# tokens of the calibration sequence and the sign steps a layer takes: 2,048
# tokens and 100 steps leave the largest load within 6 % of the mean on
# synthetic scores as collapsed as the chip's; 4,096 and 300 did no better.
# One sequence, not a batch of shorter ones (8 x 256 read worse on the one
# seed of two that it was tried on: PERF.md section 6, PR 27)
BALANCE_TOKENS, BALANCE_STEPS = 2048, 100


def without_shared_direction(matrix, rows, axis: int):
    """``matrix`` with the direction that the calibration ``rows [tokens,
    hidden]`` share (their mean, normalised) taken out along its hidden
    ``axis``: a product with any row then leaves the shared part out and
    reads what tells that row from the others."""
    import jax.numpy as jnp

    mean = rows.astype(jnp.float32).mean(axis=0)
    unit = mean / jnp.linalg.norm(mean)
    m32 = jnp.moveaxis(matrix.astype(jnp.float32), axis, 0)
    m32 = m32 - jnp.tensordot(unit, jnp.tensordot(unit, m32, 1), 0)
    return jnp.moveaxis(m32, 0, axis).astype(matrix.dtype)


def route_as_trained(params, config: dict, seed: int):
    """Make seeded weights choose experts, and next tokens, by the token
    and not by what all tokens share, as a trained model's do.

    Seeded matrices alone do not give a deployment's routing.  The hidden
    vectors of all tokens share a direction that grows with depth (the
    squared-relu experts add a positive mean each layer: 42, 57, 70, 77 %
    of a normed row's norm at the second to fifth expert layer and 83 % at
    the head, float32 reference, real widths).  Two things follow, both
    measured on the chip (PERF.md section 6, PR 27):

    - with a zero selection bias most tokens choose the same experts: 58 %
      of held experts touched a decode step, largest load 9.7 x the mean,
      against the 94 % of independent choices that the cell's 64 lanes are
      sized for;
    - the head's product with the shared direction is the same for every
      lane, so greedy decoding sends the lanes to a few tokens (8 to 28
      distinct among 64 lanes), lanes that read the same token route
      alike, and how far that goes hangs on the seed: with a balanced
      bias alone 63-86 % touched over thirteen seeds, and ``serve_tok_s``
      0.46 % a point with it - a spread of 1.9 % over six seeds.

    So, layer by layer in one walk of the plain float32 reference over a
    seeded calibration sequence (the weights are made without the program
    under test; a layer's change moves the rows of the layers after it):
    each expert layer's router loses the shared direction of the rows it
    reads (:func:`without_shared_direction`), and its selection bias then
    takes ``BALANCE_STEPS`` steps of ``bias += step x sign(mean load -
    load)`` with a step decaying from 0.05, as the published model's
    auxiliary-loss-free balancing would leave it; the head loses the
    shared direction of the final normed rows.  Measured together on three
    seeds, 63 % among them: 93.6-93.9 % touched in every layer, largest
    load 2.83-2.87 x, 63-64 distinct tokens; the router's part alone moved
    nothing (62.6 against 65.1 %, 84.0 against 83.4 %), the head's part
    alone was not run.  System and reference read the same tree."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import nemotron_h as ref

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
        for i, kind in enumerate(config["hybrid_override_pattern"]):
            layer = tree[f"layers_{i}"]
            h = ref.normed(x, layer, config)
            if kind == "E":
                kernel = without_shared_direction(
                    layer["mixer"]["router_kernel"], h, axis=0)
                bias = balance(ref.router_scores(h, kernel))
                layer = tree[f"layers_{i}"] = dict(layer, mixer=dict(
                    layer["mixer"], router_kernel=kernel, router_bias=bias))
            x = x + ref.layer_out(kind, h, layer["mixer"], config, held=held)
        tree["lm_head"] = without_shared_direction(
            tree["lm_head"], ref.normed(x, {"norm": tree["norm_f"]}, config),
            axis=1)
    return {"params": tree}


def moe_counts(engine) -> dict:
    """``engine.moe_stats()`` summed over the expert layers."""
    return {f"moe_{k}": int(v.sum()) for k, v in engine.moe_stats().items()}


def check_against_reference(engine, config, traffic, seed) -> dict:
    """``serve.py``'s check with this family's reference: one prompt
    through prefill and ``decode_tokens`` greedy steps through the cache and
    the recurrent state; the engine's first-token logits and its logits
    after the last decoded token against the plain float32 forward over
    prompt + decoded tokens, with the same experts held."""
    import jax.numpy as jnp

    from benchmark.reference import nemotron_h

    spec = traffic["check"]
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, config["vocab_size"],
                          spec["prompt_len"]).tolist()
    first = engine.prefill(0, prompt)
    seq, logits = list(prompt), first
    active = np.zeros((engine.slots,), bool)
    active[0] = True
    for _ in range(spec["decode_tokens"]):
        seq.append(int(jnp.argmax(logits)))
        tokens = np.zeros((engine.slots,), np.int32)
        tokens[0] = seq[-1]
        logits = engine.decode(tokens, active)[0]
    n = len(prompt)
    ref = nemotron_h.logits_at(
        engine.params, np.asarray(seq, np.int32), [n - 1, len(seq) - 1],
        config, held=config["experts_held"][0])
    errs = [rel_err(first, ref[0]), rel_err(logits, ref[1])]
    engine.release(0)
    return {"reference_rel_err_first_token": errs[0],
            "reference_rel_err_after_decode": errs[1],
            "reference_tolerance": spec["tolerance"],
            "reference_ok": bool(max(errs) <= spec["tolerance"])}


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
    counts_before = moe_counts(engine)
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
    ttfts = [r.result.ttft_s for r in whole]
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
    moe = {k: v - counts_before[k] for k, v in moe_counts(engine).items()}
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
            # also a counter: the 64-client cell records it as the per-layer
            # itl_p95_ms.serve_tok_s and is not judged by it (it sits in the
            # thin tail of steps with two or three prefill calls, which the
            # seed's order of lengths moves by 3 %: PERF.md section 6)
            "itl_p95_ms": itl_p95_ms,
            # lanes of the shared decode step that emitted a token (a
            # request's first token comes from prefill and is not counted,
            # so the share cannot pass 1)
            "batch_occupancy": float(np.mean(decode_lanes)) / slots,
            # recorded, not judged (a closed loop has no queue to wait in)
            "ttft_p90_ms": 1e3 * percentile(ttfts, 0.90),
            "steps": len(window_steps), **moe},
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
            # an untraced line prints no counters: the experts' here
            "moe_counters": moe},
    }
