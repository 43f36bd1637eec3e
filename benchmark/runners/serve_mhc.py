"""Serving runner for the Xing4.0 family (multi-head latent attention over
every latent row, a residual stream of ``hc_mult`` streams mixed by
manifold-constrained hyper-connections, sigmoid-routed gated experts with a
shared expert): ``runners/serve.py``'s closed loop, window and checks, run
on ``XingForCausalLM`` and compared with ``reference/xing.py``.

Cells: ``xing4-29b-l6.mladoc-closed`` (and the tests'
``tiny-xing.tiny-mladoc-closed``).  ``serve.py`` and the other runners are
the yardstick and are not edited; as they say of themselves, ``run``
repeats ``serve.py``'s body with this family's model, weights and reference
check.  The loop, the warm-up, the error measure, the percentile, the expert
counters, the removal of a shared direction and the check's positions are
imported.

Counters it adds to ``serve_hybrid.py``'s (``moe_*``, ``itl_p95_ms``):
``latent_rows`` - ``engine.rows_read()`` after the drain less after the
warm-up: the host-side sum, over every decode step of ramp, window and
drain, of the latent rows the layers read (every live row of each active
lane, the appended one among them, in each layer; the same count rides each
``engine.decode`` span).
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.lib import traffic as tf
from benchmark.lib.stats import percentile
from benchmark.runners.serve import SPANS, rel_err, warm_up
from benchmark.runners.serve_hybrid import BALANCE_STEPS, BALANCE_TOKENS
from benchmark.runners.serve_hybrid import moe_counts
from benchmark.runners.serve_sparse import check_positions
from benchmark.runners.serve_window import _unit, without_directions

# the published keys XingConfig takes under their own names
KEYS = ("vocab_size", "hidden_size", "intermediate_size",
        "num_hidden_layers", "first_k_dense_replace", "num_attention_heads",
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "rope_theta", "n_routed_experts",
        "num_experts_per_tok", "moe_intermediate_size", "n_shared_experts",
        "routed_scaling_factor", "rms_norm_eps", "hc_mult",
        "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
        "mhc_h_res_clamp_max")


def build_model(config: dict):
    import jax.numpy as jnp

    from apex_tpu.models.xing import XingConfig, XingForCausalLM

    for key, want in (("norm_topk_prob", True), ("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("n_group", 1),
                      ("topk_group", 1), ("hidden_act", "silu"),
                      ("tie_word_embeddings", False),
                      ("attention_bias", False), ("moe_layer_freq", 1)):
        if config.get(key, want) != want:
            raise ValueError(f"{key} {config[key]!r}: the model is built "
                             f"for {want!r}")
    rope = config["rope_scaling"]
    if rope["type"] != "yarn":
        raise ValueError(f"rope_scaling type {rope['type']!r}: the model "
                         f"is built for YaRN")
    return XingForCausalLM(XingConfig(
        **{k: config[k] for k in KEYS},
        rope_factor=rope["factor"],
        original_max_position_embeddings=rope[
            "original_max_position_embeddings"],
        beta_fast=rope["beta_fast"], beta_slow=rope["beta_slow"],
        mscale=rope["mscale"], mscale_all_dim=rope["mscale_all_dim"],
        experts_held=tuple(config["experts_held"])),
        params_dtype=jnp.dtype(config["assumed"]["weights_dtype"]))


def draw_params(model, config: dict, seed: int):
    """Seeded weights drawn on the device in one jitted call, as the
    configuration's ``assumed.weights`` says: matrices normal x 0.02 in the
    type the model declares them in (routers and ``phi`` float32), norm
    scales 1, selection biases 0, each mHC ``alpha`` and ``b`` from
    ``assumed.hc_init``.  The key is an argument: a seed baked into the
    program would compile a new one for every seed."""
    import jax
    import jax.numpy as jnp

    init = config["assumed"]["hc_init"]
    gain = jnp.asarray(init["alpha_times_phi_rms"], jnp.float32) / (
        init["phi_std"] * (config["hc_mult"] * config["hidden_size"]) ** 0.5)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def draw(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            names = [str(getattr(p, "key", p)) for p in path]
            key_i = jax.random.fold_in(key, i)
            if names[-1] == "scale":
                out.append(jnp.ones(leaf.shape, leaf.dtype))
            elif names[-1] == "router_bias":
                out.append(jnp.zeros(leaf.shape, leaf.dtype))
            elif names[-1] == "alpha":
                out.append(gain[:leaf.shape[0]].astype(leaf.dtype))
            elif names[-1] == "bias":
                out.append(init["bias_std"] * jax.random.normal(
                    key_i, leaf.shape, leaf.dtype))
            else:
                std = init["phi_std"] if names[-1] == "phi" else 0.02
                out.append(std * jax.random.normal(key_i, leaf.shape,
                                                   leaf.dtype))
        return jax.tree.unflatten(treedef, out)

    return draw(jax.random.key(seed % (2 ** 31 - 1), impl="rbg"))


def make_params(model, config: dict, seed: int):
    """:func:`draw_params`, then routers, selection biases and the head as
    :func:`route_as_trained` leaves them."""
    return route_as_trained(model, draw_params(model, config, seed), config,
                            seed)


def head_without_directions(head, directions):
    """``without_directions(head, directions, axis=1)`` for the head
    ``[vocab, hidden]``, a block of rows at a time in one program: op by op
    a float32 copy of the whole head and its transposes (1.9 GB each at
    this vocabulary) would stand beside the weights at once."""
    import math

    import jax
    import jax.numpy as jnp

    basis, _ = jnp.linalg.qr(jnp.stack(directions, axis=1))    # [hidden, k]

    @jax.jit
    def remove(head, basis):
        vocab = head.shape[0]
        block = math.gcd(vocab, 16384)

        def rows(m):
            m32 = m.astype(jnp.float32)
            return (m32 - (m32 @ basis) @ basis.T).astype(m.dtype)

        return jax.lax.map(rows, head.reshape(vocab // block, block, -1)
                           ).reshape(head.shape)

    return remove(head, basis)


def balance(scores, top_k: int):
    """A selection bias ``[experts]`` float32 after ``BALANCE_STEPS`` sign
    steps that move the loads of ``scores [tokens, experts]`` (sigmoid
    router scores) towards equal: ``runners/serve_sparse.py``'s balancing,
    the auxiliary-loss-free scheme's (``topk_method`` ``noaux_tc``)."""
    import jax
    import jax.numpy as jnp

    tokens, experts = scores.shape

    def step(i, bias):
        _, chosen = jax.lax.top_k(scores + bias, top_k)
        load = jnp.zeros((experts,), jnp.float32).at[
            chosen.reshape(-1)].add(1.0)
        return bias + 0.05 * 0.955 ** i * jnp.sign(
            tokens * top_k / experts - load)

    return jax.lax.fori_loop(0, BALANCE_STEPS, step,
                             jnp.zeros((experts,), jnp.float32))


def late_rows(model, params, config: dict, seed: int, spec: dict):
    """What the routers and the head read far into a sequence, through the
    model's own chunk path on a cache of one slot (``chunk`` rows a call,
    one program): ``(means [layers + 1, hidden], rows [expert layers, n,
    hidden])`` - the sum over chunks of the mean of each layer's router
    rows and, last, of the head's rows, over the rows from ``first_row`` on
    of ``sequences`` seeded random sequences of ``tokens`` tokens; and each
    expert layer's router rows of every sequence's last chunk, float32.
    Rows that far in are what a decode step of the cell routes, and the
    plain reference cannot reach them in a set-up's time."""
    import dataclasses
    import functools

    import jax
    import jax.numpy as jnp

    from apex_tpu.serving.kv_cache import commit_slot_length, init_cache

    layers, chunk = config["num_hidden_layers"], spec["chunk"]
    dense = config["first_k_dense_replace"]
    if spec["tokens"] % chunk or spec["first_row"] % chunk:
        raise ValueError(f"late_rows {spec}: whole chunks")

    def read(module, _):
        return module.name in ("post_attention_layernorm", "norm")

    @functools.partial(jax.jit, donate_argnums=1)
    def rows_of(params, cache, ids, offset):
        (_, cache), state = model.apply(
            params, ids, kv_cache=cache, slot=jnp.int32(0), position=offset,
            length=jnp.int32(chunk), capture_intermediates=read,
            mutable=["intermediates"])
        got = state["intermediates"]
        rows = [got[f"layers_{i}"]["post_attention_layernorm"]["__call__"][0]
                for i in range(layers)] + [got["norm"]["__call__"][0]]
        rows = [r.astype(jnp.float32).reshape(chunk, -1) for r in rows]
        return (commit_slot_length(cache, 0, offset + chunk),
                jnp.stack([r.mean(0) for r in rows]),
                jnp.stack(rows[dense:layers]))

    cache = init_cache(model.cache_layers(), slots=1, max_len=spec["tokens"],
                       dtype=jnp.dtype(config["assumed"]["weights_dtype"]))
    rng = np.random.default_rng([seed, spec["first_row"]])
    total = jnp.zeros((layers + 1, config["hidden_size"]), jnp.float32)
    last = []
    for _ in range(spec["sequences"]):
        ids = rng.integers(0, config["vocab_size"],
                           spec["tokens"]).astype(np.int32)
        cache = dataclasses.replace(cache, lengths=jnp.zeros_like(
            cache.lengths))
        for offset in range(0, spec["tokens"], chunk):
            cache, means, rows = rows_of(params, cache,
                                         ids[None, offset:offset + chunk],
                                         jnp.int32(offset))
            if offset >= spec["first_row"]:
                total = total + means
        last.append(rows)
    return total, jnp.concatenate(last, axis=1)


def route_as_trained(model, params, config: dict, seed: int):
    """Routers, selection biases and the head of a seeded model as a trained
    one has them where the cell serves, in two passes (Mellum's two
    directions and dots3's balanced biases together,
    ``runners/serve_window.py``, ``runners/serve_sparse.py``):

    - in one walk of the plain float32 reference over a seeded
      ``BALANCE_TOKENS``-token sequence, layer by layer, each expert layer's
      router loses the direction its rows share and its selection bias
      takes ``BALANCE_STEPS`` sign steps towards equal loads; the head loses
      the final rows' direction;
    - then, through the model's own chunk path with the tree as the walk
      left it, :func:`late_rows` of ``assumed.late_rows``: the routers and
      the head lose the mean of what they read past ``first_row`` as well,
      and each selection bias is balanced again on the last chunk of every
      sequence under the router it now has - rows that far in are where the
      cell's decode steps are, and what the rows of a seeded model share
      grows with their position and turns on the way (PERF.md section
      6).

    System and reference read the same tree."""
    import jax

    from benchmark.reference import xing as ref

    # not the check's prompt, which default_rng(seed) draws
    ids = np.random.default_rng([seed, BALANCE_TOKENS]).integers(
        0, config["vocab_size"], BALANCE_TOKENS).astype(np.int32)
    raw = params["params"]
    tree = dict(raw)
    k = config["num_experts_per_tok"]
    dense = config["first_k_dense_replace"]
    layers = config["num_hidden_layers"]
    shared = {}                          # expert layer or "head": directions
    with jax.default_matmul_precision("highest"):
        x = ref.streams(ref.embed(params, ids), config)
        for i in range(layers):
            x = ref.attention_sublayer(x, tree[f"layers_{i}"], config)
            post, res, h = ref.mlp_rows(x, tree[f"layers_{i}"], config)
            if i >= dense:
                shared[i] = [_unit(h)]
                mlp = tree[f"layers_{i}"]["mlp"]
                kernel = without_directions(mlp["router_kernel"], shared[i],
                                            axis=0)
                tree[f"layers_{i}"] = dict(tree[f"layers_{i}"], mlp=dict(
                    mlp, router_kernel=kernel,
                    router_bias=balance(ref.router_scores(h, kernel), k)))
            x = ref.sublayer_out(x, post, res, ref.mlp_out(
                h, tree[f"layers_{i}"], i, config))
        shared["head"] = [_unit(ref.final_rows(x, tree, config))]
        tree["lm_head"] = head_without_directions(raw["lm_head"],
                                                  shared["head"])
    # the model's own programs at their own precision: a Pallas kernel's
    # bf16 products under "highest" do not compile
    means, rows = late_rows(model, {"params": tree}, config, seed,
                            config["assumed"]["late_rows"])
    with jax.default_matmul_precision("highest"):
        for j, i in enumerate(range(dense, layers)):
            shared[i].append(means[i])
            mlp = tree[f"layers_{i}"]["mlp"]
            kernel = without_directions(raw[f"layers_{i}"]["mlp"][
                "router_kernel"], shared[i], axis=0)
            tree[f"layers_{i}"] = dict(tree[f"layers_{i}"], mlp=dict(
                mlp, router_kernel=kernel,
                router_bias=balance(ref.router_scores(rows[j], kernel), k)))
        shared["head"].append(means[layers])
        tree["lm_head"] = None          # one calibrated head at a time
        tree["lm_head"] = head_without_directions(raw["lm_head"],
                                                  shared["head"])
    return {"params": tree}


def check_logits(engine, config, traffic, seed):
    """``(got, want, margins, seq)``: ``seq``, ``prompt_len`` +
    ``decode_tokens`` seeded tokens, through the timed engine - the prompt
    by its chunk programs (four chunks at the real size), then one decode
    step a later token through its cache; ``got [positions, vocab]`` every
    logit vector the engine hands back on the way
    (:func:`serve_sparse.check_positions`: each chunk's last row and each
    step), ``want`` the plain float32 forward's at those positions and
    ``margins [positions, expert layers]`` its routing margins there
    (``reference/xing.py::margins``).  The steps take seeded tokens, not
    greedy ones: a seeded model's greedy tokens repeat, and positions fed
    one token again route alike."""
    from benchmark.reference import xing

    spec = traffic["check"]
    n = spec["prompt_len"]
    seq = np.random.default_rng(seed).integers(
        0, config["vocab_size"], n + spec["decode_tokens"]).astype(np.int32)
    # engine.prefill's loop, keeping every chunk's logits (slot 0 is free
    # after the drain)
    got = [engine.prefill_chunk(0, seq[start:min(start + engine.prefill_len,
                                                 n)].tolist())
           for start in range(0, n, engine.prefill_len)]
    active = np.zeros((engine.slots,), bool)
    active[0] = True
    for token in seq[n:]:
        tokens = np.zeros((engine.slots,), np.int32)
        tokens[0] = token
        got.append(engine.decode(tokens, active)[0])
    want, margins = xing.logits_at(
        engine.params, seq, check_positions(traffic), config,
        held=config["experts_held"][0], with_margins=True)
    engine.release(0)
    return (np.stack([np.asarray(g, np.float32) for g in got]),
            np.asarray(want, np.float32), np.asarray(margins), seq)


def judge(got, want, margins, traffic) -> dict:
    """The check's verdict on ``got`` against ``want`` (both ``[positions,
    vocab]`` at :func:`serve_sparse.check_positions`), each position read
    as ``||got - want|| / ||want||``.  ``reference_ok`` is decided by ONE
    number, ``reference_rel_err``: the largest reading among the positions
    whose routing the reference decides, where every expert layer's margin
    (``margins [positions, expert layers]``) is at least ``routing_margin``
    - or, where it decides none, the median reading.  A position the
    reference leaves near a tie is left out: with seeded weights bf16 moves
    a router's scores by a few thousandths, a top-4 choice among 64 then
    flips at about one position in eight, and the position reads 7-50 %, as
    far as float8 weights move every position (``tolerance_why`` in
    ``traffic/mladoc-closed.json``; PERF.md section 2).  The median, the
    stacked reading, the first token's and the last step's are printed
    beside it and decide nothing."""
    spec = traffic["check"]
    at = check_positions(traffic)
    each = [rel_err(g, w) for g, w in zip(got, want)]
    decided = np.asarray(margins).min(-1) >= spec["routing_margin"]
    counted = [e for e, d in zip(each, decided) if d]
    worst = max(counted) if counted else float(np.median(each))
    return {"reference_rel_err": worst,
            "reference_positions_decided": len(counted),
            "reference_rel_err_median": float(np.median(each)),
            "reference_rel_err_stacked": rel_err(got, want),
            "reference_rel_err_first_token": each[
                at.index(spec["prompt_len"] - 1)],
            "reference_rel_err_after_decode": each[-1],
            "reference_rel_err_each": [float(f"{e:.3g}") for e in each],
            "reference_margin_min": [float(f"{m:.3g}")
                                     for m in np.asarray(margins).min(-1)],
            "reference_tolerance": spec["tolerance"],
            "reference_ok": bool(worst <= spec["tolerance"])}


def check_against_reference(engine, config, traffic, seed) -> dict:
    """``serve.py``'s check with this family's reference and a long prompt:
    :func:`check_logits` judged by :func:`judge`."""
    got, want, margins, _ = check_logits(engine, config, traffic, seed)
    return judge(got, want, margins, traffic)


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
