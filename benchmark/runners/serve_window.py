"""Serving runner for the Mellum family (grouped-query attention under a
window of K/V rows on three layers in four and at full extent under YaRN on
the fourth, softmax-routed gated experts, all held): ``runners/serve.py``'s
closed loop, window and checks, run on ``MellumForCausalLM`` and compared
with ``reference/mellum.py``.

Cells: ``mellum2-12b-l8.repo-closed`` (and the tests'
``tiny-mellum.tiny-repo-closed``).  ``serve.py``, ``serve_hybrid.py`` and
``serve_sparse.py`` are the yardstick and are not edited; as they say of
themselves, ``run`` repeats ``serve.py``'s body with this family's model,
weights and reference check (PERF.md section 7 asks a ``benchmark`` PR to
make them arguments of one ``run``).  The loop, the warm-up, the error
measure, the percentile, the expert counters and the check's positions are
imported; the routers and the head lose two shared directions here
(:func:`route_as_trained`), the second through the model's own chunk path.

Counters it adds to ``serve_hybrid.py``'s (``moe_*``, ``itl_p95_ms``):
``window_rows`` and ``window_live_rows`` - ``engine.rows_read()`` after the
drain less after the warm-up: host-side sums, over every decode step of
ramp, window and drain, of the rows the window layers read (``min(live,
sliding_window)`` a lane a layer) and of the rows those lanes hold (the
first rides each ``engine.decode`` span too) - and ``n_routed_experts``, the
experts held here under the name the generic expert reducers divide by (this
family's key is ``num_experts``).
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.lib import traffic as tf
from benchmark.lib.stats import percentile
from benchmark.runners.serve import SPANS, rel_err, warm_up
from benchmark.runners.serve_hybrid import BALANCE_TOKENS, moe_counts
from benchmark.runners.serve_sparse import check_positions

# the published keys MellumConfig takes under their own names
KEYS = ("vocab_size", "hidden_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "sliding_window", "num_experts",
        "num_experts_per_tok", "moe_intermediate_size", "rms_norm_eps")
FULL, WINDOW = "full_attention", "sliding_attention"


def build_model(config: dict):
    import jax.numpy as jnp

    from apex_tpu.models.mellum import (
        MellumConfig,
        MellumForCausalLM,
        RopeParameters,
    )

    for key, want in (("norm_topk_prob", True), ("attention_bias", False),
                      ("tie_word_embeddings", False), ("hidden_act", "silu")):
        if config.get(key, want) != want:
            raise ValueError(f"{key} {config[key]!r}: the model is built "
                             f"for {want!r}")
    if set(config["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("mlp_layer_types: the model builds sparse layers")
    return MellumForCausalLM(MellumConfig(
        **{k: config[k] for k in KEYS},
        layer_types=tuple(config["layer_types"]),
        full_attention_rope=RopeParameters(
            **config["rope_parameters"][FULL]),
        sliding_attention_rope=RopeParameters(
            **config["rope_parameters"][WINDOW]),
        experts_held=tuple(config["experts_held"])),
        params_dtype=jnp.dtype(config["assumed"]["weights_dtype"]))


def draw_params(model, seed: int):
    """Seeded weights drawn on the device in one jitted call, as the
    configuration's ``assumed.weights`` says: matrices normal x 0.02 in the
    type the model declares them in (the router's float32), norm scales 1.
    The key is an argument: a seed baked into the program would compile a
    new one for every seed."""
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
            else:
                out.append(0.02 * jax.random.normal(
                    jax.random.fold_in(key, i), leaf.shape, leaf.dtype))
        return jax.tree.unflatten(treedef, out)

    return draw(jax.random.key(seed % (2 ** 31 - 1), impl="rbg"))


def make_params(model, config: dict, seed: int):
    """:func:`draw_params`, then routers and the head as
    :func:`route_as_trained` leaves them."""
    return route_as_trained(model, draw_params(model, seed), config, seed)[0]


def _unit(rows):
    """The direction ``rows [tokens, hidden]`` share: their mean,
    normalised (float32)."""
    import jax.numpy as jnp

    mean = rows.astype(jnp.float32).mean(axis=0)
    return mean / jnp.linalg.norm(mean)


def without_directions(matrix, directions, axis: int):
    """``matrix`` with the span of ``directions`` (hidden-sized vectors)
    taken out along its hidden ``axis``: ``without_shared_direction`` for
    more than one."""
    import jax.numpy as jnp

    basis, _ = jnp.linalg.qr(jnp.stack(directions, axis=1))
    m32 = jnp.moveaxis(matrix.astype(jnp.float32), axis, 0)
    m32 = m32 - basis @ (basis.T @ m32)
    return jnp.moveaxis(m32, 0, axis).astype(matrix.dtype)


def chunk_means(model, layers: int, chunk: int):
    """The program :func:`late_row_means` runs a chunk: ``(params, cache,
    ids [1, chunk], offset) -> (cache, means [layers + 1, hidden])``, the
    chunk through the model's cached path at ``offset`` of slot 0 and the
    mean over its rows of what each router and the head read."""
    import functools

    import jax
    import jax.numpy as jnp

    from apex_tpu.serving.kv_cache import commit_slot_length

    def read(module, _):
        return module.name in ("post_attention_layernorm", "norm")

    @functools.partial(jax.jit, donate_argnums=1)
    def means_of(params, cache, ids, offset):
        (_, cache), state = model.apply(
            params, ids, kv_cache=cache, slot=jnp.int32(0), position=offset,
            length=jnp.int32(chunk), capture_intermediates=read,
            mutable=["intermediates"])
        got = state["intermediates"]
        rows = [got[f"layers_{i}"]["post_attention_layernorm"]["__call__"][0]
                for i in range(layers)] + [got["norm"]["__call__"][0]]
        return (commit_slot_length(cache, 0, offset + chunk),
                jnp.stack([r.astype(jnp.float32).mean((0, 1)) for r in rows]))

    return means_of


def late_row_means(model, params, config: dict, seed: int, spec: dict):
    """``[layers + 1, hidden]`` float32: the sum over chunks of the mean of
    the rows each layer's router reads and, last, of the rows the head
    reads, over the rows from ``first_row`` on of ``sequences`` seeded
    random sequences of ``tokens`` tokens - through the model's own chunk
    path on a cache of one slot (``chunk`` rows a call, one program),
    because rows that far into a sequence are what a decode step of this
    cell routes and the plain reference cannot reach them in a set-up's
    time (20,480 rows of 64 dense float32 experts a layer, a sequence;
    :func:`route_as_trained` says why they differ from a short sequence's).
    What the weights are made from bears on no comparison: system and
    reference read the same tree."""
    import dataclasses

    import jax.numpy as jnp

    from apex_tpu.serving.kv_cache import init_cache

    layers, chunk = len(config["layer_types"]), spec["chunk"]
    if spec["tokens"] % chunk or spec["first_row"] % chunk:
        raise ValueError(f"late_rows {spec}: whole chunks")
    cache = init_cache(model.cache_layers(), slots=1, max_len=spec["tokens"],
                       dtype=jnp.dtype(config["assumed"]["weights_dtype"]))
    means_of = chunk_means(model, layers, chunk)
    rng = np.random.default_rng([seed, spec["first_row"]])
    total = jnp.zeros((layers + 1, config["hidden_size"]), jnp.float32)
    for _ in range(spec["sequences"]):
        ids = rng.integers(0, config["vocab_size"],
                           spec["tokens"]).astype(np.int32)
        cache = dataclasses.replace(cache, lengths=jnp.zeros_like(
            cache.lengths))
        for offset in range(0, spec["tokens"], chunk):
            cache, means = means_of(params, cache,
                                    ids[None, offset:offset + chunk],
                                    jnp.int32(offset))
            if offset >= spec["first_row"]:
                total = total + means
    return total


def route_as_trained(model, params, config: dict, seed: int):
    """``serve_hybrid.route_as_trained`` as far as this family has
    parameters for it: each layer's router and the head lose the directions
    that the rows they read share.  The published router has no selection
    bias, so none is added and none is balanced.

    Two directions each, because what the rows of a seeded model share
    grows with their position and turns on the way.  Every query reads a
    mean of many rows' V, which keeps what those rows share and loses the
    rest; the next layer's rows take it in and hand more of it on: a
    mean-field growth that a trained model does not have.  At the real size
    the shared part is 30 % of a normed row's norm at the first router and
    73 % at the head in rows past 16k, where a 2,048-token sequence's mean
    points elsewhere (my chip run, PERF.md section 6, PR 33):

    - in one walk of the plain float32 reference over a seeded
      ``BALANCE_TOKENS``-token sequence, layer by layer (a layer's change
      moves the rows after it), the mean of the rows the router reads, and
      of the final normed rows for the head;
    - then, with the tree as the walk left it, :func:`late_row_means` of
      ``assumed.late_rows``: rows past 16k of eight 20k-token sequences at
      the real size, which is where the cell's decode steps are.

    With the first alone, 16 lanes decoding past 20k rows touched 67.6 % of
    the 64 experts a step and one expert took up to 15 of the 16 tokens;
    with both 86.1 %, the largest load 5.8-6.9: what independent choices
    give (86.7 %).  Over the cell's runs the first alone read 58-66 % by
    the seed and ``serve_tok_s`` followed it (1.0 % over six seeds; the
    driver refused the cell for that).  Returns the tree and the directions
    taken out (a layer's router, ..., then the head: a list each).  System
    and reference read the same tree."""
    import jax

    from benchmark.reference import mellum as ref

    # not the check's prompt, which default_rng(seed) draws
    ids = np.random.default_rng([seed, BALANCE_TOKENS]).integers(
        0, config["vocab_size"], BALANCE_TOKENS).astype(np.int32)
    raw = params["params"]
    tree = dict(raw)
    layers = len(config["layer_types"])
    shared = []                    # a layer's router, ..., then the head
    with jax.default_matmul_precision("highest"):
        x = ref.embed(params, ids)
        for i, kind in enumerate(config["layer_types"]):
            layer = tree[f"layers_{i}"]
            x = x + ref.attention_out(x, layer, config, kind)
            h = ref.normed(x, layer["post_attention_layernorm"], config)
            shared.append([_unit(h)])
            layer = tree[f"layers_{i}"] = dict(layer, mlp=dict(
                layer["mlp"], router_kernel=without_directions(
                    layer["mlp"]["router_kernel"], shared[-1], axis=0)))
            x = x + ref.mlp_out(h, layer, config)
        shared.append([_unit(ref.normed(x, tree["norm"], config))])
        tree["lm_head"] = without_directions(tree["lm_head"], shared[-1],
                                             axis=1)
    for i, mean in enumerate(late_row_means(
            model, {"params": tree}, config, seed,
            config["assumed"]["late_rows"])):
        shared[i].append(mean)
    for i in range(layers):
        tree[f"layers_{i}"] = dict(tree[f"layers_{i}"], mlp=dict(
            tree[f"layers_{i}"]["mlp"], router_kernel=without_directions(
                raw[f"layers_{i}"]["mlp"]["router_kernel"], shared[i],
                axis=0)))
    tree["lm_head"] = without_directions(raw["lm_head"], shared[-1], axis=1)
    return {"params": tree}, shared


def check_against_reference(engine, config, traffic, seed) -> dict:
    """``serve.py``'s check with this family's reference and a long prompt:
    ``prompt_len`` tokens (four windows, four chunks at the real size)
    through the timed engine's chunk programs, then ``decode_tokens`` greedy
    steps through its cache (the ring wraps once more), against the plain
    float32 forward over prompt + decoded tokens.  Decided, as in the other
    serving cells, by the first token's logits and the logits after the last
    decoded token, each as the norm of the difference over the norm of the
    reference; the logits every other chunk and step hand back are printed
    beside them."""
    import jax.numpy as jnp

    from benchmark.reference import mellum

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
    want = mellum.logits_at(engine.params, np.asarray(seq, np.int32), at,
                            config, held=config["experts_held"][0])
    engine.release(0)
    each = [rel_err(g, w) for g, w in zip(got, want)]
    first = each[at.index(len(prompt) - 1)]
    return {"reference_rel_err_first_token": first,
            "reference_rel_err_after_decode": each[-1],
            "reference_rel_err_each": [float(f"{e:.3g}") for e in each],
            "reference_tolerance": spec["tolerance"],
            "reference_ok": bool(max(first, each[-1]) <= spec["tolerance"])}


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
    # the experts held here, under the name the generic expert reducers
    # divide by
    counts["n_routed_experts"] = config["experts_held"][1]
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
