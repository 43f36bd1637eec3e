"""Serving runner for the ZAYA1 family (compressed convolutional attention
over grouped K/V heads with a convolution tail a slot, top-1 experts chosen
by a router MLP with a depth carry, tied head): ``lib/serving.py``'s closed
loop, window and checks, run on ``ZayaForCausalLM`` and compared with
``reference/zaya.py``.

Cells: ``zaya1-8b-l10.reason-closed`` (and the tests'
``tiny-zaya.tiny-reason-closed``).  The other runners are the yardstick and
are not edited; their helpers are imported: the calibration's pieces
(``serve_window.without_directions`` / ``_unit``, ``serve_mhc.balance`` /
``head_without_directions``), the check's positions and
``serve_mhc.judge``'s rule.  Every row the calibration reads comes from the
plain reference, none from the system under test.

Counters beside ``lib/serving.py``'s: ``n_routed_experts``, the experts held
here under the name the generic expert reducers divide by (this family's key
is ``num_experts``).
"""

from __future__ import annotations

import numpy as np

from benchmark.lib.serving import serve_cell
from benchmark.runners.serve_hybrid import BALANCE_TOKENS
from benchmark.runners.serve_mhc import (
    balance,
    head_without_directions,
    judge,
)
from benchmark.runners.serve_sparse import check_positions
from benchmark.runners.serve_window import _unit, without_directions

# the published keys ZayaConfig takes under their own names
KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "cca_time0", "cca_time1", "partial_rotary_factor", "num_experts",
        "num_experts_per_tok", "moe_intermediate_size", "router_hidden_size",
        "rms_norm_eps")


def build_model(config: dict):
    import jax.numpy as jnp

    from apex_tpu.models.zaya import ZayaConfig, ZayaForCausalLM

    for key, want in (("hidden_act", "silu"), ("tie_word_embeddings", True),
                      ("attention_bias", False), ("lm_head_bias", False),
                      ("sliding_window", None)):
        if config.get(key, want) != want:
            raise ValueError(f"{key} {config[key]!r}: the model is built "
                             f"for {want!r}")
    if set(config["layer_types"]) != {"hybrid"}:
        raise ValueError("layer_types: the model builds hybrid layers (CCA "
                         "and experts) alone")
    rope = config["rope_parameters"]["hybrid"]
    if (rope["rope_type"] != "default" or rope["partial_rotary_factor"]
            != config["partial_rotary_factor"]):
        raise ValueError(f"rope_parameters.hybrid {rope}: the model is "
                         f"built for default rope over the "
                         f"partial_rotary_factor channels")
    return ZayaForCausalLM(ZayaConfig(
        **{k: config[k] for k in KEYS}, rope_theta=rope["rope_theta"],
        experts_held=tuple(config["experts_held"])),
        params_dtype=jnp.dtype(config["assumed"]["weights_dtype"]))


def draw_params(model, config: dict, seed: int):
    """Seeded weights drawn on the device in one jitted call, as the
    configuration's ``assumed.weights`` says: the projections, the experts
    and the embedding normal x 0.02 in the type the model declares them in;
    the router's matrices (float32) and the two convolutions' kernels normal
    x fan_in^-1/2, so that each keeps the spread of what it reads; norm
    scales 1, convolution biases and balancing biases 0, each temperature
    sqrt(head_dim), each depth carry ``assumed.router_gamma``.  The key is an
    argument: a seed baked into the program would compile a new one for
    every seed."""
    import jax
    import jax.numpy as jnp

    gamma = config["assumed"]["router_gamma"]
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def std(names, shape):
        if "router" in names:
            return shape[0] ** -0.5
        if names[-2] == "conv_time0":
            return shape[0] ** -0.5
        if names[-2] == "conv_time1":
            return (shape[0] * shape[2]) ** -0.5
        return 0.02

    @jax.jit
    def draw(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            names = [str(getattr(p, "key", p)) for p in path]
            if names[-1] == "scale":
                out.append(jnp.ones(leaf.shape, leaf.dtype))
            elif names[-1] in ("bias", "balance_bias"):
                out.append(jnp.zeros(leaf.shape, leaf.dtype))
            elif names[-1] == "temperature":
                out.append(jnp.full(leaf.shape, config["head_dim"] ** 0.5,
                                    leaf.dtype))
            elif names[-1] == "gamma":
                out.append(jnp.full(leaf.shape, gamma, leaf.dtype))
            else:
                out.append(std(names, leaf.shape) * jax.random.normal(
                    jax.random.fold_in(key, i), leaf.shape, leaf.dtype))
        return jax.tree.unflatten(treedef, out)

    return draw(jax.random.key(seed % (2 ** 31 - 1), impl="rbg"))


def make_params(model, config: dict, seed: int):
    """:func:`draw_params`, then routers and the head as
    :func:`route_as_trained` leaves them."""
    return route_as_trained(draw_params(model, config, seed), config, seed)


def _calibrated(router, raw_down, directions, rows, carry):
    """``(router, probs, carry)``: ``router`` with ``down`` = ``raw_down``
    less ``directions`` and the balancing bias balanced on ``rows`` under
    it, its probabilities there and the carry it hands on."""
    from benchmark.reference import zaya as ref

    router = dict(router, down=without_directions(raw_down, directions,
                                                  axis=0))
    probs, carry = ref.router(rows, router, carry)
    return dict(router, balance_bias=balance(probs, 1)), probs, carry


def late_rows(params, config: dict, seed: int, spec: dict):
    """What the routers and the head read far into a sequence, from the
    plain float32 reference's walk (top-1 routing as it routes): ``(means
    [layers + 1, hidden], rows [layers, n, hidden])`` - the sum over
    ``sequences`` seeded random sequences of ``tokens`` tokens of the mean
    of each layer's router rows and, last, of the head's rows, over the
    rows from ``first_row`` on; and each layer's router rows of every
    sequence's last ``chunk`` rows, where the cell's decode steps are."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import zaya as ref

    layers, first, chunk = (config["num_hidden_layers"], spec["first_row"],
                            spec["chunk"])
    rng = np.random.default_rng([seed, first])
    total = jnp.zeros((layers + 1, config["hidden_size"]), jnp.float32)
    last = [[] for _ in range(layers)]
    with jax.default_matmul_precision("highest"):
        for _ in range(spec["sequences"]):
            ids = rng.integers(0, config["vocab_size"],
                               spec["tokens"]).astype(np.int32)
            means = []

            def visit(i, h, probs, beta):
                means.append(h[first:].mean(0))
                last[i].append(h[-chunk:])

            x = ref.walk(params, ids, config,
                         held=config["experts_held"][0], visit=visit)
            means.append(ref.final_rows(x[first:], params, config).mean(0))
            total = total + jnp.stack(means)
    return total, jnp.stack([jnp.concatenate(r) for r in last])


def route_as_trained(params, config: dict, seed: int):
    """Routers, balancing biases and the head (the embedding, tied) of a
    seeded model as a trained one has them where the cell serves, in
    ``serve_mhc.route_as_trained``'s two passes, both on the plain float32
    reference:

    - in one walk over a seeded ``BALANCE_TOKENS``-token sequence, layer by
      layer, each router's down-projection loses the direction its rows
      share and its balancing bias takes sign steps towards equal loads of
      its top-1 choice (the carry handed on from the calibrated router
      before it); the embedding loses the final rows' direction;
    - then, with the tree as that walk left it, :func:`late_rows` of
      ``assumed.late_rows``: each router and the embedding lose the mean of
      what they read past ``first_row`` as well, and each balancing bias is
      balanced again on the last ``chunk`` rows of every sequence.

    System and reference read the same tree; no row of the calibration
    comes from the system."""
    import jax

    from benchmark.reference import zaya as ref

    # not the check's prompt, which default_rng(seed) draws
    ids = np.random.default_rng([seed, BALANCE_TOKENS]).integers(
        0, config["vocab_size"], BALANCE_TOKENS).astype(np.int32)
    raw = params["params"]
    tree = dict(raw)
    layers = config["num_hidden_layers"]
    held = config["experts_held"][0]
    shared = {}                          # layer or "head": directions
    with jax.default_matmul_precision("highest"):
        x = ref.embed(params, ids)
        carry = None
        for i in range(layers):
            layer = tree[f"layers_{i}"]
            x = x + ref.attention_out(x, layer, config)
            h = ref.normed(x, layer["post_attention_layernorm"], config)
            shared[i] = [_unit(h)]
            router, probs, carry = _calibrated(
                layer["router"], layer["router"]["down"], shared[i], h, carry)
            layer = tree[f"layers_{i}"] = dict(layer, router=router)
            x = x + ref.experts(h, layer["mlp"], ref.route_weights(
                probs, router["balance_bias"]), held=held)
        shared["head"] = [_unit(ref.final_rows(x, {"params": tree}, config))]
        tree["embed_tokens"] = {"embedding": head_without_directions(
            raw["embed_tokens"]["embedding"], shared["head"])}
    x = None
    means, rows = late_rows({"params": tree}, config, seed,
                            config["assumed"]["late_rows"])
    with jax.default_matmul_precision("highest"):
        carry = None
        for i in range(layers):
            shared[i].append(means[i])
            layer = tree[f"layers_{i}"]
            router, _, carry = _calibrated(
                layer["router"], raw[f"layers_{i}"]["router"]["down"],
                shared[i], rows[i], carry)
            tree[f"layers_{i}"] = dict(layer, router=router)
        shared["head"].append(means[layers])
        tree["embed_tokens"] = None     # one calibrated table at a time
        tree["embed_tokens"] = {"embedding": head_without_directions(
            raw["embed_tokens"]["embedding"], shared["head"])}
    return {"params": tree}


def check_logits(engine, config, traffic, seed):
    """``(got, want, margins, seq)``: ``serve_mhc.check_logits`` with this
    family's reference - ``prompt_len`` + ``decode_tokens`` seeded tokens,
    the prompt through the timed engine's chunk programs (the tail crosses
    each chunk's end), one decode step a later token through its cache;
    every logit vector handed back on the way against the plain float32
    forward's at :func:`serve_sparse.check_positions`, and the reference's
    routing margins there (``[positions, layers]``)."""
    from benchmark.reference import zaya

    spec = traffic["check"]
    n = spec["prompt_len"]
    seq = np.random.default_rng(seed).integers(
        0, config["vocab_size"], n + spec["decode_tokens"]).astype(np.int32)
    # slot 0 is free after the drain
    got = [engine.prefill_chunk(0, seq[start:min(start + engine.prefill_len,
                                                 n)].tolist())
           for start in range(0, n, engine.prefill_len)]
    active = np.zeros((engine.slots,), bool)
    active[0] = True
    for token in seq[n:]:
        tokens = np.zeros((engine.slots,), np.int32)
        tokens[0] = token
        got.append(engine.decode(tokens, active)[0])
    want, margins = zaya.logits_at(
        engine.params, seq, check_positions(traffic), config,
        held=config["experts_held"][0], with_margins=True)
    engine.release(0)
    return (np.stack([np.asarray(g, np.float32) for g in got]),
            np.asarray(want, np.float32), np.asarray(margins), seq)


def check_against_reference(engine, config, traffic, seed) -> dict:
    """:func:`check_logits` judged by ``serve_mhc.judge``: the largest
    reading among the positions the reference routes decisively in every
    layer (top-1 less top-2 ``p + beta`` at least ``routing_margin``)."""
    got, want, margins, _ = check_logits(engine, config, traffic, seed)
    return judge(got, want, margins, traffic)


def run(ctx) -> dict:
    import jax

    config, traffic = ctx.config, ctx.traffic
    if traffic["loop"] != "closed":
        raise ValueError(f"traffic loop {traffic['loop']!r}: this runner "
                         f"drives closed loops")
    model = build_model(config)
    params = make_params(model, config, ctx.seed)
    jax.block_until_ready(params)
    ctx.setup.mark("init")
    return serve_cell(
        ctx, model, params,
        lambda engine: check_against_reference(engine, config, traffic,
                                               ctx.seed),
        counters={"n_routed_experts": config["experts_held"][1]})
