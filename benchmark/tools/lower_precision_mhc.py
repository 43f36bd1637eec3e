"""The two readings a ``serve_mhc`` cell's reference tolerance is set from
(``tools/lower_precision_window.py`` does the second for the Mellum family
and is not edited), and what the large single positions of the first are:

    python3 benchmark/tools/lower_precision_mhc.py --workload <cell> --seeds 11,12 [--e4m3 11] [--routes 11]

For each seed the cell's weights are drawn as its runner draws them and the
runner's own reference check is made on an engine of the cell's size - the
check's prompt through the chunk programs, its later tokens through the
cache, every logit vector it hands back against the plain float32 forward,
judged by the runner's ``judge``: the system's reading, as a timed run's
check would read it but without the window.

``--routes``: at each of the check's positions, the experts each expert
layer chooses from the router rows the system's own programs make (the
prompt through the chunk program, the steps through the decode program, on
a cache of the engine's slots) against the experts the reference chooses
from its rows, and the reference's margin between its k-th and (k+1)-th
``score + bias``: whether the positions that read far from the reference are
the ones whose routing differs.

``--e4m3``: every matrix of the weights (stated bfloat16, 8 bits of
precision) is then rounded to float8 e4m3's 4 bits (``lax.reduce_precision``)
and the plain reference is run on the rounded weights over the check's own
sequence, judged against the float32 reference's logits (and by its
routing margins) by the same ``judge``: the reading of a path in the next
precision below, which the tolerance must refuse (``e4m3_weights_ok``
false).  One line a seed.
"""

import argparse
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def system_rows(model, params, config, traffic, seq, slots):
    """The router rows ``[expert layers, positions, hidden]`` float32 the
    model's own chunk and decode programs make at the check's positions:
    the prompt a ``prefill_len`` chunk at a time into slot 0, then one
    decode step a later token with slot 0 alone active, on a cache of
    ``slots`` slots (the engine's, so that a step has the engine's shapes)
    and the fewest whole 2,048-row blocks that hold ``seq``."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.serving.kv_cache import commit_slot_length, init_cache

    dense, layers = config["first_k_dense_replace"], config[
        "num_hidden_layers"]
    chunk = traffic["engine"]["prefill_len"]
    prompt = traffic["check"]["prompt_len"]

    def read(module, _):
        return module.name == "post_attention_layernorm"

    def rows_of(state):
        got = state["intermediates"]
        return jnp.stack([
            got[f"layers_{i}"]["post_attention_layernorm"]["__call__"][0]
            .reshape(-1, config["hidden_size"]).astype(jnp.float32)
            for i in range(dense, layers)])

    @functools.partial(jax.jit, donate_argnums=1)
    def chunk_rows(params, cache, ids, offset, length):
        (_, cache), state = model.apply(
            params, ids, kv_cache=cache, slot=jnp.int32(0), position=offset,
            length=length, capture_intermediates=read,
            mutable=["intermediates"])
        return (commit_slot_length(cache, 0, offset + length),
                rows_of(state)[:, length - 1])

    @functools.partial(jax.jit, donate_argnums=1)
    def step_rows(params, cache, tokens, active):
        (_, cache), state = model.apply(
            params, tokens[:, None], kv_cache=cache,
            position=cache.decode_positions(active), active=active,
            capture_intermediates=read, mutable=["intermediates"])
        cache = dataclasses.replace(
            cache, lengths=cache.lengths + active.astype(jnp.int32))
        return cache, rows_of(state)[:, 0]

    max_len = -(-len(seq) // 2048) * 2048
    cache = init_cache(model.cache_layers(), slots=slots, max_len=max_len,
                       dtype=jnp.dtype(config["assumed"]["weights_dtype"]))
    out = []
    for start in range(0, prompt, chunk):
        ids = np.zeros((1, chunk), np.int32)
        length = min(chunk, prompt - start)
        ids[0, :length] = seq[start:start + length]
        cache, rows = chunk_rows(params, cache, ids, jnp.int32(start),
                                 jnp.int32(length))
        out.append(rows)
    active = np.zeros((slots,), bool)
    active[0] = True
    for token in seq[prompt:]:
        tokens = np.zeros((slots,), np.int32)
        tokens[0] = token
        cache, rows = step_rows(params, cache, tokens, active)
        out.append(rows)
    cache = None
    return jnp.stack(out, axis=1)


def reference_rows(params, config, seq, at):
    """The reference's router rows ``[expert layers, positions, hidden]``
    at ``at``, from its own walk over ``seq``."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import xing

    out = []
    with jax.default_matmul_precision("highest"):
        p = params["params"]
        x = xing.streams(xing.embed(params, seq), config)
        for i in range(config["num_hidden_layers"]):
            layer = p[f"layers_{i}"]
            x = xing.attention_sublayer(x, layer, config)
            post, res, h = xing.mlp_rows(x, layer, config)
            if i >= config["first_k_dense_replace"]:
                out.append(h[jnp.asarray(at)])
            x = xing.sublayer_out(x, post, res, xing.mlp_out(
                h, layer, i, config, held=config["experts_held"][0]))
    return jnp.stack(out)


def routes(params, config, system, reference):
    """Per position: the expert layers whose chosen set differs between the
    system's rows and the reference's, and the reference's smallest margin
    (k-th less (k+1)-th ``score + bias``) over the expert layers."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import xing

    k = config["num_experts_per_tok"]
    differ, margin = [], []
    with jax.default_matmul_precision("highest"):
        for j, i in enumerate(range(config["first_k_dense_replace"],
                                    config["num_hidden_layers"])):
            mlp = params["params"][f"layers_{i}"]["mlp"]
            bias = mlp["router_bias"].astype(jnp.float32)
            sets = []
            for rows in (system[j], reference[j]):
                top, chosen = jax.lax.top_k(
                    xing.router_scores(rows, mlp["router_kernel"]) + bias,
                    k + 1)
                sets.append(np.sort(np.asarray(chosen[:, :k]), axis=-1))
            # top is the reference's, the last read
            margin.append(np.asarray(top[:, k - 1] - top[:, k]))
            differ.append((sets[0] != sets[1]).any(-1))
    differ = np.stack(differ, axis=1)                   # [positions, layers]
    return {"routes_differ_layers": [
                [int(i) for i in np.flatnonzero(d)] for d in differ],
            "routes_margin_min": [float(f"{m:.3g}")
                                  for m in np.stack(margin).min(0)]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--e4m3", default="",
                    help="the seeds whose weights are also read in e4m3")
    ap.add_argument("--routes", default="",
                    help="the seeds whose positions' routing is compared")
    ap.add_argument("--manifest", default="BENCHMARK.json")
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: on the CPU")
    args = ap.parse_args()
    manifest = _load(args.manifest)
    cell = next(w for w in manifest["workloads"]
                if w["name"] == args.workload)
    config = _load(next(c["file"] for c in manifest["configs"]
                        if c["name"] == cell["config"]))
    traffic = _load(f"benchmark/traffic/{cell['traffic']}.json")
    if traffic["runner"] != "serve_mhc":
        sys.exit(f"runner {traffic['runner']!r}: this tool knows the "
                 f"serve_mhc family")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from apex_tpu import serving as sv
    from benchmark.reference import xing
    from benchmark.runners import serve_mhc as sm
    from benchmark.runners.serve_sparse import check_positions

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        sys.exit("needs the chip: the reading is of the cell's real size")
    model = sm.build_model(config)
    e4m3, by_route = set(_seeds(args.e4m3)), set(_seeds(args.routes))
    at = check_positions(traffic)

    # donated: two copies of the weights do not fit the chip
    @functools.partial(jax.jit, donate_argnums=0)
    def to_e4m3(params):
        return jax.tree.map(
            lambda a: jax.lax.reduce_precision(a, exponent_bits=8,
                                               mantissa_bits=3)
            if a.ndim >= 2 else a, params)

    engine = params = None
    for seed in _seeds(args.seeds):
        if engine is not None:
            engine.params = None            # two sets do not fit the chip
        params = None
        params = sm.make_params(model, config, seed)
        if engine is None:
            engine = sv.DecodeEngine(model, params, **traffic["engine"])
        engine.params = params
        line = {"workload": args.workload, "seed": seed}
        got, want, margins, seq = sm.check_logits(engine, config, traffic,
                                                  seed)
        line.update(sm.judge(got, want, margins, traffic))
        if seed in by_route:
            line.update(routes(
                params, config,
                system_rows(model, params, config, traffic, seq,
                            engine.slots),
                reference_rows(params, config, seq, at)))
        if seed in e4m3:
            engine.params = None
            rounded = xing.logits_at(to_e4m3(params), seq, at, config,
                                     held=config["experts_held"][0])
            params = None
            line.update({k.replace("reference_", "e4m3_weights_"): v
                         for k, v in sm.judge(rounded, want, margins,
                                              traffic).items()
                         if k not in ("reference_positions_decided",
                                      "reference_margin_min")})
            rounded = None
        print(json.dumps(dict(line, device=jax.devices()[0].device_kind),
                         default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
