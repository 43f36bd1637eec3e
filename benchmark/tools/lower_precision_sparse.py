"""``tools/lower_precision.py`` for a ``serve_sparse`` cell (that tool knows
the ``serve_hybrid`` family and is not edited): the second reading its
reference tolerance is set from.

    python3 benchmark/tools/lower_precision_sparse.py --workload <cell> --seeds 11,12

Every matrix of the seeded weights (stated bfloat16, 8 bits of precision) is
rounded to float8 e4m3's 4 bits (``lax.reduce_precision``: XLA:TPU drops a
pair of casts) and the plain reference is run on the rounded and on the
original weights, over a sequence of the check's length; the line gives
``|rounded - original| / |original|`` of the logits at the check's positions
stacked (the number the check decides by) and at the first token's and the
last decode step's alone.  Only the weights are rounded, so an 8-bit path would read at
least this.

Two more readings say how much of the system's error a choice at the margin
is: the reference all float32 but for the router's input, rounded to
bfloat16 (top-8 choices that flip), and all float32 but for the selector's
queries and keys, rounded to bfloat16 (selected rows that flip, of 2,048 a
query).
"""

import argparse
import functools
import json
import os
import sys
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
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
    if traffic["runner"] != "serve_sparse":
        sys.exit(f"runner {traffic['runner']!r}: this tool knows the "
                 f"serve_sparse family")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np

    from benchmark.reference import dots3
    from benchmark.runners import serve_sparse as ss
    from benchmark.runners.serve import rel_err

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        sys.exit("needs the chip: the reading is of the cell's real size")
    model = ss.build_model(config)
    spec = traffic["check"]
    held = config["experts_held"][0]

    # donated: two copies of the weights do not fit the chip
    @functools.partial(jax.jit, donate_argnums=0)
    def to_e4m3(params):
        return jax.tree.map(
            lambda a: jax.lax.reduce_precision(a, exponent_bits=8,
                                               mantissa_bits=3)
            if a.ndim >= 2 else a, params)

    def to_bf16(a):
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    plain_route, plain_rope, plain_selection = (dots3.route, dots3._rope,
                                                dots3.selection)

    def route_on_bf16(u, kernel, bias, config):
        return plain_route(to_bf16(u), kernel, bias, config)

    def selection_on_bf16(u, c_q, p, config, theta):
        # the selector's rotated queries and keys as a bfloat16 cache and a
        # bfloat16 product would hold them; everything after is float32
        with mock.patch.object(
                dots3, "_rope", lambda x, th, w: to_bf16(plain_rope(x, th, w))):
            return plain_selection(u, c_q, p, config, theta)

    def logits(params, seq, at):
        # the jitted layer functions are traced again under each patch
        dots3._attention.clear_cache()
        return dots3.logits_at(params, seq, at, config, held=held)

    for seed in (int(s) for s in args.seeds.split(",")):
        params = ss.make_params(model, config, seed)
        # the check's positions (random ids stand for the greedy ones: a
        # precision does not care)
        n = spec["prompt_len"]
        seq = np.random.default_rng(seed).integers(
            0, config["vocab_size"], n + spec["decode_tokens"]).astype(
            np.int32)
        at = ss.check_positions(traffic)
        first = at.index(n - 1)
        want = logits(params, seq, at)
        with mock.patch.object(dots3, "route", route_on_bf16):
            routed = logits(params, seq, at)
        with mock.patch.object(dots3, "selection", selection_on_bf16):
            selected = logits(params, seq, at)
        got = logits(to_e4m3(params), seq, at)
        line = {"workload": args.workload, "seed": seed}
        for name, other in (("e4m3_weights", got),
                            ("bf16_router_input", routed),
                            ("bf16_selector_inputs", selected)):
            line[f"{name}_rel_err"] = rel_err(other, want)
            line[f"{name}_rel_err_first_token"] = rel_err(other[first],
                                                          want[first])
            line[f"{name}_rel_err_after_decode"] = rel_err(other[-1],
                                                           want[-1])
        print(json.dumps(dict(line, tolerance=spec["tolerance"],
                              device=jax.devices()[0].device_kind)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
