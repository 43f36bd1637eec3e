"""The second reading a reference tolerance is set from: what the cell's
check would read if the model were computed one precision below the one its
configuration states.

    python3 benchmark/tools/lower_precision.py --workload <cell> --seeds 11,12

For a ``serve_hybrid`` cell (weights stated bfloat16, 8 bits of precision):
every matrix of the seeded weights is rounded to float8 e4m3's 4 bits of
precision (the nearest type below that the chip multiplies in; the exponent
keeps bfloat16's range, as a well-scaled 8-bit path would arrange) and the
plain reference is run on the rounded and on the original weights, over a
sequence of the check's length; the line gives ``|rounded - original| /
|original|`` of the logits at the check's two positions.  Only the weights
are rounded - activations and sums stay float32 - so an 8-bit path would
read at least this.  (``lax.reduce_precision``, not a cast there and back:
XLA:TPU drops such a pair of casts as excess precision, and the first
version of this tool read 0.0 on the chip.)  The tolerance lies between the largest error the
system reads over its seeds and this reading, with room on both sides
(``traffic/<name>.json`` ``check.tolerance_why``).

A third reading says how much of the system's error a routing choice at the
margin is: the reference once more, all float32 but for the router's input,
rounded to bfloat16 - what moves is the top-k choices that flip.
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
    if traffic["runner"] != "serve_hybrid":
        sys.exit(f"runner {traffic['runner']!r}: this tool knows the "
                 f"serve_hybrid family")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import nemotron_h
    from benchmark.runners import serve_hybrid as sh
    from benchmark.runners.serve import rel_err

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        sys.exit("needs the chip: the reading is of the cell's real size")
    model = sh.build_model(config)
    spec = traffic["check"]
    held = config["experts_held"][0]

    # donated: two copies of the weights do not fit the chip
    @functools.partial(jax.jit, donate_argnums=0)
    def to_e4m3(params):
        return jax.tree.map(
            lambda a: jax.lax.reduce_precision(a, exponent_bits=8,
                                               mantissa_bits=3)
            if a.ndim >= 2 else a, params)

    plain_route = nemotron_h.route

    def route_on_bf16(u, kernel, bias, config):
        return plain_route(
            jax.lax.reduce_precision(u, exponent_bits=8, mantissa_bits=7),
            kernel, bias, config)

    for seed in (int(s) for s in args.seeds.split(",")):
        params = sh.make_params(model, config, seed)
        # the check's two positions: the prompt's end and decode_tokens on
        # (random ids stand for the greedy ones: a precision does not care)
        n = spec["prompt_len"]
        seq = np.random.default_rng(seed).integers(
            0, config["vocab_size"], n + spec["decode_tokens"]).astype(
            np.int32)
        at = [n - 1, len(seq) - 1]
        want = nemotron_h.logits_at(params, seq, at, config, held=held)
        with mock.patch.object(nemotron_h, "route", route_on_bf16):
            flipped = nemotron_h.logits_at(params, seq, at, config, held=held)
        got = nemotron_h.logits_at(to_e4m3(params), seq, at, config,
                                   held=held)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "e4m3_weights_rel_err_first_token": rel_err(got[0],
                                                                 want[0]),
            "e4m3_weights_rel_err_after_decode": rel_err(got[1],
                                                                  want[1]),
            "bf16_router_input_rel_err_first_token": rel_err(flipped[0],
                                                             want[0]),
            "bf16_router_input_rel_err_after_decode": rel_err(flipped[1],
                                                              want[1]),
            "tolerance": spec["tolerance"],
            "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
