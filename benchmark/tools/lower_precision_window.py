"""``tools/lower_precision.py`` for a ``serve_window`` cell (that tool knows
the ``serve_hybrid`` family, ``lower_precision_sparse.py`` the
``serve_sparse`` one; neither is edited): the second reading its reference
tolerance is set from.

    python3 benchmark/tools/lower_precision_window.py --workload <cell> --seeds 11,12

Every matrix of the seeded weights (stated bfloat16, 8 bits of precision) is
rounded to float8 e4m3's 4 bits (``lax.reduce_precision``: XLA:TPU drops a
pair of casts) and the plain reference is run on the rounded and on the
original weights, over a sequence of the check's length; the line gives
``|rounded - original| / |original|`` of the logits at the two positions the
check decides by, the first token's and the last decode step's.  Only the
weights are rounded, so an 8-bit path would read at least this.

One more reading says how much of the system's error a choice at the margin
is: the reference all float32 but for the router's input, rounded to
bfloat16 (top-8 choices that flip).
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
    if traffic["runner"] != "serve_window":
        sys.exit(f"runner {traffic['runner']!r}: this tool knows the "
                 f"serve_window family")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np

    from benchmark.reference import mellum
    from benchmark.runners import serve_window as sw
    from benchmark.runners.serve import rel_err

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        sys.exit("needs the chip: the reading is of the cell's real size")
    model = sw.build_model(config)
    spec = traffic["check"]

    # donated: two copies of the weights do not fit the chip
    @functools.partial(jax.jit, donate_argnums=0)
    def to_e4m3(params):
        return jax.tree.map(
            lambda a: jax.lax.reduce_precision(a, exponent_bits=8,
                                               mantissa_bits=3)
            if a.ndim >= 2 else a, params)

    plain_probs = mellum.router_probs

    def probs_on_bf16(u, kernel):
        return plain_probs(jax.lax.reduce_precision(
            u, exponent_bits=8, mantissa_bits=7), kernel)

    for seed in (int(s) for s in args.seeds.split(",")):
        params = sw.make_params(model, config, seed)
        # the check's positions (random ids stand for the greedy ones: a
        # precision does not care)
        n = spec["prompt_len"]
        seq = np.random.default_rng(seed).integers(
            0, config["vocab_size"], n + spec["decode_tokens"]).astype(
            np.int32)
        at = [n - 1, len(seq) - 1]
        want = mellum.logits_at(params, seq, at, config)
        with mock.patch.object(mellum, "router_probs", probs_on_bf16):
            routed = mellum.logits_at(params, seq, at, config)
        got = mellum.logits_at(to_e4m3(params), seq, at, config)
        line = {"workload": args.workload, "seed": seed}
        for name, other in (("e4m3_weights", got),
                            ("bf16_router_input", routed)):
            line[f"{name}_rel_err_first_token"] = rel_err(other[0], want[0])
            line[f"{name}_rel_err_after_decode"] = rel_err(other[1], want[1])
        print(json.dumps(dict(line, tolerance=spec["tolerance"],
                              device=jax.devices()[0].device_kind)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
