"""The two readings a ``serve_cca`` cell's reference tolerance is set from
(``tools/lower_precision_mhc.py`` does the same for the Xing4.0 family and is
not edited):

    python3 benchmark/tools/lower_precision_cca.py --workload <cell> --seeds 11,12 [--e4m3 11,12]

For each seed the cell's weights are drawn and calibrated as its runner does
it, and the runner's own reference check is made on an engine of the cell's
size - the check's prompt through the chunk programs, its later tokens
through the cache, every logit vector it hands back against the plain
float32 forward, judged by ``serve_mhc.judge``'s rule at the check's
``routing_margin``: the system's reading, as a timed run's check reads it
but without the window.

``--e4m3``: every matrix of the weights (stated bfloat16, 8 bits of
precision) is then rounded to float8 e4m3's 4 bits (``lax.reduce_precision``;
the exponent keeps bfloat16's range) and the plain reference is run on the
rounded weights over the check's own sequence, judged against the float32
reference's logits and routing margins by the same rule: the reading of a
path in the next precision below, which the tolerance must refuse
(``e4m3_weights_ok`` false).  One line a seed.
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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--e4m3", default="",
                    help="the seeds whose weights are also read in e4m3")
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
    if traffic["runner"] != "serve_cca":
        sys.exit(f"runner {traffic['runner']!r}: this tool knows the "
                 f"serve_cca family")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from apex_tpu import serving as sv
    from benchmark.reference import zaya
    from benchmark.runners import serve_cca as sc
    from benchmark.runners.serve_mhc import judge
    from benchmark.runners.serve_sparse import check_positions

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        sys.exit("needs the chip: the reading is of the cell's real size")
    model = sc.build_model(config)
    e4m3 = set(_seeds(args.e4m3))
    at = check_positions(traffic)

    # donated: two copies of the weights and the cache do not fit the chip
    @functools.partial(jax.jit, donate_argnums=0)
    def to_e4m3(params):
        return jax.tree.map(
            lambda a: jax.lax.reduce_precision(a, exponent_bits=8,
                                               mantissa_bits=3)
            if a.ndim >= 2 else a, params)

    engine = params = None
    for seed in _seeds(args.seeds):
        if engine is not None:
            engine.params = None
        params = None
        params = sc.make_params(model, config, seed)
        if engine is None:
            engine = sv.DecodeEngine(model, params, **traffic["engine"])
        engine.params = params
        line = {"workload": args.workload, "seed": seed}
        got, want, margins, seq = sc.check_logits(engine, config, traffic,
                                                  seed)
        line.update(judge(got, want, margins, traffic))
        if seed in e4m3:
            engine.params = None
            rounded = zaya.logits_at(to_e4m3(params), seq, at, config,
                                     held=config["experts_held"][0])
            params = None
            line.update({k.replace("reference_", "e4m3_weights_"): v
                         for k, v in judge(rounded, want, margins,
                                           traffic).items()
                         if k not in ("reference_positions_decided",
                                      "reference_margin_min")})
            rounded = None
        print(json.dumps(dict(line, device=jax.devices()[0].device_kind),
                         default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
