"""Lowered text of the accepted K/V engines' ``_decode`` and ``_prefill``
programs at their cells' shapes, to compare two checkouts bit for bit (the
check PRs 29, 30 and 33 made: a change to ``serving/kv_cache.py`` that is
not meant for the Mistral and Nemotron-H cells leaves every file the same).

    python tools/lowered_programs.py <checkout> <out dir>     # once a tree
    diff -rq <out dir of the parent> <out dir of the change>

Nothing runs and no weight is made: each engine is built on one dummy leaf
and its jitted programs are lowered on shapes - the cell's cache, the
configuration's parameters, every prefill bucket - once as the CPU traces
them (every ``jax.numpy`` reference) and once as the chip does (kernels
dispatched, shapes placed on a described v5e).  A Pallas kernel's serialized
body carries its source's path: compare the ``tpu`` files of two checkouts
after ``sed -E 's/\\22body\\22: \\22[^\\]*\\22/BODY/g'`` (or with both trees
unpacked at one path in turn)."""

import importlib
import json
import os
import sys
from unittest import mock

CELLS = {
    "mistral": ("benchmark/configs/mistral-7b-l16.json",
                "benchmark/traffic/chat-closed.json"),
    "nemotron": ("benchmark/configs/nemotron3-super-ep4-l11.json",
                 "benchmark/traffic/chat-closed-64.json")}


def main(root: str, out: str) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, root)
    os.makedirs(out, exist_ok=True)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import apex_tpu
    from apex_tpu import serving as sv
    from apex_tpu.ops import _dispatch
    from apex_tpu.serving.engine import DECODE_VECTORS
    from apex_tpu.serving.kv_cache import init_cache

    if not apex_tpu.__file__.startswith(os.path.abspath(root)):
        sys.exit(f"imported {apex_tpu.__file__}, not {root}'s package")
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def load(rel):
        with open(os.path.join(root, rel)) as f:
            return json.load(f)

    for name, (config_file, traffic_file) in CELLS.items():
        config, traffic = load(config_file), load(traffic_file)
        runner = importlib.import_module(
            f"benchmark.runners.{traffic['runner']}")
        model = runner.build_model(config)
        wdtype = jnp.dtype(config["assumed"]["weights_dtype"])
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                jnp.zeros((1, 8), jnp.int32))
        if traffic["runner"] == "serve":     # its make_params: one dtype
            shapes = jax.tree.map(lambda l: jax.ShapeDtypeStruct(
                l.shape, wdtype if len(l.shape) >= 2 else jnp.float32),
                shapes)
        sizes = traffic["engine"]
        cache = jax.eval_shape(lambda: init_cache(
            model.cache_layers(), slots=sizes["slots"],
            max_len=sizes["max_len"], dtype=wdtype))
        for where in ("cpu", "tpu"):
            # an engine of its own each way: jit keeps a program's trace by
            # the shapes it was handed, not by where they are placed, and a
            # second lowering of one engine is the first one's trace again
            # (until PR 34 the "tpu" files held the CPU's references)
            engine = sv.DecodeEngine(
                model, {"w": jnp.zeros((1,), wdtype)}, slots=2,
                max_len=sizes["prefill_len"],
                prefill_len=sizes["prefill_len"])

            def place(l):
                return jax.ShapeDtypeStruct(
                    l.shape, l.dtype,
                    **({"sharding": chip} if where == "tpu" else {}))

            def arg(shape, dtype):
                return place(jax.ShapeDtypeStruct(shape, dtype))

            p, c = jax.tree.map(place, shapes), jax.tree.map(place, cache)
            with mock.patch.object(_dispatch, "on_tpu",
                                   lambda: where == "tpu"):
                texts = {"decode": engine._decode.lower(
                    p, c, *(arg((sizes["slots"],), dtype)
                            for dtype in DECODE_VECTORS)).as_text()}
                for b in engine.prefill_buckets:
                    texts[f"prefill_{b}"] = engine._prefill.lower(
                        p, c, arg((1, b), jnp.int32), arg((), jnp.int32),
                        arg((), jnp.int32), arg((), jnp.int32)).as_text()
            for program, text in texts.items():
                with open(os.path.join(
                        out, f"{name}_{where}_{program}.txt"), "w") as f:
                    f.write(text)
            print(name, where, sorted(texts), flush=True)


if __name__ == "__main__":
    main(*sys.argv[1:3])
