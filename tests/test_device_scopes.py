"""The scopes of ``apex_tpu/obs/scopes.py`` in the engine's compiled programs.

Each of the five tiny serving configurations - the four families of the
benchmark's tests and the Llama family behind a block table - has its
``_decode`` and its largest ``_prefill`` compiled here on the CPU, and the
compiled text is read the way ``benchmark/lib/device_scopes.py`` reads a
profile's modules: every scope the vocabulary lists for the family is
there, no ``apex.`` name outside the vocabulary is, nearly every instruction
that takes device time has a component, and the scopes leave the lowered
program as it was.
"""

import contextlib
import importlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu import serving as sv  # noqa: E402
from apex_tpu.obs import scopes  # noqa: E402
from apex_tpu.serving.engine import DECODE_VECTORS  # noqa: E402
from benchmark.lib import device_scopes as ds  # noqa: E402

EVERY = {"embed", "norm", "attn_proj", "cache_write", "cache_read", "head"}
# (configuration, traffic, engine options, the scopes of the family)
FAMILIES = {
    "llama": ("tiny-mistral", "tiny-chat-closed", {}, EVERY | {"mlp"}),
    "llama-paged": ("tiny-mistral", "tiny-chat-closed",
                    {"paged": sv.PagedCacheConfig(block_size=16)},
                    EVERY | {"mlp"}),
    "nemotron-h": ("tiny-nemotron-h", "tiny-chat-closed-hybrid", {},
                   EVERY | {"state", "mlp", "router", "experts"}),
    "dots3": ("tiny-dots3-note", "tiny-longdoc-closed", {},
              EVERY | {"select", "mlp", "router", "experts"}),
    "mellum": ("tiny-mellum", "tiny-repo-closed", {},
               EVERY | {"router", "experts"}),
}
TIMED = ("dot", "fusion", "custom-call", "while", "sort")


def _load(rel):
    with open(os.path.join(ROOT, "benchmark", rel)) as f:
        return json.load(f)


def lowered(family):
    """``{"decode": Lowered, "prefill": Lowered}`` of a fresh engine of the
    family at its tiny cell's sizes (jit keeps a trace by shapes: a second
    lowering of one engine would be the first one's trace again)."""
    config_name, traffic_name, options, _ = FAMILIES[family]
    config, traffic = (_load(f"configs/{config_name}.json"),
                       _load(f"traffic/{traffic_name}.json"))
    runner = importlib.import_module(
        f"benchmark.runners.{traffic['runner']}")
    model = runner.build_model(config)
    wdtype = jnp.dtype(config["assumed"]["weights_dtype"])
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    sizes = traffic["engine"]
    engine = sv.DecodeEngine(
        model, {"w": jnp.zeros((1,), wdtype)}, slots=sizes["slots"],
        max_len=sizes["max_len"], prefill_len=sizes["prefill_len"],
        **options)
    cache = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype),
        engine._cache)
    arg = jax.ShapeDtypeStruct
    scalar = arg((), jnp.int32)
    return {
        "decode": engine._decode.lower(
            params, cache, *(arg((sizes["slots"],), dtype)
                             for dtype in DECODE_VECTORS)),
        "prefill": engine._prefill.lower(
            params, cache, arg((1, engine.prefill_buckets[-1]), jnp.int32),
            scalar, scalar, scalar)}


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def compiled(request):
    return request.param, {name: low.compile().as_text()
                           for name, low in lowered(request.param).items()}


def test_component_refuses_a_name_outside_the_vocabulary():
    with pytest.raises(ValueError, match="nonsense"):
        scopes.component("nonsense")
    for name in scopes.VOCABULARY:
        with scopes.component(name):
            pass


def test_a_decorated_function_keeps_its_name_and_opens_its_scope():
    @scopes.component(scopes.MLP)
    def double(x):
        return x * 2

    assert double.__name__ == "double"
    text = jax.jit(double).lower(jnp.ones((4,))).compile().as_text()
    assert "apex.mlp" in text


def test_every_scope_of_the_family_occurs(compiled):
    family, texts = compiled
    wanted = FAMILIES[family][3]
    for program, text in texts.items():
        found = set(re.findall(r"apex\.([A-Za-z0-9_]+)", text))
        missing = wanted - found
        assert not missing, (family, program, sorted(missing))


def test_no_apex_name_outside_the_vocabulary(compiled):
    family, texts = compiled
    for program, text in texts.items():
        found = set(re.findall(r"apex\.([A-Za-z0-9_]+)", text))
        assert found <= set(scopes.VOCABULARY), (family, program, found)


def test_nine_in_ten_timed_instructions_carry_a_component(compiled):
    family, texts = compiled
    for program, text in texts.items():
        computations = ds.text_computations(text)
        component = ds.resolve(computations)
        timed = [i.name for instrs, _ in computations.values()
                 for i in instrs if i.opcode in TIMED]
        assert len(timed) > 20, (family, program, len(timed))
        bare = [n for n in timed if component[n] == ds.UNSCOPED]
        assert len(bare) <= 0.1 * len(timed), (family, program, bare[:10])


@pytest.mark.parametrize("family", ["llama", "nemotron-h"])
def test_the_scopes_leave_the_lowered_program_as_it_was(family, monkeypatch):
    with_scopes = {name: low.as_text()
                   for name, low in lowered(family).items()}
    monkeypatch.setattr(scopes, "_open",
                        lambda name: contextlib.nullcontext())
    without = {name: low.as_text() for name, low in lowered(family).items()}
    assert with_scopes == without
    # the patch took: a compiled program of the patched trace names none
    assert "apex." not in lowered(family)["decode"].compile().as_text()
