"""A prompt chunk's head runs on the one row the engine keeps.

Every model the engine serves takes ``keep=`` (a traced row index): it cuts
its hidden state to that row after the last layer, so the final norm, the
head (and Xing's read-out of its streams) never see the other rows.  Those
act on each token alone, so the kept row's logits are the row the full
forward gives.  Here, at toy widths on the CPU, for each of the five
families: ``apply(..., keep=k)`` against row ``k`` of ``apply(...)`` at the
first, a middle and the last row; and ``DecodeEngine.prefill_chunk`` over a
prompt of several chunks, then greedy decode steps, against the same engine
driven through a model that runs the head on every row and slices after it
(what the engine did before it passed ``keep``)."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu import serving as sv  # noqa: E402
from benchmark.runners import (  # noqa: E402
    serve,
    serve_hybrid,
    serve_mhc,
    serve_sparse,
    serve_window,
)

# family -> (the runner that builds it, its toy configuration)
FAMILIES = {
    "mistral": (serve, "tiny-mistral"),
    "nemotron_h": (serve_hybrid, "tiny-nemotron-h"),
    "dots3": (serve_sparse, "tiny-dots3-note"),
    "mellum": (serve_window, "tiny-mellum"),
    "xing": (serve_mhc, "tiny-xing"),
}
ROWS, LANES = 24, 2
CHUNK, PROMPT, STEPS, MAX_LEN = 16, 40, 4, 64
# float32 against float32: the one-row head may sum in another order
TOLERANCE = 1e-5


def rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def build(family):
    runner, name = FAMILIES[family]
    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{name}.json")) as f:
        config = json.load(f)
    model = runner.build_model(config)
    params = jax.jit(model.init)(jax.random.key(0),
                                 jnp.zeros((1, 8), jnp.int32))
    return model, params, config["vocab_size"]


class AllRows:
    """``model`` as the engine ran it before ``keep``: the head on every row
    of the chunk, then the chunk's last real row (``length - 1``) sliced
    from its logits, whatever row the engine asks for."""

    def __init__(self, model):
        self._model = model

    def __getattr__(self, name):
        return getattr(self._model, name)

    def apply(self, params, *args, keep=None, **kwargs):
        logits, cache = self._model.apply(params, *args, **kwargs)
        if keep is not None:
            logits = jax.lax.dynamic_slice_in_dim(
                logits, kwargs["length"] - 1, 1, axis=0)
        return logits, cache


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def family(request):
    return (request.param,) + build(request.param)


@pytest.fixture(scope="module")
def forward(family):
    """``(every row's logits, kept(row))`` of one family: one program for
    every row, one that keeps the row it is handed."""
    _, model, params, vocab = family
    ids = np.random.default_rng(0).integers(
        0, vocab, (LANES, ROWS)).astype(np.int32)
    every = jax.jit(model.apply)(params, ids)
    kept = jax.jit(lambda p, i, k: model.apply(p, i, keep=k))
    return every, lambda row: kept(params, ids, np.int32(row))


@pytest.mark.parametrize("row", [0, ROWS // 2, ROWS - 1])
def test_the_kept_row_is_that_row_of_every_row(family, forward, row):
    vocab = family[3]
    every, kept = forward
    got = kept(row)
    assert every.shape == (ROWS, LANES, vocab)
    assert got.shape == (1, LANES, vocab)
    assert rel_err(got[0], every[row]) < TOLERANCE


def _serve(model, params, ids):
    """Each chunk's logits, then ``STEPS`` greedy tokens and their logits,
    through one ``DecodeEngine`` (slot 1 of 2: the idle lane decodes too)."""
    eng = sv.DecodeEngine(model, params, slots=2, max_len=MAX_LEN,
                          prefill_len=CHUNK, prefill_buckets=(CHUNK,))
    chunks = [np.asarray(eng.prefill_chunk(1, ids[s:s + CHUNK].tolist()))
              for s in range(0, len(ids), CHUNK)]
    active = np.array([False, True])
    tokens, steps, last = [], [], chunks[-1]
    for _ in range(STEPS):
        tokens.append(int(np.argmax(last)))
        feed = np.zeros((2,), np.int32)
        feed[1] = tokens[-1]
        last = np.asarray(eng.decode(feed, active)[1])
        steps.append(last)
    return chunks, tokens, steps


def test_prefill_chunk_matches_the_all_rows_path(family):
    """A prompt of three chunks (the last one short of its bucket), then
    greedy decode: the same chunk-end logits and the same tokens."""
    name, model, params, vocab = family
    ids = np.random.default_rng(7).integers(0, vocab, PROMPT).astype(
        np.int32)
    chunks, tokens, steps = _serve(model, params, ids)
    want_chunks, want_tokens, want_steps = _serve(AllRows(model), params, ids)
    assert len(chunks) == 3
    for got, want in zip(chunks, want_chunks):
        assert got.shape == (vocab,) and rel_err(got, want) < TOLERANCE, name
    assert tokens == want_tokens
    for got, want in zip(steps, want_steps):
        assert rel_err(got, want) < TOLERANCE, name
