"""Operations a step needs, computed from shapes.  Kept with the benchmark
so that no PR that claims a gain can change the count."""

from __future__ import annotations


def gpt_train_step(config: dict, traffic: dict) -> float:
    """Model FLOPs of one GPT-2 training step, forward + backward (3x the
    forward), recomputation not counted.

    Per token: ``6 * P`` for the ``P`` weights that sit in a matmul —
    ``12 h^2`` a layer (qkv ``3h^2``, attention output ``h^2``, MLP
    ``8h^2``) and the tied head ``V h`` — plus causal attention, ``6 s h``
    a layer (QK^T and PV, ``4 s h`` forward, halved by the mask).  ``V``
    is the vocabulary as run (padded).  The same terms as ``bench.py``'s
    ``6 N + 12 L h s / 2`` with ``N`` restricted to matmul weights."""
    h = config["n_embd"]
    layers = config["n_layer"]
    vocab = config["assumed"]["padded_vocab_size"]
    seq = traffic["seq_len"]
    per_token = 6 * (12 * layers * h * h + vocab * h) + 6 * layers * seq * h
    return float(per_token * traffic["batch"] * seq)
