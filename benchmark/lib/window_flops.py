"""Operations one prompt chunk of a Mellum model needs, from the
configuration's shapes: the model's count, whatever implements it
(``lib/sparse_flops.py`` counts a dots3-note chunk's)."""

from benchmark.lib import window_bytes as wb
from benchmark.lib.sparse_flops import _visible

FULL, WINDOW = wb.FULL, wb.WINDOW


def mellum_prefill_chunk(config: dict, *, tokens: int, offset: int) -> float:
    """Operations of one chunk of ``tokens`` real rows that starts at
    position ``offset`` (two a multiply-add):

    - matrices: a layer's attention matrices, its router and the
      ``num_experts_per_tok`` experts a row's choices land on (every expert
      is held here; a share of them by expectation where a chip holds a
      share), a row; the head for the chunk's last row, the only one whose
      logits anybody reads;
    - full attention: a full layer scores and sums, for each row, every
      visible key on every query head (causal: ``offset + t + 1`` keys for
      row ``t``);
    - window attention: a window layer ``min(visible, sliding_window)``.

    A program that reads every key of a block under a mask, or that
    multiplies the head for every row, does more than this and reads a
    lower share of the peak, never a higher one."""
    kinds = config["layer_types"]
    h = config["hidden_size"]
    here = wb.held(config) / config["num_experts"]
    per_row = len(kinds) * (
        wb.attention_matrices(config) + h * config["num_experts"]
        + config["num_experts_per_tok"] * here * wb.expert_matrices(config))
    matrices = 2.0 * (tokens * per_row + h * config["vocab_size"])
    a_key = 2.0 * 2 * config["num_attention_heads"] * config["head_dim"]
    full = a_key * kinds.count(FULL) * _visible(offset, tokens, 1 << 62)
    window = a_key * kinds.count(WINDOW) * _visible(
        offset, tokens, config["sliding_window"])
    return matrices + full + window
