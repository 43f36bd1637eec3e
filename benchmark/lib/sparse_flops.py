"""Operations one prompt chunk of a dots3-note model needs, from the
configuration's shapes: the model's count, whatever implements it
(``lib/flops.py`` counts a training step's)."""

from benchmark.lib import sparse_bytes as sb

FULL, WINDOW = sb.FULL, sb.WINDOW


def _visible(offset: int, tokens: int, cap: int) -> int:
    """``sum over the chunk's rows t of min(offset + t + 1, cap)``: the keys
    the chunk's queries may read when each sees at most ``cap``."""
    first, last = offset + 1, offset + tokens          # keys of first, last
    if last <= cap:
        return (first + last) * tokens // 2
    if first > cap:
        return cap * tokens
    ramp = cap - first + 1                             # rows still below cap
    return (first + cap) * ramp // 2 + cap * (tokens - ramp)


def dots3_prefill_chunk(config: dict, *, tokens: int, offset: int) -> float:
    """Operations of one chunk of ``tokens`` real rows that starts at
    position ``offset`` (two a multiply-add):

    - matrices: every matrix outside the routed experts but the head, a
      row, plus the experts its choices land on here by expectation
      (``num_experts_per_tok`` x held / published of them); the head for
      the chunk's last row, the only one whose logits anybody reads;
    - selector scores: a full layer scores, for each row, every visible
      key on ``index_n_heads`` heads of ``index_head_dim``;
    - selected attention: a full layer attends ``min(visible,
      index_topk)`` rows a query in the absorbed form a selected read takes
      (scores on the stored ``kv_lora_rank + qk_rope_head_dim`` columns,
      values on ``kv_lora_rank``), every head;
    - window attention: a window layer attends ``min(visible,
      sliding_window_size)`` rows a query on expanded heads
      (``swa_qk_nope_head_dim + swa_qk_rope_head_dim`` and
      ``swa_v_head_dim``).

    A program that reads every visible row under a mask does more than
    this and reads a lower share of the peak, never a higher one."""
    kinds = config["layer_types"]
    h = config["hidden_size"]
    n_moe = len(kinds) - config["first_k_dense_replace"]
    published = config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"])
    per_row = (sum(sb.attention_matrices(config, k) for k in kinds)
               + config["first_k_dense_replace"] * 3 * h
               * config["intermediate_size"]
               + n_moe * (h * published
                          + (config["n_shared_experts"]
                             + config["num_experts_per_tok"]
                             * config["n_routed_experts"] / published)
                          * sb.expert_matrices(config)))
    matrices = 2.0 * (tokens * per_row + h * config["vocab_size"])
    n_full, n_win = kinds.count(FULL), kinds.count(WINDOW)
    seen = _visible(offset, tokens, 1 << 62)
    index = 2.0 * n_full * seen * (config["index_n_heads"]
                                   * config["index_head_dim"])
    latent = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    selected = (2.0 * n_full * _visible(offset, tokens, config["index_topk"])
                * config["num_attention_heads"]
                * (latent + config["kv_lora_rank"]))
    window = (2.0 * n_win
              * _visible(offset, tokens, config["sliding_window_size"])
              * config["swa_num_attention_heads"]
              * (config["swa_qk_nope_head_dim"]
                 + config["swa_qk_rope_head_dim"]
                 + config["swa_v_head_dim"]))
    return matrices + index + selected + window
