"""Operations one prompt chunk of a Xing4.0 model needs, from the
configuration's shapes: the model's count, whatever implements it
(``lib/sparse_flops.py`` counts a dots3-note chunk's)."""

from benchmark.lib import mla_bytes as mb
from benchmark.lib.sparse_flops import _visible


def xing_prefill_chunk(config: dict, *, tokens: int, offset: int) -> float:
    """Operations of one chunk of ``tokens`` real rows that starts at
    position ``offset`` (two a multiply-add):

    - matrices, a row: every layer's attention and its two mHC projections,
      the dense MLPs, each expert layer's router, shared experts and the
      ``num_experts_per_tok`` experts a row's choices land on (a share of
      them by expectation where a chip holds a share); once: the read-out's
      projection and the head for the chunk's last row, the only one whose
      logits anybody reads;
    - attention, every layer, causal: each row scores every visible key
      (``offset + t + 1`` for row ``t``) on every head over ``nope +
      rope`` channels and sums ``v_head_dim`` of each, after each of the
      ``offset + tokens`` visible rows is expanded once from its latent
      values to every head's K-nope and V (the explicit form: fewer
      operations at a chunk's width than the absorbed one).

    A program that reads whole blocks under a mask, expands a row for each
    group of heads, or multiplies the head for every row does more than
    this and reads a lower share of the peak, never a higher one."""
    h, layers = config["hidden_size"], config["num_hidden_layers"]
    dense = config["first_k_dense_replace"]
    n_moe = layers - dense
    published = config["n_routed_experts"]
    held = config.get("experts_held", [0, published])[1]
    nc = config["hc_mult"] * h
    per_row = (layers * (mb.attention_matrices(config)
                         + 2 * nc * mb.mhc_columns(config))
               + dense * 3 * h * config["intermediate_size"]
               + n_moe * (h * published
                          + (config["n_shared_experts"]
                             + config["num_experts_per_tok"] * held
                             / published) * mb.expert_matrices(config)))
    matrices = 2.0 * (tokens * per_row + nc * config["hc_mult"]
                      + h * config["vocab_size"])
    heads = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    v = config["v_head_dim"]
    scores = 2.0 * _visible(offset, tokens, 1 << 62) * heads * (nope + rope
                                                                + v)
    expand = (2.0 * (offset + tokens) * config["kv_lora_rank"] * heads
              * (nope + v))
    return matrices + layers * (scores + expand)
