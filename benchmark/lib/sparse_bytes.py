"""Bytes one decode step of a dots3-note model must move across HBM, from
the configuration's shapes (``lib/bytes.py`` counts a dense model's,
``lib/hybrid_bytes.py`` a Nemotron-H's)."""

from benchmark.lib.bytes import ITEMSIZE

FULL, WINDOW = "full_attention", "sliding_attention"


def _w(config: dict) -> int:
    return ITEMSIZE[config.get("assumed", {}).get("weights_dtype",
                                                  "bfloat16")]


def attention_matrices(config: dict, kind: str) -> int:
    """Elements of one attention layer's matrices: the two low-rank pairs,
    the gate, the output projection and, in a full layer, the selector's
    three."""
    pre = "swa_" if kind == WINDOW else ""
    h = config["hidden_size"]
    heads = config[pre + "num_attention_heads"]
    q_rank, rank = config[pre + "q_lora_rank"], config[pre + "kv_lora_rank"]
    nope, rope = (config[pre + "qk_nope_head_dim"],
                  config[pre + "qk_rope_head_dim"])
    v = config[pre + "v_head_dim"]
    n = (h * q_rank + q_rank * heads * (nope + rope) + h * (rank + rope)
         + rank * heads * (nope + v) + heads * v * h + h * heads)
    if kind == FULL:
        j, d = config["index_n_heads"], config["index_head_dim"]
        n += q_rank * j * d + h * d + h * j
    return n


def expert_matrices(config: dict) -> int:
    """Elements of one gated expert: gate, up, down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def row_bytes(config: dict) -> dict:
    """Bytes of one stored row of each kind: a selector key, a full layer's
    latent row, a window layer's."""
    w = _w(config)
    return {
        "index": w * config["index_head_dim"],
        "latent": w * (config["kv_lora_rank"] + config["qk_rope_head_dim"]),
        "window": w * (config["swa_kv_lora_rank"]
                       + config["swa_qk_rope_head_dim"])}


def outside_experts(config: dict) -> int:
    """Bytes of every matrix and vector outside the routed experts, read
    once a step: attention (with the selector), the dense MLP, each expert
    layer's shared expert in ``assumed.weights_dtype`` and its router in
    float32, the norm scales in float32, the head."""
    w = _w(config)
    h = config["hidden_size"]
    kinds = config["layer_types"]
    dense = config["first_k_dense_replace"]
    n_moe = len(kinds) - dense
    published = config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"])
    matrices = (sum(attention_matrices(config, k) for k in kinds)
                + dense * 3 * h * config["intermediate_size"]
                + n_moe * config["n_shared_experts"] * expert_matrices(config)
                + h * config["vocab_size"])
    vectors = 0
    for k in kinds:
        pre = "swa_" if k == WINDOW else ""
        vectors += (2 * h + config[pre + "q_lora_rank"]
                    + config[pre + "kv_lora_rank"]
                    + (2 * config["index_head_dim"] if k == FULL else 0))
    vectors += h + n_moe * (h * published + published)
    return w * matrices + 4 * vectors


def held_expert_matrices(config: dict, touched_share: float) -> float:
    """Bytes of the three matrices of each expert held here, over every
    expert layer, times the share of them a step touches: what the grouped
    products of one decode step must read."""
    n_moe = len(config["layer_types"]) - config["first_k_dense_replace"]
    return (n_moe * config["n_routed_experts"] * expert_matrices(config)
            * _w(config) * float(touched_share))


def dots3_decode_step(config: dict, *, lanes: int, index_rows: int,
                      attended_rows: int, window_rows: int,
                      touched_share: float) -> int:
    """One batched decode step of ``lanes`` active lanes:

    - :func:`outside_experts`, once;
    - :func:`held_expert_matrices` at ``touched_share``, the mean share of
      held experts that a step gives at least one token: an expert nobody
      chose is not read;
    - the selector keys the full layers score (``index_rows``), the latent
      rows the selection leaves to attend (``attended_rows``) and the window
      layers' rows (``window_rows``), the counts of the step's
      ``engine.decode`` span, at their stored widths;
    - the rows the step appends: a latent row and a selector key a full
      layer, a ring row a window layer, a lane.

    Activations, the embedding rows and anything the compiler spills are
    left out: a share of a roofline counts what the algorithm needs.  The
    program as built scores keys in whole blocks up to the longest lane's
    length and keeps a float32 score a row; that is its cost, not the
    algorithm's."""
    row = row_bytes(config)
    kinds = config["layer_types"]
    appended = int(lanes) * (
        kinds.count(FULL) * (row["latent"] + row["index"])
        + kinds.count(WINDOW) * row["window"])
    return int(outside_experts(config)
               + held_expert_matrices(config, touched_share)
               + int(index_rows) * row["index"]
               + int(attended_rows) * row["latent"]
               + int(window_rows) * row["window"] + appended)
