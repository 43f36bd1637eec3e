"""Bytes one decode step of a Xing4.0 model must move across HBM, from the
configuration's shapes (``lib/sparse_bytes.py`` counts a dots3-note's,
``lib/window_bytes.py`` a Mellum's)."""

from benchmark.lib import hybrid_bytes
from benchmark.lib.bytes import ITEMSIZE

LANE = 128


def reads(config: dict) -> bool:
    """Whether ``config`` is of the family these counts know: latent
    attention with no selector under a residual stream of ``hc_mult``
    streams."""
    return all(k in config for k in ("hc_mult", "kv_lora_rank",
                                     "q_lora_rank", "n_routed_experts"))


def _w(config: dict) -> int:
    return ITEMSIZE[config.get("assumed", {}).get("weights_dtype",
                                                  "bfloat16")]


def attention_matrices(config: dict) -> int:
    """Elements of one layer's latent attention: q_a, q_b, kv_a, kv_b, o."""
    h, heads = config["hidden_size"], config["num_attention_heads"]
    q_rank, rank = config["q_lora_rank"], config["kv_lora_rank"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    v = config["v_head_dim"]
    return (h * q_rank + q_rank * heads * (nope + rope) + h * (rank + rope)
            + rank * heads * (nope + v) + heads * v * h)


def expert_matrices(config: dict) -> int:
    """Elements of one gated expert: gate, up, down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def mhc_columns(config: dict) -> int:
    """Columns of one sublayer's mHC projection: ``2n + n^2``."""
    n = config["hc_mult"]
    return 2 * n + n * n


def row_bytes(config: dict) -> int:
    """Bytes of one stored latent row: ``kv_lora_rank + qk_rope_head_dim``
    values in whole lane tiles (576 -> 640), in the weights' type."""
    width = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    return -(-width // LANE) * LANE * _w(config)


def outside_experts(config: dict) -> int:
    """Bytes of every matrix and vector outside the routed experts, read
    once a step: attention, the dense MLPs, each expert layer's shared
    experts and the head in ``assumed.weights_dtype``; the routers, their
    selection biases, the norm scales and every mHC projection (two
    sublayers a layer and the read-out, with their gains and biases) in
    float32."""
    h, layers = config["hidden_size"], config["num_hidden_layers"]
    dense = config["first_k_dense_replace"]
    n_moe = layers - dense
    experts = config["n_routed_experts"]
    nc = config["hc_mult"] * h
    matrices = (layers * attention_matrices(config)
                + dense * 3 * h * config["intermediate_size"]
                + n_moe * config["n_shared_experts"] * expert_matrices(config)
                + h * config["vocab_size"])
    vectors = (layers * (2 * h + config["q_lora_rank"]
                         + config["kv_lora_rank"])
               + h + n_moe * (h * experts + experts)
               + 2 * layers * (nc + 1) * mhc_columns(config)
               + (nc + 1) * config["hc_mult"] + 2 * layers * 3 + 1)
    return _w(config) * matrices + 4 * vectors


def held_expert_matrices(config: dict, touched_share: float) -> float:
    """Bytes of the three matrices of each expert held here, over every
    expert layer, times the share of them a step touches: what the grouped
    products of one decode step must read."""
    n_moe = config["num_hidden_layers"] - config["first_k_dense_replace"]
    held = config.get("experts_held", [0, config["n_routed_experts"]])[1]
    return (n_moe * held * expert_matrices(config) * _w(config)
            * float(touched_share))


def touched_share(counters: dict, config: dict):
    """The mean share of the held experts a decode step gives at least one
    token (``hybrid_bytes.touched_share``); None when the run counted
    none."""
    held = config.get("experts_held", [0, config["n_routed_experts"]])[1]
    return hybrid_bytes.touched_share(counters, {"n_routed_experts": held})


def xing_decode_step(config: dict, *, lanes: int, latent_rows: int,
                     touched_share: float) -> int:
    """One batched decode step of ``lanes`` active lanes:

    - :func:`outside_experts`, once;
    - :func:`held_expert_matrices` at ``touched_share``: an expert nobody
      chose is not read;
    - the latent rows every layer reads, ``latent_rows`` (the count of the
      step's ``engine.decode`` span: over the active lanes and the layers,
      every live row, the appended one among them), at their stored width;
    - the rows the step appends: one a lane a layer.

    Activations (the residual streams among them), the embedding rows and
    anything the compiler spills are left out: a share of a roofline counts
    what the algorithm needs."""
    row = row_bytes(config)
    return int(outside_experts(config)
               + held_expert_matrices(config, touched_share)
               + int(latent_rows) * row
               + int(lanes) * config["num_hidden_layers"] * row)
