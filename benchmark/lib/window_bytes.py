"""Bytes one decode step of a Mellum model must move across HBM, from the
configuration's shapes (``lib/bytes.py`` counts a dense model's,
``lib/hybrid_bytes.py`` a Nemotron-H's, ``lib/sparse_bytes.py`` a
dots3-note's)."""

from benchmark.lib import hybrid_bytes
from benchmark.lib.bytes import ITEMSIZE

FULL, WINDOW = "full_attention", "sliding_attention"


def reads(config: dict) -> bool:
    """Whether ``config`` is of the family these counts know: K/V heads
    under a window by ``layer_types``, and ``num_experts`` experts."""
    return all(k in config for k in ("layer_types", "sliding_window",
                                     "num_experts", "head_dim"))


def _w(config: dict) -> int:
    return ITEMSIZE[config.get("assumed", {}).get("weights_dtype",
                                                  "bfloat16")]


def attention_matrices(config: dict) -> int:
    """Elements of one attention layer's matrices: q, k, v, o."""
    h, d = config["hidden_size"], config["head_dim"]
    return (2 * h * config["num_attention_heads"] * d
            + 2 * h * config["num_key_value_heads"] * d)


def expert_matrices(config: dict) -> int:
    """Elements of one gated expert: gate, up, down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def row_bytes(config: dict) -> int:
    """Bytes of one cached token of one layer: K and V of every KV head."""
    return (2 * config["num_key_value_heads"] * config["head_dim"]
            * _w(config))


def held(config: dict) -> int:
    """The experts a layer holds here."""
    return config.get("experts_held", [0, config["num_experts"]])[1]


def touched_share(counters: dict, config: dict):
    """``hybrid_bytes.touched_share`` for this family, whose configuration
    counts its experts under another key: the mean share of the held experts
    a decode step gives at least one token; None when the run counted
    none."""
    return hybrid_bytes.touched_share(counters,
                                      {"n_routed_experts": held(config)})


def outside_experts(config: dict) -> int:
    """Bytes of every matrix and vector outside the experts, read once a
    step: attention in ``assumed.weights_dtype``, each layer's router and
    two norm scales in float32, the final norm, the head."""
    h = config["hidden_size"]
    layers = len(config["layer_types"])
    matrices = layers * attention_matrices(config) + h * config["vocab_size"]
    vectors = layers * (h * config["num_experts"] + 2 * h) + h
    return _w(config) * matrices + 4 * vectors


def held_expert_matrices(config: dict, touched_share: float) -> float:
    """Bytes of the three matrices of each expert held here, over every
    layer, times the share of them a step touches: what the grouped products
    of one decode step must read."""
    return (len(config["layer_types"]) * held(config)
            * expert_matrices(config) * _w(config) * float(touched_share))


def mellum_decode_step(config: dict, *, lanes: int, kv_tokens: int,
                       window_rows: int, touched_share: float) -> int:
    """One batched decode step of ``lanes`` active lanes:

    - :func:`outside_experts`, once;
    - :func:`held_expert_matrices` at ``touched_share``: an expert nobody
      chose is not read;
    - the K and V rows a full layer reads, ``kv_tokens`` (the cached tokens
      of the active lanes, the count of the step's ``engine.decode`` span)
      in each full layer;
    - the K and V rows the window layers read, ``window_rows`` (the span's
      count: over the active lanes and the window layers, ``min(live,
      sliding_window)``);
    - the rows the step appends: one a lane a layer.

    Activations, the embedding rows and anything the compiler spills are
    left out: a share of a roofline counts what the algorithm needs."""
    kinds = config["layer_types"]
    row = row_bytes(config)
    return int(outside_experts(config)
               + held_expert_matrices(config, touched_share)
               + int(kv_tokens) * kinds.count(FULL) * row
               + int(window_rows) * row
               + int(lanes) * len(kinds) * row)
