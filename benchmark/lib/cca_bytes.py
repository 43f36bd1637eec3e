"""Bytes one decode step of a ZAYA1 model must move across HBM, from the
configuration's shapes (``lib/window_bytes.py`` counts a Mellum's,
``lib/mla_bytes.py`` a Xing4.0's)."""

from benchmark.lib import hybrid_bytes
from benchmark.lib.bytes import ITEMSIZE


def reads(config: dict) -> bool:
    """Whether ``config`` is of the family these counts know: compressed
    convolutional attention beside a router MLP of ``router_hidden_size``."""
    return all(k in config for k in ("cca_time0", "cca_time1",
                                     "router_hidden_size", "num_experts"))


def _w(config: dict) -> int:
    return ITEMSIZE[config.get("assumed", {}).get("weights_dtype",
                                                  "bfloat16")]


def mixed(config: dict) -> int:
    """Channels of ``[q~ ; k~]``, what the two convolutions mix."""
    return ((config["num_attention_heads"] + config["num_key_value_heads"])
            * config["head_dim"])


def tail_width(config: dict) -> int:
    """Values a slot carries a layer: the last ``cca_time0 - 1`` inputs of
    the first convolution, the last ``cca_time1 - 1`` of the second, the
    shifted value heads' row."""
    return ((config["cca_time0"] - 1 + config["cca_time1"] - 1)
            * mixed(config)
            + config["num_key_value_heads"] // 2 * config["head_dim"])


def attention_elements(config: dict) -> int:
    """Elements of one layer's CCA in the weights' type: q, k, v, o, the
    two convolutions' kernels and biases."""
    h, d = config["hidden_size"], config["head_dim"]
    heads, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    m = mixed(config)
    return (2 * h * heads * d + 2 * h * nkv * d
            + config["cca_time0"] * m + config["cca_time1"] * (heads + nkv)
            * d * d + 2 * m)


def router_floats(config: dict) -> int:
    """float32 values of one layer's router and of what else is float32 in
    it: the down-projection, gamma, the MLP, the balancing bias, the
    temperatures and the two norm scales."""
    h, r, e = (config["hidden_size"], config["router_hidden_size"],
               config["num_experts"])
    return (h * r + 1 + 2 * r * r + r * e + e
            + config["num_attention_heads"] + 2 * h)


def expert_matrices(config: dict) -> int:
    """Elements of one gated expert: gate, up, down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def held(config: dict) -> int:
    return config.get("experts_held", [0, config["num_experts"]])[1]


def touched_share(counters: dict, config: dict):
    """``hybrid_bytes.touched_share`` for this family (its experts are
    counted under ``num_experts``); None when the run counted none."""
    return hybrid_bytes.touched_share(counters,
                                      {"n_routed_experts": held(config)})


def held_expert_matrices(config: dict, touched_share: float) -> float:
    """Bytes of the three matrices of each expert held here, over the
    ``num_hidden_layers`` layers the chip holds (``layer_types`` lists the
    published 40), times the share of them a step touches: what the grouped
    products of one decode step must read."""
    return (config["num_hidden_layers"] * held(config)
            * expert_matrices(config) * _w(config) * float(touched_share))


def row_bytes(config: dict) -> int:
    """Bytes of one cached token of one layer: K and V of every K/V head."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] * _w(config)


def zaya_decode_step(config: dict, *, lanes: int, kv_tokens: int,
                     touched_share: float) -> int:
    """One batched decode step of ``lanes`` active lanes:

    - every layer's CCA matrices and kernels once, in the weights' type, its
      router, temperatures and norm scales in float32; the final norm; the
      tied table once, as the head (of the embedding one row a lane is
      read, left out);
    - :func:`held_expert_matrices` at ``touched_share``: an expert nobody
      chose is not read;
    - the K and V rows the active lanes attend, ``kv_tokens`` (the cached
      tokens of the step's ``engine.decode`` span) in each layer, and the
      row each lane appends;
    - each active lane's convolution tail in each layer, read and written.

    Activations and anything the compiler spills are left out: a share of a
    roofline counts what the algorithm needs.  The program as built also
    reads and writes the tails of idle lanes; that is its cost, not the
    algorithm's."""
    layers, w = config["num_hidden_layers"], _w(config)
    h = config["hidden_size"]
    matrices = layers * attention_elements(config) + config["vocab_size"] * h
    floats = layers * router_floats(config) + h
    return int(w * matrices + 4 * floats
               + held_expert_matrices(config, touched_share)
               + (int(kv_tokens) + int(lanes)) * layers * row_bytes(config)
               + 2 * int(lanes) * layers * tail_width(config) * w)
