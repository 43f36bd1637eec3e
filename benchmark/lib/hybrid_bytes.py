"""Bytes one decode step of a Nemotron-H model must move across HBM, from
the configuration's shapes (``lib/bytes.py`` counts a dense model's)."""

from benchmark.lib.bytes import ITEMSIZE


def touched_share(counters: dict, config: dict):
    """Mean share of the held experts a decode step gives at least one
    token, from ``runners/serve_hybrid.py``'s counters (sums over steps and
    expert layers); None when the run counted none."""
    steps = counters.get("moe_steps")
    if not steps:
        return None
    return counters["moe_touched"] / (steps * config["n_routed_experts"])


def held_expert_matrices(config: dict, touched_share: float) -> float:
    """Bytes of the two matrices of each expert held here, over every expert
    layer, times the share of them a step touches: what the grouped
    products of one decode step must read."""
    w = ITEMSIZE[config.get("assumed", {}).get("weights_dtype", "bfloat16")]
    return (config["hybrid_override_pattern"].count("E") * w
            * config["n_routed_experts"] * 2 * config["moe_latent_size"]
            * config["moe_intermediate_size"] * float(touched_share))


def nemotron_h_decode_step(config: dict, *, lanes: int, kv_tokens: int,
                           touched_share: float) -> int:
    """One batched decode step of ``lanes`` active lanes:

    - every matrix outside the routed experts, read once in
      ``assumed.weights_dtype`` (a Mamba layer's in and out projections and
      its convolution; an attention layer's q, k, v, o; an expert layer's
      latent pair and shared expert; the head), the router's matrix and
      every per-channel vector in float32;
    - the two matrices of each held expert times ``touched_share``, the mean
      share of held experts that a step gives at least one token (the
      window's ``moe_touched / (moe_steps x experts held)``): an expert
      nobody chose is not read;
    - the recurrent state of the active lanes, read and written: the SSM
      state in float32 and the convolution tail in the weights' type;
    - the K and V rows of the ``kv_tokens`` cached tokens the active lanes
      attend, in the weights' type.

    Activations, the rows the step appends, the embedding rows and anything
    the compiler spills are left out: a share of a roofline counts what the
    algorithm needs.  The program as built also reads and writes the state
    of idle lanes; that is its cost, not the algorithm's."""
    w = ITEMSIZE[config.get("assumed", {}).get("weights_dtype", "bfloat16")]
    h = config["hidden_size"]
    pattern = config["hybrid_override_pattern"]
    n_mamba, n_attn, n_moe = (pattern.count(k) for k in "M*E")
    heads, hd = config["mamba_num_heads"], config["mamba_head_dim"]
    d_inner = heads * hd
    conv_dim = d_inner + 2 * config["n_groups"] * config["ssm_state_size"]
    conv_rows = config["conv_kernel"]
    mamba = (w * (h * (d_inner + conv_dim + heads) + d_inner * h
                  + (conv_rows + 1) * conv_dim)
             + 4 * (3 * heads + d_inner + h))
    q_o = 2 * h * config["num_attention_heads"] * config["head_dim"]
    k_v = 2 * h * config["num_key_value_heads"] * config["head_dim"]
    attn = w * (q_o + k_v) + 4 * h
    latent = config["moe_latent_size"]
    published = config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"])
    moe = (w * (2 * h * latent
                + 2 * h * config["moe_shared_expert_intermediate_size"])
           + 4 * (h * published + published + h))
    head = w * h * config["vocab_size"] + 4 * h
    state = 2 * int(lanes) * n_mamba * (
        4 * heads * hd * config["ssm_state_size"]
        + w * (conv_rows - 1) * conv_dim)
    kv = (int(kv_tokens) * n_attn * 2 * config["num_key_value_heads"]
          * config["head_dim"] * w)
    return int(n_mamba * mamba + n_attn * attn + n_moe * moe
               + held_expert_matrices(config, touched_share)
               + head + state + kv)
