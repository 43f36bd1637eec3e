"""Bytes a program must move across HBM, from the configuration's shapes
(the memory side of a roofline; ``lib/flops.py`` is the compute side)."""

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def llama_decode_step(config: dict, kv_tokens: int) -> int:
    """One batched decode step of a Llama-family model: every matrix the
    program multiplies by, read once in ``assumed.weights_dtype`` (a
    layer's q, k, v, o, gate, up, down, and the head: the ``[vocab,
    hidden]`` matrix whether it is tied or not; not the embedding table
    as such, of which one row a lane is read), the norm scales in
    ``assumed.norm_dtype``, and the K and V rows of the ``kv_tokens``
    cached tokens the active lanes attend, in the type the cache keeps
    them in (the weights').  Activations, the rows the step appends and
    anything the compiler spills are left out: a share of a roofline
    counts what the algorithm needs."""
    h = config["hidden_size"]
    f = config["intermediate_size"]
    layers = config["num_hidden_layers"]
    heads = config["num_attention_heads"]
    kv_heads = config["num_key_value_heads"]
    assumed = config.get("assumed", {})
    head_dim = assumed.get("head_dim", h // heads)
    w = ITEMSIZE[assumed.get("weights_dtype", "bfloat16")]
    norm = ITEMSIZE[assumed.get("norm_dtype", "float32")]
    q_o = 2 * h * heads * head_dim
    k_v = 2 * h * kv_heads * head_dim
    matrices = layers * (q_o + k_v + 3 * h * f) + h * config["vocab_size"]
    scales = (2 * layers + 1) * h
    kv_row = layers * 2 * kv_heads * head_dim * w
    return matrices * w + scales * norm + int(kv_tokens) * kv_row
