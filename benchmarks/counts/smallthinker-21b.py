"""Operations the `smallthinker-21b` decoder needs, from its shapes.
Matmul parameters count two operations a token: the four attention
projections (28 query and 4 key/value heads of 128), the router, and SIX
experts of three projections each (the 58 experts a token is not routed
to are not work the traffic needs). Attention's QK^T and PV count
2 * 2 * 28 * 128 a position attended to. A full layer attends to every
earlier position: the driver's `attention_positions`. A window layer
attends to min(position, 4096) of them, and the driver's counters
cannot tell a position inside the window from one beyond it; the lesser
count that is true of every traffic is taken: `attention_positions`
scaled by window / context, which a window layer reaches only if every
token sits at the context's end. The head counts over generated tokens
only; the embedding is a lookup. Work the program does beyond this (the
padded part of a bucket, idle slots' experts, cache positions past a
slot's fill) is not counted: a share of the peak is of the work the
traffic needs."""


def layer_params(cfg) -> dict:
    """Parameters of one layer, by part."""
    e, hd = cfg["hidden_size"], cfg["head_dim"]
    nq = cfg["num_attention_heads"] * hd
    nk = cfg["num_key_value_heads"] * hd
    return {"attention": 2 * e * nq + 2 * e * nk,
            "router": e * cfg["moe_num_primary_experts"],
            "expert": 3 * e * cfg["moe_ffn_hidden_size"],
            "norms": 2 * e}


def parameters(cfg) -> int:
    """Every parameter held: all experts, embedding, head, final norm."""
    p = layer_params(cfg)
    layer = p["attention"] + p["router"] + p["norms"] \
        + cfg["moe_num_primary_experts"] * p["expert"]
    e = cfg["hidden_size"]
    return cfg["num_hidden_layers"] * layer + 2 * e * cfg["vocab_size"] + e


def _layer_kinds(cfg):
    n = cfg["num_hidden_layers"]
    window = sum(1 for w in cfg["sliding_window_layout"][:n] if w)
    return n - window, window


def active_matmul_params(cfg) -> int:
    """Matmul parameters one token passes through in one layer."""
    p = layer_params(cfg)
    return p["attention"] + p["router"] \
        + cfg["moe_num_active_primary_experts"] * p["expert"]


def serve_flops(cfg, c) -> float:
    """Of every prompt and generated token of the window: `c` holds
    `prompt_tokens`, `tokens_out`, and `attention_positions`, the sum
    over all those tokens of the positions each attends to."""
    full, window = _layer_kinds(cfg)
    tokens = c["prompt_tokens"] + c["tokens_out"]
    per_position = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    in_window = cfg["sliding_window_size"] / cfg["max_position_embeddings"]
    return (2.0 * tokens * cfg["num_hidden_layers"]
            * active_matmul_params(cfg)
            + 2.0 * c["tokens_out"] * cfg["hidden_size"] * cfg["vocab_size"]
            + per_position * c["attention_positions"]
            * (full + window * min(1.0, in_window)))
