"""Operations and bytes the `lfm2-8b-a1b` decoder needs, from its shapes.
Matmul parameters count two operations a token: a short convolution's
two projections (in_proj to B, C and x, out_proj), attention's four
matrices, in an expert layer the router and FOUR routed experts of
three projections each (the 28 a token is not routed to are not work
the traffic needs), in a dense layer its FFN. The convolution's three
taps and its two gates are elementwise and not counted. Attention
counts 2 * 32 * (64 + 64) operations a position attended to (the
run's `attention_positions`) a layer. The head, tied to the
embedding, counts over generated tokens only; the embedding is a
lookup. Work the program does beyond this (the padded part of a
bucket, idle slots, cache positions past a slot's fill) is not counted:
a share of the peak is of the work the traffic needs.

`decode_weight_bytes` counts EVERY expert of every layer: at the cell's
128 slots a decode step routes 512 pairs over 32 experts, so every
expert is read (one is left untouched with odds of (7/8)^128, 4e-8),
whatever the routing."""


def _kinds(cfg):
    """(conv layers, attention layers, dense layers, expert layers) of
    the layers held."""
    n = cfg["num_hidden_layers"]
    types = cfg["layer_types"][:n]
    dense = min(cfg["num_dense_layers"], n)
    conv = sum(t == "conv" for t in types)
    return conv, n - conv, dense, n - dense


def layer_params(cfg) -> dict:
    """Parameters of one layer, by part."""
    e, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = e // heads
    kv = cfg["num_key_value_heads"] * hd
    return {"conv": 3 * e * e + e * e,
            "conv_taps": cfg["conv_L_cache"] * e,
            "attention": e * heads * hd + 2 * e * kv + heads * hd * e,
            "qk_norms": 2 * hd,
            "router": e * cfg["num_experts"],
            "router_other": cfg["num_experts"],
            "expert": 3 * e * cfg["moe_intermediate_size"],
            "dense_ffn": 3 * e * cfg["intermediate_size"],
            "norms": 2 * e}


def parameters(cfg) -> int:
    """Every parameter held: all experts, the embedding (which is the
    head), the final norm."""
    p, (conv, attn, dense, sparse) = layer_params(cfg), _kinds(cfg)
    e = cfg["hidden_size"]
    return (conv * (p["conv"] + p["conv_taps"])
            + attn * (p["attention"] + p["qk_norms"])
            + (conv + attn) * p["norms"] + dense * p["dense_ffn"]
            + sparse * (p["router"] + p["router_other"]
                        + cfg["num_experts"] * p["expert"])
            + e * cfg["vocab_size"] + e)


def matmul_params(cfg, experts: int) -> int:
    """Matmul parameters of all layers with `experts` routed experts a
    layer counted: `num_experts_per_tok` is what one token passes."""
    p, (conv, attn, dense, sparse) = layer_params(cfg), _kinds(cfg)
    return (conv * p["conv"] + attn * p["attention"]
            + dense * p["dense_ffn"]
            + sparse * (p["router"] + experts * p["expert"]))


def serve_flops(cfg, c) -> float:
    """Of every prompt and generated token of the window: `c` holds
    `prompt_tokens`, `tokens_out`, and `attention_positions`, the sum
    over all those tokens of the positions each attends to."""
    tokens = c["prompt_tokens"] + c["tokens_out"]
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    per_position = 2.0 * cfg["num_attention_heads"] * 2 * hd
    return (2.0 * tokens * matmul_params(cfg, cfg["num_experts_per_tok"])
            + 2.0 * c["tokens_out"] * cfg["hidden_size"] * cfg["vocab_size"]
            + per_position * _kinds(cfg)[1] * c["attention_positions"])


def decode_weight_bytes(cfg, itemsize: int) -> int:
    """What one decode step reads of the weights at the cell's rows:
    every matrix and every expert in the served type, the taps, the
    float32 router, bias and norms, and the tied head (of the embedding
    as an embedding only the step's rows)."""
    p, (conv, attn, dense, sparse) = layer_params(cfg), _kinds(cfg)
    e = cfg["hidden_size"]
    served = matmul_params(cfg, cfg["num_experts"]) - sparse * p["router"] \
        + conv * p["conv_taps"] + e * cfg["vocab_size"]
    return (itemsize * served
            + 4 * (sparse * (p["router"] + p["router_other"])
                   + attn * p["qk_norms"] + (conv + attn) * p["norms"] + e))


def cache_bytes_per_position(cfg, itemsize: int) -> int:
    """K and V of the attention layers held (8 heads of 64 each)."""
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return _kinds(cfg)[1] * 2 * cfg["num_key_value_heads"] * hd * itemsize
