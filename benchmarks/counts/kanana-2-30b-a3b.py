"""Operations and bytes the `kanana-2-30b-a3b` decoder needs, from its
shapes. Matmul parameters count two operations a token: latent
attention's four matrices (the query's, the latent's down-projection,
its up-projection to every head's key and value, the output's), in an
expert layer the router, SIX routed experts of three projections each
(the 122 a token is not routed to are not work the traffic needs) and
the shared expert, in a leading dense layer its FFN. Attention counts in
its LEAST form, the expanded one: 2 * 32 * (192 + 128) operations a
position attended to (the driver's `attention_positions`); what a
decode step's absorbed form spends beyond that (2 * 32 * (2 * 512 + 64)
a position, 1088 multiply-adds against 320) is not counted, so no share
can pass 100% by it. The head counts over generated tokens only; the
embedding is a lookup. Work the program does beyond this (the padded
part of a bucket, idle slots' experts, cache positions past a slot's
fill) is not counted: a share of the peak is of the work the traffic
needs.

`decode_weight_bytes` is a FLOOR: what every decode step must read
whatever the routing (attention, shared expert, router, a dense layer's
FFN, the head) and `num_experts_per_tok` routed experts a layer, the
fewest a step with one live slot touches. The driver hands a reader no
count of the experts a step touched (PERF.md section 7 c), and the
program streams all 128 a step (`RoutedExperts._few_rows`), so
`decode_hbm_share.mla` reads far under what the memory did."""


def _kinds(cfg):
    """(leading dense layers, expert layers)."""
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def layer_params(cfg) -> dict:
    """Parameters of one layer, by part."""
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    rank, f = cfg["kv_lora_rank"], cfg["moe_intermediate_size"]
    return {"attention": e * h * (nope + rope) + e * (rank + rope)
            + rank * h * (nope + dv) + h * dv * e,
            "attention_other": rank,
            "router": e * cfg["n_routed_experts"],
            "router_other": cfg["n_routed_experts"],
            "expert": 3 * e * f,
            "shared": 3 * e * f * cfg["n_shared_experts"],
            "dense_ffn": 3 * e * cfg["intermediate_size"],
            "norms": 2 * e}


def parameters(cfg) -> int:
    """Every parameter held: all experts, embedding, head, final norm."""
    p, (dense, sparse) = layer_params(cfg), _kinds(cfg)
    e = cfg["hidden_size"]
    every = p["attention"] + p["attention_other"] + p["norms"]
    return ((dense + sparse) * every + dense * p["dense_ffn"]
            + sparse * (p["router"] + p["router_other"] + p["shared"]
                        + cfg["n_routed_experts"] * p["expert"])
            + 2 * e * cfg["vocab_size"] + e)


def matmul_params(cfg, experts: int) -> int:
    """Matmul parameters of all layers with `experts` routed experts a
    layer counted: `num_experts_per_tok` is what one token passes."""
    p, (dense, sparse) = layer_params(cfg), _kinds(cfg)
    return ((dense + sparse) * p["attention"] + dense * p["dense_ffn"]
            + sparse * (p["router"] + p["shared"] + experts * p["expert"]))


def serve_flops(cfg, c) -> float:
    """Of every prompt and generated token of the window: `c` holds
    `prompt_tokens`, `tokens_out`, and `attention_positions`, the sum
    over all those tokens of the positions each attends to."""
    tokens = c["prompt_tokens"] + c["tokens_out"]
    per_position = 2.0 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])
    return (2.0 * tokens * matmul_params(cfg, cfg["num_experts_per_tok"])
            + 2.0 * c["tokens_out"] * cfg["hidden_size"] * cfg["vocab_size"]
            + per_position * cfg["num_hidden_layers"]
            * c["attention_positions"])


def decode_weight_bytes(cfg, itemsize: int) -> int:
    """The floor of what one decode step reads of the weights (see the
    module's text): the matrices in the served type, the float32 router,
    bias and norms, the head (of the embedding only the step's rows)."""
    p, (dense, sparse) = layer_params(cfg), _kinds(cfg)
    e = cfg["hidden_size"]
    served = matmul_params(cfg, cfg["num_experts_per_tok"]) \
        - sparse * p["router"] + e * cfg["vocab_size"]
    return (itemsize * served
            + 4 * (sparse * (p["router"] + p["router_other"])
                   + (dense + sparse) * (p["attention_other"] + p["norms"])
                   + e))


def cache_bytes_per_position(cfg, itemsize: int) -> int:
    """The latent and the shared rotary key, every layer."""
    return cfg["num_hidden_layers"] * (
        cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize
