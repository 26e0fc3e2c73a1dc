"""Operations and bytes the `olmo-hybrid-7b` decoder needs, from its
shapes. Matmul parameters count two operations a token: a full layer's
four attention projections (30 heads of 128 over 30 K/V heads), a linear
layer's q, k, v, gate and output projections and its two gates' (Wa,
Wb), every layer's three FFN matrices. A full layer's QK^T and PV count
2 * 2 * 30 * 128 a position attended to: the driver's
`attention_positions`. A linear layer's recurrence counts in its LEAST
form, 3 * 96 * 192 multiply-adds a head and token (S^T k, the rank-one
update, S^T q); what the chunked prefill spends beyond that (the
chunk's K K^T, Q K^T, its triangular solve) is not counted, so no share
can pass 100% by it. The convolution's four taps, the norms and the
gates' elementwise work are not counted either. The head counts over
generated tokens only; the embedding is a lookup. Work the program does
beyond this (the padded part of a bucket, idle slots, cache positions
past a slot's fill) is not counted: a share of the peak is of the work
the traffic needs.

`cache_bytes_per_position` counts the full layers' K and V alone. What
a step moves of the recurrent layers' state is no function of positions:
`recurrent_bytes_per_slot`, read once and written once for each live
slot at the least (the program replaces every slot's, live or not), is
the term `readers/decode_hbm_share_recurrent.py` adds for this cell's
`decode_hbm_share.hybrid`; `readers/decode_hbm_share.py` has no such
term, so this cell is not on `decode_hbm_share`'s list."""

_LINEAR = "linear_attention"


def _kinds(cfg):
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    linear = sum(1 for k in kinds if k == _LINEAR)
    return len(kinds) - linear, linear


def layer_params(cfg) -> dict:
    """Parameters of one layer, by part: the two mixers' matrices and
    what else they hold (taps, gates' vectors, norms), the FFN, the two
    sub-layer norms."""
    e = cfg["hidden_size"]
    hd = e // cfg["num_attention_heads"]
    nq, nkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    h = cfg["linear_num_value_heads"]
    nk = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    nv = h * cfg["linear_value_head_dim"]
    return {"full": 2 * e * nq + 2 * e * nkv,
            "full_other": nq + nkv,
            "linear": e * (2 * nk + 3 * nv) + 2 * e * h,
            "linear_other": cfg["linear_conv_kernel_dim"] * (2 * nk + nv)
            + 2 * h + cfg["linear_value_head_dim"],
            "ffn": 3 * e * cfg["intermediate_size"],
            "norms": 2 * e}


def parameters(cfg) -> int:
    """Every parameter held: the layers, embedding, head, final norm."""
    p, (full, linear) = layer_params(cfg), _kinds(cfg)
    e = cfg["hidden_size"]
    return (full * (p["full"] + p["full_other"])
            + linear * (p["linear"] + p["linear_other"])
            + (full + linear) * (p["ffn"] + p["norms"])
            + 2 * e * cfg["vocab_size"] + e)


def matmul_params(cfg) -> int:
    """Matmul parameters one token passes through in all layers."""
    p, (full, linear) = layer_params(cfg), _kinds(cfg)
    return full * p["full"] + linear * p["linear"] \
        + (full + linear) * p["ffn"]


def serve_flops(cfg, c) -> float:
    """Of every prompt and generated token of the window: `c` holds
    `prompt_tokens`, `tokens_out`, and `attention_positions`, the sum
    over all those tokens of the positions each attends to."""
    full, linear = _kinds(cfg)
    tokens = c["prompt_tokens"] + c["tokens_out"]
    e = cfg["hidden_size"]
    per_position = 4.0 * e          # 2 * 2 * heads * head_dim
    recurrence = 2.0 * 3 * cfg["linear_num_value_heads"] \
        * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"]
    return (2.0 * tokens * matmul_params(cfg)
            + 2.0 * c["tokens_out"] * e * cfg["vocab_size"]
            + per_position * full * c["attention_positions"]
            + recurrence * linear * tokens)


def decode_weight_bytes(cfg, itemsize: int) -> int:
    """What one decode step has to read of the weights: every layer's
    matrices and taps in the served type, its float32 vectors, and the
    head (of the embedding only the rows of the step's tokens)."""
    p, (full, linear) = layer_params(cfg), _kinds(cfg)
    e = cfg["hidden_size"]
    return (itemsize * (matmul_params(cfg) + e * cfg["vocab_size"])
            + 4 * (full * p["full_other"] + linear * p["linear_other"]
                   + (full + linear) * p["norms"] + e))


def cache_bytes_per_position(cfg, itemsize: int) -> int:
    """K and V of the full-attention layers; the recurrent layers keep
    nothing by position (see the note on `decode_hbm_share` above)."""
    full, _ = _kinds(cfg)
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2 * full * cfg["num_key_value_heads"] * hd * itemsize


def recurrent_bytes_per_slot(cfg, state_itemsize: int,
                             tail_itemsize: int) -> int:
    """What the linear layers keep a serving slot: a state of
    key x value a head and the convolution's tail, the last taps - 1
    rows of the q, k, v projections before it."""
    _, linear = _kinds(cfg)
    h = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    channels = cfg["linear_num_key_heads"] * 2 * dk + h * dv
    return linear * (h * dk * dv * state_itemsize
                     + (cfg["linear_conv_kernel_dim"] - 1) * channels
                     * tail_itemsize)
