"""Operations and bytes a GPT-NeoX-shaped decoder needs, from its
shapes. Matmul parameters count two operations a token forward and six
in training; attention's QK^T and PV count the causal half of
2 * 2 * T * hidden a token and layer. The embedding is a lookup. Work
the program does beyond this (the head over every prefill position, the
padded part of a bucket, cache positions past a slot's fill) is not
counted: a share of the peak is of the work the traffic needs."""


def _layer_matmul_params(cfg) -> int:
    e, f = cfg["hidden_size"], cfg["intermediate_size"]
    return 4 * e * e + 2 * e * f


def train_flops_per_item(cfg, mix) -> float:
    """Per token of a packed row of `sequence` tokens."""
    e, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    dense = layers * _layer_matmul_params(cfg) + e * cfg["vocab_size"]
    attention = layers * 2 * mix["sequence"] * e   # causal half of 4*T*e
    return 6.0 * dense + 3.0 * attention


def serve_flops(cfg, c) -> float:
    """Of every prompt and generated token of the window: `c` holds
    `prompt_tokens`, `tokens_out`, and `attention_positions`, the sum
    over all those tokens of the positions each attends to."""
    e, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    tokens = c["prompt_tokens"] + c["tokens_out"]
    return (2.0 * tokens * layers * _layer_matmul_params(cfg)
            + 2.0 * c["tokens_out"] * e * cfg["vocab_size"]
            + 4.0 * layers * e * c["attention_positions"])


def decode_weight_bytes(cfg, itemsize: int) -> int:
    """What one decode step has to read of the weights: every block and
    the head (of the embedding only the rows of the step's tokens)."""
    e, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = _layer_matmul_params(cfg) + 4 * e + f + e + 4 * e
    return itemsize * (cfg["num_hidden_layers"] * per_layer
                       + e * cfg["vocab_size"])


def cache_bytes_per_position(cfg, itemsize: int) -> int:
    return 2 * cfg["num_hidden_layers"] * cfg["hidden_size"] * itemsize
