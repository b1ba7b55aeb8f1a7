"""FLOPs a Qwen3 forward over input embeddings needs, from its shapes."""


def layer_params(config: dict) -> int:
    """Parameters of one decoder layer, norms included."""
    D, F = config["hidden_size"], config["intermediate_size"]
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    attn = D * H * hd + 2 * D * KV * hd + H * hd * D
    return attn + 3 * D * F + 2 * D + 2 * hd


def non_embedding_params(config: dict) -> int:
    return config["num_hidden_layers"] * layer_params(config)


def step_flops(config: dict, rows: int, tokens: int) -> float:
    """One call over ``rows`` sequences of ``tokens`` positions: every
    layer's linear maps for every token (2 per multiply-add), causal
    attention (scores and values over the j <= i positions), and the head
    for the last position's ``out_features`` logits only.  Norms, RoPE and
    the softmax's exponentials are not counted."""
    D, F = config["hidden_size"], config["intermediate_size"]
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    hd, L = config["head_dim"], config["num_hidden_layers"]
    linear = 2 * (D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F)
    attn = 4 * H * hd * tokens * (tokens + 1) // 2
    per_row = L * (tokens * linear + attn) + 2 * D * config["out_features"]
    return float(rows * per_row)
