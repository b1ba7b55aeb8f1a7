"""FLOPs a Jamba forward over input embeddings needs, and the bytes its
selective scan must move, from its shapes."""


def _dims(config: dict):
    D, H = config["hidden_size"], config["num_attention_heads"]
    L = config["num_hidden_layers"]
    n_attn = sum(i % config["attn_layer_period"]
                 == config["attn_layer_offset"] for i in range(L))
    return (D, H, config["num_key_value_heads"], D // H,
            config["intermediate_size"], config["mamba_expand"] * D,
            config["mamba_d_state"], config["mamba_d_conv"],
            config["mamba_dt_rank"], n_attn, L - n_attn)


def layer_params(config: dict) -> tuple[int, int]:
    """Parameters of one attention layer and of one Mamba layer, each with
    its MLP and its two norms."""
    D, H, KV, hd, F, di, N, K, R, _, _ = _dims(config)
    common = 3 * D * F + 2 * D
    attn = D * H * hd + 2 * D * KV * hd + H * hd * D
    mamba = (D * 2 * di + K * di + di + di * (R + 2 * N) + R + 2 * N
             + R * di + di + di * N + di + di * D)
    return attn + common, mamba + common


def non_embedding_params(config: dict) -> int:
    *_, n_attn, n_mamba = _dims(config)
    attn, mamba = layer_params(config)
    return n_attn * attn + n_mamba * mamba


def step_flops(config: dict, rows: int, tokens: int) -> float:
    """One call over ``rows`` sequences of ``tokens`` positions: per token,
    every layer's linear maps (2 per multiply-add) and MLP; in each Mamba
    layer the depthwise convolution (2 per tap), the scan's 6 operations per
    state element (dt*A, the decay, dt*x*B, the update's add, and C.h's
    multiply-add) plus dt*x; in each attention layer causal attention
    (scores and values over the j <= i positions); then the head for the
    last position's ``out_features`` logits.  Norms, activations and
    exponentials are not counted."""
    D, H, KV, hd, F, di, N, K, R, n_attn, n_mamba = _dims(config)
    mlp = 2 * 3 * D * F
    attn_tok = 2 * (D * H * hd + 2 * D * KV * hd + H * hd * D) + mlp
    mamba_tok = (2 * (D * 2 * di + di * (R + 2 * N) + R * di + di * D)
                 + 2 * K * di + 6 * di * N + di + mlp)
    causal = 4 * H * hd * tokens * (tokens + 1) // 2
    per_row = (tokens * (n_attn * attn_tok + n_mamba * mamba_tok)
               + n_attn * causal + 2 * D * config["out_features"])
    return float(rows * per_row)


def scan_bytes(rows: int, tokens: int, config: dict) -> int:
    """Bytes the selective scans of one call must move, summed over the
    Mamba layers: per layer x and dt read and y written at float32
    (rows, tokens, d_inner), B and C read at float32 (rows, tokens,
    d_state), and A (d_inner, d_state) at float32.  Counted from shapes,
    whatever implements the scan."""
    *_, di, N, _, _, _, n_mamba = _dims(config)
    per_layer = 4 * (3 * rows * tokens * di + 2 * rows * tokens * N + di * N)
    return n_mamba * per_layer
