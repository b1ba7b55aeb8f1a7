"""FLOPs a Mamba-1 forward over input embeddings needs, from its shapes."""


def layer_params(config: dict) -> int:
    """Parameters of one Mamba layer, its norm included."""
    D, di = config["d_model"], config["d_inner"]
    N, K, R = config["d_state"], config["d_conv"], config["dt_rank"]
    return (D * 2 * di + K * di + di + di * (R + 2 * N) + R * di + di
            + di * N + di + di * D + D)


def non_embedding_params(config: dict) -> int:
    return config["n_layer"] * layer_params(config)


def step_flops(config: dict, rows: int, tokens: int) -> float:
    """One call over ``rows`` sequences of ``tokens`` positions: per token
    and layer, the linear maps (2 per multiply-add), the depthwise
    convolution (2 per tap), and the scan's 6 operations per state element
    (dt*A, the decay, dt*x*B, the update's add, and C.h's multiply-add) plus
    dt*x; then the head for the last position's ``out_features`` logits.
    Norms, activations and exponentials are not counted."""
    D, di = config["d_model"], config["d_inner"]
    N, K, R = config["d_state"], config["d_conv"], config["dt_rank"]
    linear = 2 * (D * 2 * di + di * (R + 2 * N) + R * di + di * D)
    per_token = linear + 2 * K * di + 6 * di * N + di
    per_row = (config["n_layer"] * tokens * per_token
               + 2 * D * config["out_features"])
    return float(rows * per_row)
