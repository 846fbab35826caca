"""Closed-form operation and byte counts of Jamba, from a
configuration's sizes (benchmark/configs/jamba2-3b-serve.json keys),
beside lib/flops.py and under its rules: what the ALGORITHM needs, not
what a compiler reports; one multiply-accumulate is two FLOPs.
"""

STATE_ITEMSIZE = 4      # the recurrent state is float32
SCAN_ITEMSIZE = 4       # and so are the scan's operands and its result


def is_attention(cfg: dict, i: int) -> bool:
    return i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def layer_counts(cfg: dict) -> tuple:
    """(Mamba layers, attention layers)."""
    attn = sum(is_attention(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return cfg["num_hidden_layers"] - attn, attn


def d_inner(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def _ffn_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def _mamba_matmul_params(cfg: dict) -> int:
    d, di = cfg["hidden_size"], d_inner(cfg)
    r, n = cfg["mamba_dt_rank"], cfg["mamba_d_state"]
    return d * 2 * di + di * (r + 2 * n) + r * di + di * d


def _attn_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    kv = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    return 2 * d * d + 2 * d * kv


def params(cfg: dict) -> int:
    """Every parameter (the tied embedding once)."""
    d, di = cfg["hidden_size"], d_inner(cfg)
    r, n = cfg["mamba_dt_rank"], cfg["mamba_d_state"]
    mamba, attn = layer_counts(cfg)
    small = (cfg["mamba_d_conv"] * di + di      # convolution and its bias
             + di                               # the step's bias
             + n * di + di                      # A_log, D
             + r + 2 * n)                       # the three norms
    return (cfg["vocab_size"] * d + d
            + cfg["num_hidden_layers"] * (_ffn_params(cfg) + 2 * d)
            + mamba * (_mamba_matmul_params(cfg) + small)
            + attn * _attn_params(cfg))


def active_matmul_params(cfg: dict) -> int:
    """Matmul parameters one token activates in the trunk (every layer
    is dense: `num_experts` 1). The head is counted apart, only where a
    next token is read."""
    mamba, attn = layer_counts(cfg)
    return (cfg["num_hidden_layers"] * _ffn_params(cfg)
            + mamba * _mamba_matmul_params(cfg) + attn * _attn_params(cfg))


def scan_token_flops(cfg: dict) -> int:
    """FLOPs of the recurrence for one token in ONE Mamba layer: three
    multiply-accumulates an element of the [d_state, d_inner] state
    (the decay times the state, plus delta x B, times C into y), the
    exponential counted as one operation, and delta x, D x per channel."""
    di, n = d_inner(cfg), cfg["mamba_d_state"]
    return 7 * n * di + 4 * di


def attention_pair_flops(cfg: dict) -> int:
    """One query-key pair in one attention layer, all query heads: the
    score and the weighted sum over head_dim."""
    return 2 * 2 * cfg["hidden_size"]


def token_flops(cfg: dict, context: int, head: bool) -> float:
    """FLOPs one token needs at `context` attended positions: the
    matmuls it activates, the convolution and the recurrence in every
    Mamba layer, the attention products over the context in the
    attention layers, and (only where a next token is read) the tied
    head."""
    mamba, attn = layer_counts(cfg)
    return (2 * active_matmul_params(cfg)
            + mamba * (scan_token_flops(cfg)
                       + 2 * cfg["mamba_d_conv"] * d_inner(cfg))
            + attn * context * attention_pair_flops(cfg)
            + (2 * cfg["hidden_size"] * cfg["vocab_size"] if head else 0))


def request_prefill_flops(cfg: dict, prompt: int) -> float:
    """The prompt's tokens but the last (which the decode step runs):
    token i attends i + 1 positions."""
    n = max(prompt - 1, 0)
    _, attn = layer_counts(cfg)
    return n * token_flops(cfg, 0, head=False) \
        + attn * (n * (n + 1) // 2) * attention_pair_flops(cfg)


def state_bytes_per_slot(cfg: dict, conv_itemsize: int = 2) -> int:
    """What one slot keeps beside its pages, all Mamba layers: the
    float32 recurrent state and the convolution's last d_conv - 1
    inputs."""
    mamba, _ = layer_counts(cfg)
    di = d_inner(cfg)
    return mamba * (cfg["mamba_d_state"] * di * STATE_ITEMSIZE
                    + (cfg["mamba_d_conv"] - 1) * di * conv_itemsize)


def scan_cost(cfg: dict, sequences: int, tokens: int) -> tuple:
    """(bytes, flops) the selective scan must move and do in ONE Mamba
    layer for `sequences` calls' worth of state (each sequence's
    [d_state, d_inner] state read once and written once) advancing
    `tokens` tokens in all: a token's x and delta in and its y out
    ([d_inner] each) and its B and C ([d_state] each), float32."""
    di, n = d_inner(cfg), cfg["mamba_d_state"]
    nbytes = sequences * 2 * n * di * STATE_ITEMSIZE \
        + tokens * (3 * di + 2 * n) * SCAN_ITEMSIZE
    return nbytes, tokens * scan_token_flops(cfg)
