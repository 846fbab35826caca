"""Closed-form operation and byte counts of the EXAONE-MoE share, from
a configuration's sizes (benchmark/configs/k-exaone-ep8-serve.json
keys), beside lib/flops.py and under its rules: what the ALGORITHM
needs, not what a compiler reports; one multiply-accumulate is two
FLOPs. Held experts' and the band's work only: an expert on another
chip and a key outside the window cost this chip nothing.
"""


def layer_counts(cfg: dict) -> tuple:
    """(window layers, global layers) among the layers held."""
    held = cfg["sliding_windows"][:cfg["num_hidden_layers"]]
    window = sum(1 for w in held if w)
    return window, len(held) - window


def kv_lanes(cfg: dict) -> int:
    """A token's K (or V) row in one layer."""
    return cfg["num_key_value_heads"] * cfg["head_dim"]


def _attn_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    return 2 * d * q + 2 * d * kv_lanes(cfg)


def _gated_params(cfg: dict, width: int) -> int:
    return 3 * cfg["hidden_size"] * width


def _layers(cfg: dict) -> tuple:
    dense = cfg["first_k_dense_replace"]
    return dense, cfg["num_hidden_layers"] - dense


def held_params(cfg: dict) -> int:
    """Parameters this share holds (norm scales left out: 0.002%)."""
    d = cfg["hidden_size"]
    dense, moe = _layers(cfg)
    outputs = cfg["ep"]["router_outputs"]
    per_moe = (_attn_params(cfg) + d * outputs + outputs
               + _gated_params(cfg, cfg["moe_intermediate_size"]
                               * cfg["num_shared_experts"])
               + cfg["num_experts"]
               * _gated_params(cfg, cfg["moe_intermediate_size"]))
    return (dense * (_attn_params(cfg)
                     + _gated_params(cfg, cfg["intermediate_size"]))
            + moe * per_moe + 2 * cfg["vocab_size"] * d)


def active_matmul_params(cfg: dict) -> float:
    """Held matmul parameters one token activates in the trunk: every
    layer's four attention projections, the dense layers' MLP, and in an
    expert layer the router, the shared expert and the token's expected
    share of routed experts that live HERE (top-k times held / router
    width: 8 x 16/128 = 1). The head is counted apart, only where a next
    token is read."""
    dense, moe = _layers(cfg)
    local = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["ep"]["router_outputs"]
    per_moe = (_attn_params(cfg)
               + cfg["hidden_size"] * cfg["ep"]["router_outputs"]
               + _gated_params(cfg, cfg["moe_intermediate_size"]
                               * cfg["num_shared_experts"])
               + local * _gated_params(cfg, cfg["moe_intermediate_size"]))
    return dense * (_attn_params(cfg)
                    + _gated_params(cfg, cfg["intermediate_size"])) \
        + moe * per_moe


def attention_pair_flops(cfg: dict) -> int:
    """One query-key pair in one layer, all query heads: the score and
    the weighted sum over head_dim."""
    return 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]


def attended(cfg: dict, context: int) -> int:
    """Query-key pairs one token at `context` attended positions costs
    over all layers held: the whole context in a global layer, at most
    the window in a window layer."""
    window, full = layer_counts(cfg)
    return full * context + window * min(context, cfg["sliding_window"])


def token_flops(cfg: dict, context: int, head: bool) -> float:
    """FLOPs one token needs at `context` attended positions (itself
    included): the held matmuls it activates, the attention products
    over the context in the global layers and over the band in the
    window layers, and (only where a next token is read) the head over
    the held vocabulary."""
    return (2 * active_matmul_params(cfg)
            + attended(cfg, context) * attention_pair_flops(cfg)
            + (2 * cfg["hidden_size"] * cfg["vocab_size"] if head else 0))


def request_prefill_flops(cfg: dict, prompt: int) -> float:
    """The prompt's tokens but the last (which the decode step runs):
    token i attends i + 1 positions in a global layer and min(i + 1,
    window) in a window layer."""
    n = max(prompt - 1, 0)
    w = min(n, cfg["sliding_window"])
    window, full = layer_counts(cfg)
    pairs = full * (n * (n + 1) // 2) \
        + window * (w * (w + 1) // 2 + (n - w) * cfg["sliding_window"])
    return 2 * active_matmul_params(cfg) * n \
        + pairs * attention_pair_flops(cfg)


def paged_attention_decode_cost(cfg: dict, context_tokens: int,
                                itemsize: int = 2) -> tuple:
    """(bytes, flops) of decode attention in ONE GLOBAL layer over
    `context_tokens` live positions summed over the batch's slots: the K
    and the V row (kv_heads * head_dim lanes each) of the live context
    read once, QK^T and PV for every query head."""
    return (2 * context_tokens * kv_lanes(cfg) * itemsize,
            context_tokens * attention_pair_flops(cfg))


def window_row_bytes(cfg: dict, itemsize: int = 2) -> int:
    """One ring row read in one window layer: K and V of one position."""
    return 2 * kv_lanes(cfg) * itemsize


def ring_bytes_per_slot(cfg: dict, itemsize: int = 2) -> int:
    """What one slot keeps beside its pages: `sliding_window` rows of K
    and of V in every window layer."""
    window, _ = layer_counts(cfg)
    return window * cfg["sliding_window"] * window_row_bytes(cfg, itemsize)
