"""Closed-form operation and byte counts of the DeepSeek-V2 share, from
a configuration's sizes (benchmark/configs/deepseek-v2-ep4-serve.json
keys), beside lib/flops.py and under its rules: what the ALGORITHM
needs, not what a compiler reports; one multiply-accumulate is two
FLOPs.
"""


def _mla_params(cfg: dict) -> int:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * H * qk
            + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * H * (cfg["qk_nope_head_dim"]
                                         + cfg["v_head_dim"])
            + H * cfg["v_head_dim"] * d)


def _gated_params(cfg: dict, width: int) -> int:
    return 3 * cfg["hidden_size"] * width


def held_params(cfg: dict) -> int:
    """Parameters this share holds (norm scales left out: 0.01%)."""
    d = cfg["hidden_size"]
    dense = cfg["first_k_dense_replace"]
    moe = cfg["num_hidden_layers"] - dense
    per_moe = (_mla_params(cfg)
               + _gated_params(cfg, cfg["moe_intermediate_size"]
                               * cfg["n_shared_experts"])
               + d * cfg["ep"]["router_outputs"]
               + cfg["n_routed_experts"]
               * _gated_params(cfg, cfg["moe_intermediate_size"]))
    return (dense * (_mla_params(cfg)
                     + _gated_params(cfg, cfg["intermediate_size"]))
            + moe * per_moe + 2 * cfg["vocab_size"] * d)


def active_matmul_params(cfg: dict) -> float:
    """Held matmul parameters one token activates in the trunk: every
    layer's MLA projections, the dense layers' MLP, and in an expert
    layer the router, the shared experts and the token's expected share
    of routed experts that live HERE (top-k times held / router
    width: 6 x 40/160 = 1.5). The head is counted apart, only where a
    next token is read."""
    dense = cfg["first_k_dense_replace"]
    moe = cfg["num_hidden_layers"] - dense
    local = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["ep"]["router_outputs"]
    per_moe = (_mla_params(cfg)
               + _gated_params(cfg, cfg["moe_intermediate_size"]
                               * cfg["n_shared_experts"])
               + cfg["hidden_size"] * cfg["ep"]["router_outputs"]
               + local * _gated_params(cfg, cfg["moe_intermediate_size"]))
    return dense * (_mla_params(cfg)
                    + _gated_params(cfg, cfg["intermediate_size"])) \
        + moe * per_moe


def latent_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """One token's cache rows over all layers: [c_kv | k_pe], 576 lanes
    at the published widths (the slab's zero pad lanes are not what the
    algorithm needs)."""
    return cfg["num_hidden_layers"] * itemsize \
        * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def attention_pair_flops(cfg: dict, absorbed: bool) -> int:
    """FLOPs of one query-key pair in one layer, all heads: absorbed
    (decode) the score over 576 lanes and the weighted sum over 512,
    up-projected (prefill) the score over 192 and the sum over 128."""
    H = cfg["num_attention_heads"]
    if absorbed:
        return 2 * H * (2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    return 2 * H * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                    + cfg["v_head_dim"])


def token_flops(cfg: dict, context: int, decode: bool) -> float:
    """FLOPs one token needs at `context` attended positions: the held
    matmuls it activates, the attention products over the context in
    the form its path uses (absorbed in decode, up-projected in
    prefill), and, in decode, the head over the held vocabulary."""
    trunk = 2 * active_matmul_params(cfg)
    attn = cfg["num_hidden_layers"] * context \
        * attention_pair_flops(cfg, absorbed=decode)
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"] if decode else 0
    return trunk + attn + head


def request_prefill_flops(cfg: dict, prompt: int) -> float:
    """The prompt's tokens but the last (which the decode step runs):
    token i attends i + 1 positions."""
    n = max(prompt - 1, 0)
    return 2 * active_matmul_params(cfg) * n \
        + cfg["num_hidden_layers"] * (n * (n + 1) // 2) \
        * attention_pair_flops(cfg, absorbed=False)


def mla_decode_cost(cfg: dict, context_tokens: int,
                    itemsize: int = 2) -> tuple:
    """(bytes, flops) of absorbed decode attention in ONE layer over
    `context_tokens` live positions summed over the batch's slots: each
    live latent row read once (576 lanes), the two absorbed products
    against all heads."""
    lanes = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return (context_tokens * lanes * itemsize,
            context_tokens * attention_pair_flops(cfg, absorbed=True))
