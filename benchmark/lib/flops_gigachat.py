"""Closed-form operation and byte counts of the GigaChat3.5 share, from a
configuration's sizes (benchmark/configs/gigachat3.5-ep16-serve.json
keys), beside lib/flops.py and under its rules: what the ALGORITHM
needs, not what a compiler reports; one multiply-accumulate is two
FLOPs. An MLA layer is DeepSeek-V2's block (lib/flops_deepseek_v2.py)
with an output gate.
"""

from benchmark.lib.flops_deepseek_v2 import (_mla_params,
                                             attention_pair_flops)

STATE_ITEMSIZE = 4      # the GatedDeltaNet state is float32
GDN_ITEMSIZE = 4        # and so are the kernels' q, k, v, g, beta and o
BLOCK = 64              # tokens of one block of the chunked form


def layer_counts(cfg: dict) -> tuple:
    """(GatedDeltaNet layers, MLA layers)."""
    attn = sum(i in cfg["full_attention_layers"]
               for i in range(cfg["num_hidden_layers"]))
    return cfg["num_hidden_layers"] - attn, attn


def _gdn_dims(cfg: dict) -> tuple:
    """(value heads, dk, dv, convolution channels)."""
    hv = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return hv, dk, dv, 2 * cfg["linear_num_key_heads"] * dk + hv * dv


def _gated_params(cfg: dict, width: int) -> int:
    return 3 * cfg["hidden_size"] * width


def gdn_params(cfg: dict) -> int:
    """Matmul parameters of one GatedDeltaNet block: W_qkvz, W_ba,
    W_out."""
    d = cfg["hidden_size"]
    hv, _dk, dv, cd = _gdn_dims(cfg)
    return d * (cd + hv * dv) + d * 2 * hv + hv * dv * d


def mla_params(cfg: dict) -> int:
    """Matmul parameters of one MLA block and its output gate."""
    gate = cfg["hidden_size"] * cfg["num_attention_heads"] \
        * cfg["v_head_dim"] if cfg["gated_attention"] else 0
    return _mla_params(cfg) + gate


def local_experts_per_token(cfg: dict) -> float:
    """A token's expected choices of an expert held HERE: top-k times
    held / router width (8 x 16/256 = 0.5)."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["ep"]["router_outputs"]


def _ffn(cfg: dict, i: int, experts: float) -> float:
    if i < cfg["first_k_dense_replace"]:
        return _gated_params(cfg, cfg["intermediate_size"])
    return (_gated_params(cfg, cfg["moe_intermediate_size"]
                          * cfg["n_shared_experts"])
            + cfg["hidden_size"] * cfg["ep"]["router_outputs"]
            + experts * _gated_params(cfg, cfg["moe_intermediate_size"]))


def _layers(cfg: dict, experts) -> float:
    total = 0.0
    for i in range(cfg["num_hidden_layers"]):
        mixer = mla_params(cfg) if i in cfg["full_attention_layers"] \
            else gdn_params(cfg)
        total += mixer + _ffn(cfg, i, experts)
    return total


def held_params(cfg: dict) -> int:
    """Matmul parameters and the embedding this share holds (norm
    scales, the convolution, A_log and dt_bias left out)."""
    return int(_layers(cfg, cfg["n_routed_experts"])
               + 2 * cfg["vocab_size"] * cfg["hidden_size"])


def active_matmul_params(cfg: dict) -> float:
    """Held matmul parameters one token activates in the trunk: every
    mixer, the dense FFN, the router, the shared expert and the token's
    expected share of experts that live here. The head is counted apart,
    only where a next token is read."""
    return _layers(cfg, local_experts_per_token(cfg))


def state_bytes_per_slot(cfg: dict, conv_itemsize: int = 2) -> int:
    """What one slot keeps beside its pages, all GatedDeltaNet layers:
    the float32 [Hv, dk, dv] state and the convolution's last conv - 1
    inputs."""
    n, _ = layer_counts(cfg)
    hv, dk, dv, cd = _gdn_dims(cfg)
    return n * (hv * dk * dv * STATE_ITEMSIZE
                + (cfg["linear_conv_kernel_dim"] - 1) * cd * conv_itemsize)


def gdn_step_flops(cfg: dict) -> int:
    """The recurrence's FLOPs for one token in ONE layer: per value head
    the decay of the state, S^T k, the rank-1 update (a multiply and an
    add an element) and S^T q, seven an element of [dk, dv]."""
    hv, dk, dv, _cd = _gdn_dims(cfg)
    return 7 * hv * dk * dv


def gdn_chunk_flops(cfg: dict, tokens: int) -> float:
    """The chunked form's FLOPs for `tokens` tokens in ONE layer, per
    value head and block of BLOCK: K K^T, T (K diag), T V, Q K^T and the
    masked product with U (each 2 bt^2 wide), W S0, Q S0 and K^T U (each
    2 bt dk dv), and the triangular inverse (bt^3 / 3)."""
    hv, dk, dv, _cd = _gdn_dims(cfg)
    bt = BLOCK
    block = 2 * bt * bt * (3 * dk + 2 * dv) + 3 * 2 * bt * dk * dv \
        + bt ** 3 / 3
    return hv * block * tokens / bt


def _gdn_token_bytes(cfg: dict) -> int:
    """One token's operands of the recurrence and its output: q, k, v,
    o [Hv, 128] and g, beta [Hv], float32."""
    hv, dk, dv, _cd = _gdn_dims(cfg)
    return hv * (2 * dk + 2 * dv + 2) * GDN_ITEMSIZE


def gdn_decode_cost(cfg: dict, lanes: int) -> tuple:
    """(bytes, flops) of the decode kernel in ONE layer for `lanes`
    lanes advanced one token each: every lane's [Hv, dk, dv] state read
    once and written once, and its operands."""
    hv, dk, dv, _cd = _gdn_dims(cfg)
    nbytes = lanes * (2 * hv * dk * dv * STATE_ITEMSIZE
                      + _gdn_token_bytes(cfg))
    return nbytes, lanes * gdn_step_flops(cfg)


def gdn_prefill_cost(cfg: dict, chunks: int, tokens: int) -> tuple:
    """(bytes, flops) of the chunked kernel in ONE layer for `chunks`
    dispatches (one slot's state read and written each) over `tokens`
    tokens in all."""
    hv, dk, dv, _cd = _gdn_dims(cfg)
    nbytes = chunks * 2 * hv * dk * dv * STATE_ITEMSIZE \
        + tokens * _gdn_token_bytes(cfg)
    return nbytes, gdn_chunk_flops(cfg, tokens)


def token_flops(cfg: dict, context: int, decode: bool) -> float:
    """FLOPs one token needs at `context` attended positions: the held
    matmuls it activates, the convolution and the recurrence in every
    GatedDeltaNet layer (a step in decode, its share of the chunked
    form in prefill), the attention products over the context in every
    MLA layer in the form its path uses (absorbed in decode,
    up-projected in prefill), and, in decode, the head over the held
    vocabulary."""
    n_gdn, n_mla = layer_counts(cfg)
    _hv, _dk, _dv, cd = _gdn_dims(cfg)
    rule = gdn_step_flops(cfg) if decode else gdn_chunk_flops(cfg, 1)
    return (2 * active_matmul_params(cfg)
            + n_gdn * (rule + 2 * cfg["linear_conv_kernel_dim"] * cd)
            + n_mla * context * attention_pair_flops(cfg, absorbed=decode)
            + (2 * cfg["hidden_size"] * cfg["vocab_size"] if decode else 0))


def request_prefill_flops(cfg: dict, prompt: int) -> float:
    """The prompt's tokens but the last (which the decode step runs):
    token i attends i + 1 positions."""
    n = max(prompt - 1, 0)
    _n_gdn, n_mla = layer_counts(cfg)
    return n * token_flops(cfg, 0, decode=False) \
        + n_mla * (n * (n + 1) // 2) * attention_pair_flops(cfg,
                                                             absorbed=False)
