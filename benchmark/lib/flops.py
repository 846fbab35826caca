"""Closed-form operation and byte counts, from a configuration's sizes.

These are the counts the ALGORITHM needs (what a roofline or an MFU
divides), not what a compiler reports: recomputation, padding and
casts do not count. One multiply-accumulate is two FLOPs.
"""


def gpt2_params(cfg: dict) -> int:
    d, layers = cfg["n_embd"], cfg["n_layer"]
    inner = cfg.get("n_inner") or 4 * d
    per_layer = (3 * (d * d + d) + d * d + d       # q, k, v, out
                 + d * inner + inner + inner * d + d  # MLP
                 + 4 * d)                          # two LayerNorms
    return (cfg["vocab_size"] * d + cfg["n_positions"] * d
            + layers * per_layer + 2 * d)


def gpt2_kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    return 2 * cfg["n_layer"] * cfg["n_embd"] * itemsize


def gpt2_token_flops(cfg: dict, context: int, head: bool) -> int:
    """FLOPs one token needs at `context` attended positions: every
    matmul of the trunk, the two attention products over the context,
    and (only where a next token is read) the tied output head."""
    d, layers = cfg["n_embd"], cfg["n_layer"]
    inner = cfg.get("n_inner") or 4 * d
    trunk = 2 * layers * (4 * d * d + 2 * d * inner)
    attn = 2 * layers * 2 * context * d
    return trunk + attn + (2 * d * cfg["vocab_size"] if head else 0)


def gpt2_request_flops(cfg: dict, prompt: int, outputs: int) -> int:
    """All model FLOPs of one request: `prompt` tokens in, `outputs`
    out. Token i (0-based) attends i + 1 positions; the tokens that
    produce an output are the last prompt token and every output but
    the last."""
    n = prompt + max(outputs - 1, 0)
    d, layers = cfg["n_embd"], cfg["n_layer"]
    inner = cfg.get("n_inner") or 4 * d
    trunk = 2 * layers * (4 * d * d + 2 * d * inner) * n
    attn = 2 * layers * 2 * d * (n * (n + 1) // 2)
    return trunk + attn + 2 * d * cfg["vocab_size"] * outputs


def paged_attention_decode_cost(cfg: dict, context_tokens: int,
                                itemsize: int = 2) -> tuple:
    """(bytes, flops) of decode attention in ONE layer over
    `context_tokens` live positions summed over the batch's slots: K and
    V of the live context read once, QK^T and PV."""
    d = cfg["n_embd"]
    return 2 * context_tokens * d * itemsize, 2 * 2 * context_tokens * d


def roofline_seconds(nbytes: float, flops: float, peaks: dict) -> tuple:
    """The least time the chip could take and which bound sets it."""
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    t_flop = flops / peaks["flops_bf16"]
    return (t_mem, "memory") if t_mem >= t_flop else (t_flop, "compute")
