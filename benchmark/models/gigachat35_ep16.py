"""GigaChat3.5-432B-A28B as one chip's share of a 16-chip expert-parallel
deployment, as a user function: `kubeml fn create gigachat3.5-ep16 -f
this`.

Widths as published (huggingface.co/ai-sage/GigaChat3.5-432B-A28B,
config.json): hidden 7168; GatedDeltaNet layers of 32 key and 64 value
heads of 128 (a 4-tap convolution, the 2 x sigmoid output gate); MLA at
64 heads (q_lora_rank 1536, kv_lora_rank 512, head dims 128 + 64 for
queries and keys and 128 for values, YaRN factor 8 over 32768 positions,
an output gate) in every fourth layer; a dense SwiGLU of 18432 in the
first layers and then 256 experts of 2048, top 8, sigmoid scores
re-normalised, factor 2.5, one shared expert; SwiGLU clamp 10,
zero-centred sandwich norms, rms_norm_eps 1e-6, an untied head. Cut to
one chip (benchmark/configs/gigachat3.5-ep16-serve.json `reduced`): the
dense layer 0 and one period, layers 1-4 with MLA at 3, experts 0-15 of
256 (the router stays 256 wide), an eighth of the vocabulary, 4096
positions a slot. bfloat16 parameters.
"""

from kubeml_tpu.models.gigachat import GigaChat, GigaChatModule


class GigaChat35EP16(GigaChat):
    name = "gigachat3.5-ep16"

    def build(self):
        return GigaChatModule(
            vocab_size=16032, max_len=4096, hidden=7168, layers=5,
            first_dense=1, full_attention_layers=(3,), heads=64,
            q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, gated_attention=True,
            linear_key_heads=32, linear_value_heads=64,
            linear_key_head_dim=128, linear_value_head_dim=128,
            linear_conv=4, linear_gate_scale=2.0, linear_norm_eps=1e-6,
            intermediate_size=18432, moe_intermediate_size=2048,
            n_shared_experts=1, n_routed_experts=256, n_held_experts=16,
            ep_rank=0, experts_per_tok=8, routed_scaling_factor=2.5,
            swiglu_limit=10.0, rope_theta=1e5, rope_factor=8.0,
            rope_original_max=32768, rope_beta_fast=32.0,
            rope_beta_slow=1.0, rope_mscale=1.0, rope_mscale_all_dim=1.0,
            rms_eps=1e-6)
