"""DeepSeek-V2 as one chip's share of a four-chip expert-parallel
deployment, as a user function: `kubeml fn create deepseek-v2-ep4 -f this`.

Widths as published (huggingface.co/deepseek-ai/DeepSeek-V2,
config.json): hidden 5120, 128 heads, q_lora_rank 1536, kv_lora_rank
512, qk_nope_head_dim 128, qk_rope_head_dim 64, v_head_dim 128, dense
width 12288, expert width 1536, 2 shared experts, a router over 160
experts in 8 groups (top 3 groups, top 6, scaling factor 16), YaRN
factor 40 over 4096. Cut to one chip (benchmark/configs/
deepseek-v2-ep4-serve.json `reduced`): the leading dense layer and 4
expert layers, the 40 experts of routing groups 0 and 1, a quarter of
the vocabulary, 4096 positions a slot. bfloat16 parameters.
"""

from kubeml_tpu.models.deepseek_v2 import DeepSeekV2, DeepSeekV2Module


class DeepSeekV2EP4(DeepSeekV2):
    name = "deepseek-v2-ep4"

    def build(self):
        return DeepSeekV2Module(
            vocab_size=25600, max_len=4096, hidden=5120, layers=5,
            first_dense=1, heads=128, q_lora_rank=1536, kv_lora_rank=512,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            intermediate_size=12288, moe_intermediate_size=1536,
            n_shared_experts=2, n_routed_experts=160, n_held_experts=40,
            ep_rank=0, n_group=8, topk_group=3, experts_per_tok=6,
            routed_scaling_factor=16.0)
