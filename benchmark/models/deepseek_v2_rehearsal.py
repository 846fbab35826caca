"""A three-layer DeepSeek-V2-shaped share for the CPU rehearsal of the
serve cell (`run.py --allow-cpu`); never a benchmark configuration."""

from kubeml_tpu.models.deepseek_v2 import DeepSeekV2, DeepSeekV2Module


class DeepSeekV2Rehearsal(DeepSeekV2):
    name = "deepseek-v2-rehearsal"

    def build(self):
        return DeepSeekV2Module(
            vocab_size=4096, max_len=256, hidden=256, layers=3,
            first_dense=1, heads=8, q_lora_rank=96, kv_lora_rank=128,
            qk_nope_head_dim=32, qk_rope_head_dim=64, v_head_dim=32,
            intermediate_size=512, moe_intermediate_size=128,
            n_shared_experts=2, n_routed_experts=32, n_held_experts=8,
            ep_rank=0, n_group=8, topk_group=3, experts_per_tok=6,
            routed_scaling_factor=16.0)
