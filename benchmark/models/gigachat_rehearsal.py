"""A five-layer GigaChat3.5-shaped share (GatedDeltaNet layers of 2 key
and 4 value heads of 128 around one MLA layer, 8 of 32 experts held,
top 4) for the CPU rehearsal of the serve cell (`run.py --allow-cpu`);
never a benchmark configuration."""

from kubeml_tpu.models.gigachat import GigaChat, GigaChatModule


class GigaChatRehearsal(GigaChat):
    name = "gigachat-rehearsal"

    def build(self):
        return GigaChatModule(
            vocab_size=4096, max_len=256, hidden=256, layers=5,
            first_dense=1, full_attention_layers=(3,), heads=8,
            q_lora_rank=96, kv_lora_rank=128, qk_nope_head_dim=32,
            qk_rope_head_dim=64, v_head_dim=32, linear_key_heads=2,
            linear_value_heads=4, intermediate_size=512,
            moe_intermediate_size=128, n_routed_experts=32,
            n_held_experts=8, experts_per_tok=4)
