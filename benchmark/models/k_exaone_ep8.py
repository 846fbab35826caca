"""K-EXAONE-236B-A23B as one chip's share of an eight-chip
expert-parallel deployment, as a user function: `kubeml fn create
k-exaone-ep8 -f this`.

Widths as published (huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B,
config.json): hidden 6144, 64 query heads over 8 key-value heads of
128, window layers of 128 positions with rotary positions (theta 1e6)
and global layers without positions in the pattern L L L G, dense width
18432 in the leading layer, expert width 2048, one shared expert, a
sigmoid router over 128 experts (top 8, re-normalised, scaling factor
2.5), an untied head. Cut to one chip (benchmark/configs/
k-exaone-ep8-serve.json `reduced`): the leading dense layer and the four
layers that follow (L L L G L), experts 0-15 of 128, an eighth of the
vocabulary, 18432 positions a slot; the multi-token-prediction layer is
not held. bfloat16 parameters.
"""

from kubeml_tpu.models.exaone_moe import ExaoneMoE, ExaoneMoEModule


class KExaoneEP8(ExaoneMoE):
    name = "k-exaone-ep8"

    def build(self):
        return ExaoneMoEModule(
            vocab_size=19200, max_len=18432, hidden=6144, layers=5,
            sliding_windows=(128, 128, 128, 0, 128), first_dense=1,
            heads=64, kv_heads=8, head_dim=128, intermediate_size=18432,
            moe_intermediate_size=2048, n_shared_experts=1, n_experts=128,
            n_held_experts=16, ep_rank=0, experts_per_tok=8,
            routed_scaling_factor=2.5, rope_theta=1e6, rms_eps=1e-5)
