"""A five-layer EXAONE-MoE-shaped share (L L L G L, window 8, 4 of 16
experts held) for the CPU rehearsal of the serve cell (`run.py
--allow-cpu`); never a benchmark configuration."""

from kubeml_tpu.models.exaone_moe import ExaoneMoE, ExaoneMoEModule


class ExaoneMoERehearsal(ExaoneMoE):
    name = "exaone-moe-rehearsal"

    def build(self):
        return ExaoneMoEModule(
            vocab_size=4096, max_len=256, hidden=256, layers=5,
            sliding_windows=(8, 8, 8, 0, 8), first_dense=1, heads=4,
            kv_heads=2, head_dim=64, intermediate_size=512,
            moe_intermediate_size=128, n_shared_experts=1, n_experts=16,
            n_held_experts=4, ep_rank=0, experts_per_tok=4,
            routed_scaling_factor=2.5, rope_theta=1e6, rms_eps=1e-5)
