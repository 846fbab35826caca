"""A four-layer Jamba-shaped model (two Mamba layers, two attention
layers) for the CPU rehearsal of the serve cell (`run.py --allow-cpu`);
never a benchmark configuration."""

from kubeml_tpu.models.jamba import Jamba, JambaModule


class JambaRehearsal(Jamba):
    name = "jamba-rehearsal"

    def build(self):
        return JambaModule(
            vocab_size=4096, max_len=256, hidden=256, layers=4,
            attn_period=2, attn_offset=1, heads=2, kv_heads=1,
            intermediate_size=512, expand=2, d_state=16, d_conv=4,
            dt_rank=16, rms_eps=1e-6)
