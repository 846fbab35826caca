"""AI21-Jamba2-3B whole, as a user function: `kubeml fn create
jamba2-3b -f this`.

Widths as published (huggingface.co/ai21labs/AI21-Jamba2-3B,
config.json): hidden 2560, 28 layers of which layers 7 and 21 (period
14, offset 7) are attention layers of 20 query heads over one key-value
head of 128 and the other 26 Mamba layers (expand 2: d_inner 5120,
d_state 16, d_conv 4, dt_rank 160), a dense gated feed-forward of 8192
in every layer, vocabulary 65536 with a tied head. Cut to one chip
(benchmark/configs/jamba2-3b-serve.json `reduced`): 6144 positions a
slot of the published 262144, and nothing else. bfloat16 parameters.
"""

from kubeml_tpu.models.jamba import Jamba, JambaModule


class Jamba2_3B(Jamba):
    name = "jamba2-3b"

    def build(self):
        return JambaModule(
            vocab_size=65536, max_len=6144, hidden=2560, layers=28,
            attn_period=14, attn_offset=7, heads=20, kv_heads=1,
            intermediate_size=8192, expand=2, d_state=16, d_conv=4,
            dt_rank=160, rms_eps=1e-6)
