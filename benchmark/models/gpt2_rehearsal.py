"""An eight-layer GPT-2-shaped model for the CPU rehearsal of the serve
cells (`run.py --allow-cpu`); never a benchmark configuration."""

from kubeml_tpu.models.gpt import GPTMini, GPTModule


class GPT2Rehearsal(GPTMini):
    name = "gpt2-rehearsal"

    def build(self):
        return GPTModule(vocab_size=16384, max_len=128, hidden=256, layers=8,
                         heads=4, ffn=1024, dropout=0.0)
