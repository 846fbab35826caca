"""GPT-2 large as a user function: `kubeml fn create gpt2-large -f this`.

Sizes as published (huggingface.co/openai-community/gpt2-large,
config.json): 36 layers, 1280 hidden, 20 heads of 64, 1024 positions,
vocabulary 50257, feed-forward 5120. The trunk is the repo's own
GPTModule, which takes every size as a field; bfloat16 compute.
"""

from kubeml_tpu.models.gpt import GPTMini, GPTModule


class GPT2Large(GPTMini):
    name = "gpt2-large"

    def build(self):
        return GPTModule(vocab_size=50257, max_len=1024, hidden=1280,
                         layers=36, heads=20, ffn=5120)
