"""Plain references: straightforward jax.numpy in float32 at `highest`
matmul precision, no kernels, cache or batching. They import nothing of
kubeml_tpu and take nothing it has made."""
