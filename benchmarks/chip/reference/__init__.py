"""Plain references: float32 jax.numpy, no kernels, nothing of the
program imported."""
