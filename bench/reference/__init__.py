"""Plain float32 references of the benchmark's models (``jax.numpy`` only,
independent of the program): forward, loss and gradients, blocked by
relation so that the full graphs fit."""
