"""Plain references: ``jax.numpy``, float32, 'highest' matmul precision, no
Flax module, no kernel. The comparison that decides ``correct`` reads these
and nothing of the program's own model code."""
