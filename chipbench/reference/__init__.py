"""Plain references: float32 ``jax.numpy`` at matmul precision ``highest``, no kernels,
no cache, written from the published descriptions. Nothing here imports the program."""
