"""Model families, a file each: the seeded weights' spec, the plain reference and the cost
counts (``train.py`` and ``lowprec.py`` are what they share). Plain references: float32
``jax.numpy`` at matmul precision ``highest``, no kernels, no cache, written from the
published descriptions. Nothing here imports the program."""
