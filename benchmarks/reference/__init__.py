"""Plain references: float32 ``jax.numpy``, no cache, no kernels.

Nothing here imports the program (``fei_tpu``), and nothing here takes a
value the program made: weights are recomputed from the seed.
"""
