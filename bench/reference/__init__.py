"""Plain ``jax.numpy`` references that decide whether a run is correct.

They import nothing of the program and take nothing it made: weights
come from the benchmark's own generator (``bench.core.weights``), the
layout is the one the configuration describes.
"""
