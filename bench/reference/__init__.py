"""One module per block the benchmark knows, its family: the leaf layout,
the check of the program's arch, the FLOP count and the plain
``jax.numpy`` reference that decides whether a run is correct.

A configuration names its family with a ``"family"`` key, and one without
it is ``dense`` (``dense.py`` lists what a family holds).  A family
imports nothing of the program and takes nothing it made: weights come
from the benchmark's own generator (``bench.core.weights``), the layout is
the one the configuration describes.
"""

import importlib


def family(config: dict):
    """The family module of ``config``: ``bench/reference/<family>.py``."""
    return importlib.import_module(f"{__name__}.{config.get('family', 'dense')}")
