"""Compute hot-spot kernels behind a backend-portable dispatch registry.

``dispatch`` is the registry (selection by platform/dtype/shape, env and
context overrides, selection counts); ``ops`` holds the
jit'd public entry points; ``ref`` the pure-jnp oracles; the remaining
modules register the Pallas-TPU / Pallas-interpret / chunked-XLA
implementations.  Model code calls ``dispatch.call``/``ops.*`` — never a
kernel module directly — so a JAX rename or a new platform is absorbed
inside this package.
"""

from . import dispatch  # noqa: F401  (registry; impls register lazily)
