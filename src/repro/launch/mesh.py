"""Device meshes: the production pod meshes the dry-run compiles for,
and the one-host mesh both drivers execute on.

Every mesh here is built by a FUNCTION (not a module-level constant) so
that importing this module never touches jax device state; callers
(dryrun) are responsible for setting
``--xla_force_host_platform_device_count`` before jax initializes.
"""

from __future__ import annotations

import jax

from repro import compat
from repro.core.device import (AxisSpec, ICI_BW, MeshSpec, chip_for,
                               multi_pod_mesh_spec, single_pod_mesh_spec)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def production_mesh_spec(*, multi_pod: bool = False) -> MeshSpec:
    """The cost model's view of the same mesh."""
    return multi_pod_mesh_spec() if multi_pod else single_pod_mesh_spec()


def host_mesh(n_dev: int):
    """The ``(data, model)`` device mesh of this host and the cost
    model's spec of it, priced for this host's chip.

    A host with four or more chips (and an even count) gets a model axis
    of 2, so the layer-wise search has tensor parallelism to choose from;
    smaller hosts put every chip on ``data``."""
    dims = (n_dev // 2, 2) if (n_dev >= 4 and n_dev % 2 == 0) else (n_dev, 1)
    mesh = compat.make_mesh(dims, ("data", "model"))
    dev = jax.devices()[0]
    spec = MeshSpec(axes=(AxisSpec("data", dims[0], ICI_BW),
                          AxisSpec("model", dims[1], ICI_BW)),
                    chip=chip_for(dev.platform, dev.device_kind))
    return mesh, spec
