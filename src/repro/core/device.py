"""Device & mesh hardware model.

The paper models hardware as a *device graph* with per-connection bandwidth
(Section 4).  A TPU pod slice is homogeneous with named-axis topology, so the
device graph collapses to: a chip spec (peak FLOP/s, HBM bandwidth/capacity)
plus a per-mesh-axis link bandwidth.  The ``pod`` axis crosses the slower
inter-pod fabric and carries a discounted bandwidth; the search therefore
learns to keep all-to-all-heavy dimensions off that axis — the TPU-native
analogue of the paper's intra-node NVLink vs inter-node Infiniband split.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass

GiB = 1024**3

# ---- analytic hardware constants (one seam per number) -------------------- #
# Every hand-written roofline/topology constant the cost model falls back to
# when no measured :class:`~repro.profiling.DeviceProfile` overrides it lives
# here under a name.  ``launch.profile`` measures the machine-specific
# replacements; nothing outside this module should restate these literals.

#: Fraction of peak FLOP/s realistically achievable on dense matmuls;
#: replaced by ``measured_flops / peak_flops`` under a device profile.
DEFAULT_MXU_EFFICIENCY = 0.55
#: Fraction of peak HBM bandwidth realistically achievable on streaming
#: reads; replaced by ``measured_hbm_bw / hbm_bw`` under a device profile.
DEFAULT_HBM_EFFICIENCY = 0.8

#: TPU v5e roofline constants (the grading target): 197 TFLOP/s bf16,
#: 819 GB/s HBM, 16 GiB capacity, 128 MiB VMEM.
TPU_V5E_PEAK_FLOPS = 197e12
TPU_V5E_HBM_BW = 819e9
TPU_V5E_HBM_BYTES = 16 * GiB
TPU_V5E_VMEM_BYTES = 128 * 1024**2

ICI_BW = 50e9        # intra-pod ICI, per link
POD_BW = 12.5e9      # inter-pod (DCN/optical) — heavily discounted

#: Collective kinds an axis can carry a measured alpha-beta curve for.
COLLECTIVE_KINDS = ("all_reduce", "reduce_scatter", "all_gather",
                    "all_to_all")


@dataclass(frozen=True)
class ChipSpec:
    """A single accelerator chip (roofline constants)."""

    name: str
    peak_flops: float        # bf16 FLOP/s
    hbm_bw: float            # bytes/s
    hbm_bytes: float         # capacity, bytes
    vmem_bytes: float        # on-chip vector memory, bytes
    # Fraction of peak realistically achievable; calibrated from a measured
    # DeviceProfile via ChipSpec.calibrated(), analytic defaults otherwise.
    mxu_efficiency: float = DEFAULT_MXU_EFFICIENCY
    hbm_efficiency: float = DEFAULT_HBM_EFFICIENCY

    @property
    def eff_flops(self) -> float:
        return self.peak_flops * self.mxu_efficiency

    @property
    def eff_hbm_bw(self) -> float:
        return self.hbm_bw * self.hbm_efficiency

    def calibrated(self, measured_flops: float | None = None,
                   measured_hbm_bw: float | None = None) -> "ChipSpec":
        """A copy whose efficiencies make ``eff_flops`` / ``eff_hbm_bw``
        equal the measured rates; ``None`` keeps the analytic default
        (field-by-field fallback)."""
        kw = {}
        if measured_flops is not None and measured_flops > 0:
            kw["mxu_efficiency"] = float(measured_flops) / self.peak_flops
        if measured_hbm_bw is not None and measured_hbm_bw > 0:
            kw["hbm_efficiency"] = float(measured_hbm_bw) / self.hbm_bw
        return dataclasses.replace(self, **kw) if kw else self


# TPU v5e (the grading target).
TPU_V5E = ChipSpec(
    name="tpu_v5e",
    peak_flops=TPU_V5E_PEAK_FLOPS,
    hbm_bw=TPU_V5E_HBM_BW,
    hbm_bytes=TPU_V5E_HBM_BYTES,
    vmem_bytes=TPU_V5E_VMEM_BYTES,
)


#: Chips the cost model can price, keyed by ``jax.Device.device_kind``.
#: Peaks are the published ones (Google Cloud documentation, "TPU v5e").
CHIPS_BY_KIND: dict[str, ChipSpec] = {
    "TPU v5 lite": TPU_V5E,
}


def chip_for(platform: str, device_kind: str, log=print) -> ChipSpec:
    """The :class:`ChipSpec` the plan search prices for this device.

    A TPU whose kind is not in :data:`CHIPS_BY_KIND` raises: pricing it
    as some other chip would search plans for the wrong hardware.  A
    host without a TPU (CPU tests, rehearsals) prices for a v5e and says
    so through ``log``."""
    if platform != "tpu":
        log(f"cost model: no TPU here ({platform}); plans are priced "
            f"for a {TPU_V5E.name}")
        return TPU_V5E
    try:
        return CHIPS_BY_KIND[device_kind]
    except KeyError:
        raise ValueError(
            f"unknown TPU device_kind {device_kind!r}: the cost model has "
            f"peaks for {sorted(CHIPS_BY_KIND)} only; add the chip's "
            f"published peaks to CHIPS_BY_KIND") from None


@dataclass(frozen=True)
class CollectiveCost:
    """Cost of one collective: wall seconds and per-chip bytes sent."""

    time: float
    bytes: float

    def __add__(self, other: "CollectiveCost") -> "CollectiveCost":
        return CollectiveCost(self.time + other.time, self.bytes + other.bytes)

    def __mul__(self, k: float) -> "CollectiveCost":
        return CollectiveCost(self.time * k, self.bytes * k)

    __rmul__ = __mul__


ZERO_COST = CollectiveCost(0.0, 0.0)


@dataclass(frozen=True)
class AxisSpec:
    """One named mesh axis: its size and the link bandwidth collectives over
    it see (bytes/s per chip).

    ``curves`` optionally carries measured alpha-beta collective curves as
    ``(kind, alpha_seconds, bw_bytes_per_s)`` triples — one per collective
    kind in :data:`COLLECTIVE_KINDS` — fitted by the profiling microbench
    (``t = alpha + wire_bytes / bw``).  An axis without a curve for a kind
    prices it from the analytic ``bw`` with zero latency, so the default
    (empty) tuple is bit-identical to the uncalibrated model.
    """

    name: str
    size: int
    bw: float  # bytes/s per chip for ring collectives along this axis
    curves: tuple[tuple[str, float, float], ...] = ()

    def curve(self, kind: str) -> tuple[float, float]:
        """``(alpha_seconds, bw_bytes_per_s)`` for one collective kind."""
        for k, alpha, bw in self.curves:
            if k == kind:
                return alpha, bw
        return 0.0, self.bw


@dataclass(frozen=True)
class MeshSpec:
    """Named-axis device mesh + chip roofline constants.

    This is the cost model's entire view of hardware (paper's device graph).
    """

    axes: tuple[AxisSpec, ...]
    chip: ChipSpec = TPU_V5E

    # ------------------------------------------------------------------ #
    def __post_init__(self):
        names = [a.name for a in self.axes]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate mesh axis names: {names}")

    @property
    def axis_names(self) -> tuple[str, ...]:
        return tuple(a.name for a in self.axes)

    @property
    def num_devices(self) -> int:
        return math.prod(a.size for a in self.axes)

    def axis(self, name: str) -> AxisSpec:
        for a in self.axes:
            if a.name == name:
                return a
        raise KeyError(f"no mesh axis {name!r} in {self.axis_names}")

    def axis_size(self, name: str) -> int:
        return self.axis(name).size

    def degree(self, axes: tuple[str, ...]) -> int:
        return math.prod(self.axis_size(a) for a in axes)

    # ---- collective primitives (ring algorithms) ---------------------- #
    # Each returns ``CollectiveCost(time, bytes)``: seconds on the slowest
    # participating chip, and per-chip bytes sent over the wire.  Per-axis
    # stages price as ``alpha + wire_bytes / bw`` from the axis's measured
    # curve for that collective kind; the analytic fallback is alpha=0 at
    # the axis's nominal ``bw`` (see AxisSpec.curve).

    def all_reduce(self, bytes_full: float, axes: tuple[str, ...]) -> "CollectiveCost":
        """Ring all-reduce of a ``bytes_full`` buffer over ``axes``.

        Hierarchical: reduce-scatter+all-gather along each axis in turn
        (2*(s-1)/s per stage); after each reduce-scatter stage the live shard
        shrinks by the axis size, matching XLA's hierarchical lowering.
        """
        t = b = 0.0
        live = bytes_full
        for name in axes:
            a = self.axis(name)
            if a.size == 1:
                continue
            stage = 2.0 * (a.size - 1) / a.size * live
            alpha, bw = a.curve("all_reduce")
            t += alpha + stage / bw
            b += stage
            live /= a.size
        return CollectiveCost(t, b)

    def reduce_scatter(self, bytes_full: float, axes: tuple[str, ...]) -> "CollectiveCost":
        t = b = 0.0
        live = bytes_full
        for name in axes:
            a = self.axis(name)
            if a.size == 1:
                continue
            stage = (a.size - 1) / a.size * live
            alpha, bw = a.curve("reduce_scatter")
            t += alpha + stage / bw
            b += stage
            live /= a.size
        return CollectiveCost(t, b)

    def all_gather(self, bytes_shard: float, axes: tuple[str, ...]) -> "CollectiveCost":
        """Gather a per-chip ``bytes_shard`` over ``axes`` (result grows)."""
        t = b = 0.0
        live = bytes_shard
        for name in axes:
            a = self.axis(name)
            if a.size == 1:
                continue
            stage = (a.size - 1) * live
            alpha, bw = a.curve("all_gather")
            t += alpha + stage / bw
            b += stage
            live *= a.size
        return CollectiveCost(t, b)

    def all_to_all(self, bytes_local: float, axes: tuple[str, ...]) -> "CollectiveCost":
        """All-to-all of the per-chip ``bytes_local`` buffer over ``axes``."""
        t = b = 0.0
        for name in axes:
            a = self.axis(name)
            if a.size == 1:
                continue
            stage = (a.size - 1) / a.size * bytes_local
            alpha, bw = a.curve("all_to_all")
            t += alpha + stage / bw
            b += stage
        return CollectiveCost(t, b)

    def min_bw(self, axes: tuple[str, ...]) -> float:
        if not axes:
            return ICI_BW
        return min(self.axis(a).bw for a in axes)

    # ------------------------------------------------------------------ #
    def subspec(self, **sizes: int) -> "MeshSpec":
        """A copy with some axis sizes overridden (for what-if analysis)."""
        new = tuple(
            dataclasses.replace(a, size=sizes.get(a.name, a.size)) for a in self.axes
        )
        return MeshSpec(axes=new, chip=self.chip)


def single_pod_mesh_spec(data: int = 16, model: int = 16,
                         chip: ChipSpec = TPU_V5E) -> MeshSpec:
    """The production single-pod mesh: 16x16 = 256 chips."""
    return MeshSpec(
        axes=(AxisSpec("data", data, ICI_BW), AxisSpec("model", model, ICI_BW)),
        chip=chip,
    )


def multi_pod_mesh_spec(pods: int = 2, data: int = 16, model: int = 16,
                        chip: ChipSpec = TPU_V5E) -> MeshSpec:
    """The production multi-pod mesh: 2 x 16 x 16 = 512 chips."""
    return MeshSpec(
        axes=(
            AxisSpec("pod", pods, POD_BW),
            AxisSpec("data", data, ICI_BW),
            AxisSpec("model", model, ICI_BW),
        ),
        chip=chip,
    )
