"""Measured device profiles: the profiling subsystem that calibrates the
cost model from real hardware.

The paper's execution simulator runs on *measured* per-layer times and
per-connection bandwidths (Section 4); this package is that measurement
layer for our stack.  :mod:`~repro.profiling.microbench` times real jitted
executions (chip roofline, kernel backends through the dispatcher,
collectives over the device mesh); :mod:`~repro.profiling.profile`
persists them as a versioned :class:`DeviceProfile` JSON artifact (the
on-disk artifact next to ParallelPlan JSON);
:meth:`repro.core.cost_model.CostModel.from_profile` consumes one, field
by field, falling back to the analytic constants for anything the profile
lacks.  :mod:`~repro.profiling.calibration` closes the loop with a
predicted-vs-measured per-layer report (``cost_model_rel_error``).
"""

from .calibration import format_layer_report, layer_report
from .microbench import build_profile, measure_collectives, measure_kernels
from .profile import (CollectiveCurve, DeviceProfile, ProfileError,
                      ProfileFormatError, default_profile_path,
                      fit_alpha_beta, load_profile, profile_dir)

__all__ = [
    "CollectiveCurve",
    "DeviceProfile",
    "ProfileError",
    "ProfileFormatError",
    "build_profile",
    "default_profile_path",
    "fit_alpha_beta",
    "format_layer_report",
    "layer_report",
    "load_profile",
    "measure_collectives",
    "measure_kernels",
    "profile_dir",
]
