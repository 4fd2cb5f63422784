"""Device-profile driver: measure this host and persist a DeviceProfile.

    PYTHONPATH=src python -m repro.launch.profile --out profile.json

Measures the chip roofline (dense-matmul FLOP/s, HBM stream bandwidth),
every eligible kernel dispatch backend per (op, shape class), and — when
more than one device is visible — the four ring collectives over a
message-size ladder on each mesh axis, fitted to alpha-beta curves.  The
resulting JSON feeds ``--device-profile`` on train / serve / dryrun,
calibrating the plan search's cost model to the measured machine.

On a CPU host, 8 virtual devices for the collective sweep come from::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.profile --smoke --out p.json
"""

from __future__ import annotations

import argparse
import json

import jax

from repro.profiling import build_profile, default_profile_path
from repro.profiling import microbench as mb

KiB = 1024
MiB = 1024 * 1024

#: CI-sized ladders: seconds, not minutes, on a shared runner.
SMOKE = dict(matmul_sizes=(128, 256), stream_sizes=(1 * MiB, 4 * MiB),
             collective_sizes=(64 * KiB, 256 * KiB, 1 * MiB),
             repeats=3, warmup=1)


def parse_axes(spec: str) -> dict[str, int]:
    """``"data=4,model=2"`` -> ``{"data": 4, "model": 2}``."""
    out: dict[str, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, size = part.partition("=")
        if not size:
            raise ValueError(f"bad --axes entry {part!r}; want name=size")
        out[name.strip()] = int(size)
    return out


def default_axes(n_dev: int) -> dict[str, int]:
    """The serve-mesh factoring: (n/2, 2) when n >= 4 and even, else a
    single data axis — the axes plans are actually searched over."""
    if n_dev <= 1:
        return {}
    if n_dev >= 4 and n_dev % 2 == 0:
        return {"data": n_dev // 2, "model": 2}
    return {"data": n_dev}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="measure a DeviceProfile for this host")
    ap.add_argument("--out", default="",
                    help="output JSON path (default: the profile cache, "
                         "keyed by device kind)")
    ap.add_argument("--axes", default="",
                    help="mesh axes to sweep collectives over, e.g. "
                         "data=4,model=2 (default: factor the visible "
                         "devices like the serve mesh)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized ladders (small matmuls, short "
                         "collective ladder, 3 repeats)")
    ap.add_argument("--repeats", type=int, default=0,
                    help="override median-of-k repeats")
    ap.add_argument("--warmup", type=int, default=0,
                    help="override warmup iterations")
    ap.add_argument("--shape-classes", default="small",
                    help="comma-separated kernel shape classes "
                         "(small, base)")
    ap.add_argument("--skip-collectives", action="store_true",
                    help="skip the collective sweep even with >1 device")
    args = ap.parse_args(argv)

    kw = dict(SMOKE) if args.smoke else {}
    if args.repeats > 0:
        kw["repeats"] = args.repeats
    if args.warmup > 0:
        kw["warmup"] = args.warmup
    kw["shape_classes"] = tuple(
        s.strip() for s in args.shape_classes.split(",") if s.strip())

    n_dev = len(jax.devices())
    axes = parse_axes(args.axes) if args.axes else default_axes(n_dev)
    if args.skip_collectives:
        axes = {}
    print(f"profile: {n_dev} device(s) [{mb.device_kind()}], "
          f"collective axes {axes or 'none'}")

    prof = build_profile(axes=axes, **kw)

    out = args.out or str(default_profile_path(prof.device_kind))
    prof.save(out)
    print(f"profile: measured flops {prof.measured_flops:.3e} FLOP/s, "
          f"hbm {prof.measured_hbm_bw:.3e} B/s")
    for axis, curves in sorted(prof.collectives.items()):
        for kind, c in sorted(curves.items()):
            print(f"profile: {axis}/{kind}: alpha {c.alpha * 1e6:.1f} us, "
                  f"bw {c.bw:.3e} B/s")
    factors = prof.kernel_factors()
    for (op, backend), f in sorted(factors.items()):
        print(f"profile: kernel {op}/{backend}: factor {f:.2f}")
    print(f"profile: wrote {out}")
    print(json.dumps({"device_kind": prof.device_kind,
                      "kernel_entries": len(prof.kernel_times),
                      "collective_axes": sorted(prof.collectives)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
