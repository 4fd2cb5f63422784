"""Measured device profiles: persistence hygiene (round-trip, corrupt
and version refusal — same contract as the ParallelPlan JSON), the
alpha-beta fit, field-by-field analytic fallback, the no-profile
bit-identity guarantee over every arch, and (slow) the end-to-end
demonstration that a measured profile can move the searched plan."""

import json
import subprocess
import sys
import textwrap

import pytest

from repro import configs as C
from repro.core import AxisSpec, CostModel, ICI_BW, MeshSpec, find_strategy
from repro.core.device import COLLECTIVE_KINDS
from repro.models.arch import SHAPES
from repro.models.graph_export import export_graph
from repro.profiling import (CollectiveCurve, DeviceProfile,
                             ProfileFormatError, fit_alpha_beta)

MESH = MeshSpec(axes=(AxisSpec("data", 4, ICI_BW),
                      AxisSpec("model", 2, ICI_BW)))


def _profile(**kw):
    """A synthetic measured profile: slow chip, latency-heavy links."""
    base = dict(
        device_kind="TestChip v0",
        measured_flops=1e12,
        measured_hbm_bw=1e11,
        collectives={
            "data": {k: CollectiveCurve(k, alpha=25e-6, bw=2e10,
                                        sizes=(1024.0, 4096.0),
                                        times=(3e-5, 5e-5))
                     for k in COLLECTIVE_KINDS},
            "model": {k: CollectiveCurve(k, alpha=5e-6, bw=4e10)
                      for k in COLLECTIVE_KINDS},
        },
        kernel_times={("flash_attention", "xla", "small"): 1e-3,
                      ("flash_attention", "ref", "small"): 2.5e-3,
                      ("mamba_scan", "xla", "small"): 4e-4},
        meta={"jax": "test", "platform": "cpu"},
    )
    base.update(kw)
    return DeviceProfile(**base)


# ---------------------------------------------------------------- fit


def test_fit_alpha_beta_recovers_known_curve():
    alpha, bw = 12e-6, 3.5e10
    sizes = [2.0**k for k in range(14, 23)]
    times = [alpha + s / bw for s in sizes]
    a, b = fit_alpha_beta(sizes, times)
    assert a == pytest.approx(alpha, rel=1e-6)
    assert b == pytest.approx(bw, rel=1e-6)


def test_fit_alpha_beta_degrades_gracefully():
    # constant times (pure latency): no negative bandwidth out of the fit
    sizes = [1e3, 1e4, 1e5]
    a, b = fit_alpha_beta(sizes, [1e-4, 1e-4, 1e-4])
    assert a >= 0.0 and b > 0.0
    # through-origin data must not fit a negative alpha
    a, b = fit_alpha_beta(sizes, [s / 1e9 for s in sizes])
    assert a >= 0.0 and b == pytest.approx(1e9, rel=1e-6)
    # a single rung (or none) cannot be fit — refuse, don't guess
    with pytest.raises(ValueError):
        fit_alpha_beta([4096.0], [1e-5])
    with pytest.raises(ValueError):
        fit_alpha_beta([4096.0, 4096.0], [1e-5, 2e-5])


def test_curve_predict_matches_model():
    c = CollectiveCurve("all_reduce", alpha=1e-5, bw=1e9)
    assert c.predict(1e6) == pytest.approx(1e-5 + 1e6 / 1e9)
    with pytest.raises(ValueError):
        CollectiveCurve("not_a_collective", alpha=0.0, bw=1e9)
    with pytest.raises(ValueError):
        CollectiveCurve("all_reduce", alpha=0.0, bw=0.0)


# -------------------------------------------------------- persistence


def test_profile_json_round_trip(tmp_path):
    prof = _profile()
    again = DeviceProfile.from_json(prof.to_json())
    assert again == prof
    # and through the file system, atomically
    path = prof.save(tmp_path / "p.json")
    loaded = DeviceProfile.load(path)
    assert loaded == prof
    assert loaded.fingerprint() == prof.fingerprint()


def test_corrupt_profiles_rejected(tmp_path):
    garbage = tmp_path / "garbage.json"
    garbage.write_text("{not json at all")
    with pytest.raises(ProfileFormatError):
        DeviceProfile.load(garbage)

    with pytest.raises(ProfileFormatError):
        DeviceProfile.load(tmp_path / "missing.json")

    wrong_schema = tmp_path / "wrong_schema.json"
    wrong_schema.write_text(json.dumps({"schema": "something.else"}))
    with pytest.raises(ProfileFormatError):
        DeviceProfile.load(wrong_schema)

    # a valid profile under a bumped version is refused, not half-read
    good = _profile().to_json()
    bad_version = tmp_path / "bad_version.json"
    bad_version.write_text(json.dumps({**good, "version": 999}))
    with pytest.raises(ProfileFormatError):
        DeviceProfile.load(bad_version)

    # structurally broken payload under a valid header
    broken = json.loads(json.dumps(good))
    broken["collectives"] = {"data": {"all_reduce": "nope"}}
    bad_body = tmp_path / "bad_body.json"
    bad_body.write_text(json.dumps(broken))
    with pytest.raises(ProfileFormatError):
        DeviceProfile.load(bad_body)


# -------------------------------------------------------- calibration


def test_calibrate_mesh_sets_measured_rates_and_curves():
    prof = _profile()
    cal = prof.calibrate_mesh(MESH)
    # chip efficiencies become measured/peak, so the effective rates the
    # cost model prices with ARE the measured rates
    assert cal.chip.eff_flops == pytest.approx(1e12)
    assert cal.chip.eff_hbm_bw == pytest.approx(1e11)
    ax = cal.axis("data")
    assert ax.curve("all_reduce") == (pytest.approx(25e-6),
                                      pytest.approx(2e10))
    # the raw axis bandwidth follows the measured all_gather rate (the
    # point-to-point proxy min_bw / stage transfers price with)
    assert ax.bw == pytest.approx(2e10)
    # calibrating twice is a no-op (find_staged_strategy re-calibrates
    # through its inner find_strategy calls)
    assert prof.calibrate_mesh(cal) == cal


def test_field_by_field_analytic_fallback():
    base = MESH.chip
    # only flops measured: hbm efficiency keeps the analytic default
    cal = _profile(measured_hbm_bw=None).calibrate_mesh(MESH)
    assert cal.chip.eff_flops == pytest.approx(1e12)
    assert cal.chip.hbm_efficiency == base.hbm_efficiency
    # only hbm measured: mxu efficiency keeps the analytic default
    cal = _profile(measured_flops=None).calibrate_mesh(MESH)
    assert cal.chip.mxu_efficiency == base.mxu_efficiency
    assert cal.chip.eff_hbm_bw == pytest.approx(1e11)
    # no collectives measured: axes keep their analytic bandwidth and
    # the zero-latency curve default
    cal = _profile(collectives={}).calibrate_mesh(MESH)
    for ax in cal.axes:
        assert ax.bw == ICI_BW and ax.curves == ()


def test_kernel_factors_normalize_to_fastest_backend():
    factors = _profile().kernel_factors()
    assert factors[("flash_attention", "xla")] == pytest.approx(1.0)
    assert factors[("flash_attention", "ref")] == pytest.approx(2.5)
    assert factors[("mamba_scan", "xla")] == pytest.approx(1.0)


@pytest.mark.parametrize("name", C.ALL_ARCHS)
def test_no_profile_costs_bit_identical(name):
    """Acceptance: without a profile every cost is *bit-identical* to the
    pre-profiling analytic model — the calibration seam (curve defaults,
    from_profile(None), kernel-factor overrides) must price to the exact
    same floats."""
    arch = C.reduced(name)
    shape = SHAPES["train_4k"]
    if arch.skip_reason(shape):
        shape = SHAPES["decode_32k"]
    graph = export_graph(arch, shape)
    analytic = CostModel(MESH, phase=shape.kind)
    seamed = CostModel.from_profile(None, MESH, phase=shape.kind)
    strat = find_strategy(graph, MESH, phase=shape.kind)
    assert seamed.total_time(graph, strat) == analytic.total_time(
        graph, strat)
    for node in graph.nodes.values():
        cfg = strat.assignment[node.name]
        assert seamed.t_c(node, cfg) == analytic.t_c(node, cfg)
    # an *empty* profile (nothing measured) is the same guarantee
    empty = DeviceProfile(device_kind="Empty v0")
    from_empty = CostModel.from_profile(empty, MESH, phase=shape.kind)
    assert from_empty.total_time(graph, strat) == analytic.total_time(
        graph, strat)


def test_searched_plan_records_profile_provenance():
    arch = C.reduced("llama3_2_1b")
    shape = SHAPES["train_4k"]
    graph = export_graph(arch, shape)
    strat = find_strategy(graph, MESH, phase="train", profile=_profile())
    assert strat.meta["device_profile"] == _profile().fingerprint()


def test_calibrated_mesh_survives_plan_codec(tmp_path):
    """A plan searched under a calibrated mesh must round-trip the
    measured curves and chip efficiencies through its JSON — reloading
    the plan reconstructs the same priced mesh."""
    from repro.plans import build_parallel_plan
    from repro.plans.parallel_plan import ParallelPlan

    arch = C.reduced("llama3_2_1b")
    pp = build_parallel_plan(
        arch, MESH, strategy="searched", phases=("decode",),
        prompt_len=64, max_batch=8, max_len=128, profile=_profile())
    path = pp.save(tmp_path / "plan.json")
    loaded = ParallelPlan.load(path, arch=arch)
    assert loaded.meta["device_profile"] == _profile().fingerprint()
    assert (loaded.meta["phases"]["decode"]["device_profile"]
            == _profile().fingerprint())
    cal = _profile().calibrate_mesh(MESH)
    assert loaded.mesh.chip.eff_flops == pytest.approx(cal.chip.eff_flops)
    assert loaded.mesh.axis("data").curves == cal.axis("data").curves
    assert loaded.mesh.axis("data").bw == pytest.approx(
        cal.axis("data").bw)


# ------------------------------------------------- end-to-end (slow)


PLAN_DIFFERENCE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    from repro import configs as C
    from repro.core import AxisSpec, ICI_BW, MeshSpec, find_strategy
    from repro.models.arch import SHAPES
    from repro.models.graph_export import export_graph
    from repro.profiling import build_profile

    # measure THIS host (CPU smoke ladders): orders of magnitude off the
    # TPU-v5e analytic constants in both compute and collective latency
    prof = build_profile(axes={"data": 4, "model": 2},
                         matmul_sizes=(128, 256),
                         stream_sizes=(1 << 20, 4 << 20),
                         collective_sizes=(1 << 16, 1 << 18, 1 << 20),
                         shape_classes=("small",),
                         repeats=3, warmup=1)
    assert prof.measured_flops and prof.collectives

    mesh = MeshSpec(axes=(AxisSpec("data", 4, ICI_BW),
                          AxisSpec("model", 2, ICI_BW)))
    # batch-1 long-context decode is where measured reality bites the
    # analytic model hardest: per-token collective messages are tiny, so
    # the measured launch latency (alpha ~100s of us on a CPU host, vs
    # the analytic 0) flips sharded configs to replicated
    moved = []
    for arch_name in ("rwkv6_1b6", "jamba_1_5_large"):
        arch = C.reduced(arch_name)
        for shape_name in ("decode_32k", "long_500k"):
            shape = SHAPES[shape_name]
            if arch.skip_reason(shape):
                continue
            graph = export_graph(arch, shape)
            analytic = find_strategy(graph, mesh, phase=shape.kind)
            profiled = find_strategy(graph, mesh, phase=shape.kind,
                                     profile=prof)
            assert profiled.meta["device_profile"] == prof.fingerprint()
            diff = [n for n in analytic.assignment
                    if analytic.assignment[n] != profiled.assignment[n]]
            if diff:
                moved.append((arch_name, shape_name, len(diff)))
    assert moved, "measured profile never moved any searched plan"
    print("OK moved=" + repr(moved))
""")


@pytest.mark.slow
def test_measured_profile_changes_searched_plan():
    """Acceptance: on the 8-virtual-device CI mesh, a profile measured on
    the actual (CPU) host steers the search to a different plan than the
    analytic TPU constants for at least one (arch, phase) cell."""
    r = subprocess.run([sys.executable, "-c", PLAN_DIFFERENCE],
                       capture_output=True, text=True, timeout=1200,
                       cwd=".")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout, r.stdout
