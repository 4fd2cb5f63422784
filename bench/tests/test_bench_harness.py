"""The whole harness on the CPU at toy size: a sound run is correct, each
fault planted in the timed path makes it incorrect, the control reads
above the program, and without a chip or without the program the command
exits non-zero and prints no result."""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from bench import run as bench_run
from bench.core import peaks
from bench.core.runtime import CompileCount
from bench.core.spec import ROOT

from toycells import toy_cell

CELLS = {"serve": "olmo-1b.chat", "train": "granite-3-2b-d8.train"}


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])


def drive(name, capsys, *, trace=0, fault=None, control=False):
    cell = toy_cell(name)
    args = types.SimpleNamespace(seed=2**33 + 5, seconds=2.0, trace=trace,
                                 rate=None, control=control, fault=fault)
    assert bench_run.run(cell, args, "(none)", CompileCount()) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("path", ["serve", "train"])
def test_sound_run_is_correct(path, capsys):
    out = drive(CELLS[path], capsys)
    assert out["correct"] is True, out["compared"]
    assert list(out)[-1] == "compared"
    names = {m["name"] for m in toy_cell(CELLS[path]).end_to_end}
    assert set(out["metrics"]) == names


@pytest.mark.parametrize("path, fault", [
    ("serve", "token"), ("serve", "state"), ("train", "state"),
    ("train", "half")])
def test_planted_fault_is_not_correct(path, fault, capsys):
    out = drive(CELLS[path], capsys, fault=fault)
    assert out["correct"] is False, out["compared"]


@pytest.mark.parametrize("path", ["serve", "train"])
def test_control_reads_above_the_program(path, capsys):
    out = drive(CELLS[path], capsys, control=True)
    for k, c in out["compared"].items():
        assert out["control"][f"control_{k}"] > c["value"], k


# limits for the toy serving cell, set by the cell's rule at toy size:
# above the program's readings (0 on the CPU, which multiplies in f32)
# and below the control's on the tests' seed (0.0105 and 0.00018).  A
# width-64 model's logits are too small for int8 weights to reach the
# real cell's limits, which the control fails on the chip (PERF.md).
TOY_SERVE_LIMITS = {"widest_logit_gap": 0.005, "mean_logit_gap": 5e-5}


@pytest.mark.parametrize("path, groups", [
    ("serve", ("control", "altered")), ("train", ("control", "half_batch"))])
def test_control_and_faults_fail_the_limits(path, groups, capsys,
                                            monkeypatch):
    """The harness's own comparison decides the control and each fault
    the reference plants: judged against the cell's limits, each comes
    out not correct while the program's run is correct."""
    from bench.core import checks

    if path == "serve":
        monkeypatch.setattr(checks, "load_limits",
                            lambda cell: dict(TOY_SERVE_LIMITS))
    out = drive(CELLS[path], capsys, control=True)
    assert out["correct"] is True, out["compared"]
    for g in groups:
        assert out["control"][f"{g}_correct"] is False, (g, out["control"])


def test_judge_controls_groups_readings_by_limit():
    limits = {"widest_logit_gap": 0.1, "mean_logit_gap": 0.01}
    got = bench_run.judge_controls(
        {"control_widest_logit_gap": 0.05, "control_mean_logit_gap": 0.02,
         "control_not_first_share": 0.1, "altered_widest_logit_gap": 0.01,
         "altered_mean_logit_gap": 0.001}, limits)
    assert got["control_correct"] is False
    assert got["altered_correct"] is True
    assert "control_not_first_share" in got and len(got) == 7


def test_traced_run_reports_per_layer_metrics(capsys):
    out = drive(CELLS["serve"], capsys, trace=1)
    # no device plane on the CPU: the trace-read metrics stay silent
    assert {"engine_step_ms.serve", "mfu.serve"} <= set(out["metrics"])
    assert "paged_decode_roofline.serve" not in out["metrics"]


def command(cwd, env=None):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS["train"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})))


def test_without_a_chip_exits_nonzero_and_prints_nothing():
    res = command(ROOT)
    assert res.returncode != 0 and res.stdout.strip() == ""
    assert "no TPU" in res.stderr


def test_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = command(tmp_path)
    assert res.returncode != 0 and res.stdout.strip() == ""
