"""CI pipeline guards: the workflow file stays well-formed and wired to
the tier-1 command, and the compat-grep gate actually fails when a
versioned JAX symbol leaks outside ``compat.py``."""

import subprocess
from pathlib import Path

import pytest

yaml = pytest.importorskip("yaml")

ROOT = Path(__file__).resolve().parents[1]
WORKFLOW = ROOT / ".github" / "workflows" / "ci.yml"


def _load():
    return yaml.safe_load(WORKFLOW.read_text())


def _all_run_lines(job):
    return "\n".join(s.get("run", "") for s in job["steps"])


def _triggers(wf):
    # pyyaml parses the bare `on:` key as boolean True
    return wf.get("on", wf.get(True))


def test_workflow_parses_with_expected_jobs():
    wf = _load()
    assert set(wf["jobs"]) == {"lint", "test"}
    for name, job in wf["jobs"].items():
        assert "runs-on" in job and job["steps"], name
        for step in job["steps"]:
            assert "uses" in step or "run" in step, (name, step)


def test_workflow_cancels_superseded_runs_and_bounds_job_time():
    """Stacked pushes must cancel in-flight runs of the same ref, and
    every job needs an explicit timeout — a hung Pallas-interpret test
    otherwise burns the 6-hour GitHub default."""
    wf = _load()
    conc = wf["concurrency"]
    assert conc["cancel-in-progress"] is True
    assert "github.ref" in conc["group"]
    for name, job in wf["jobs"].items():
        assert isinstance(job.get("timeout-minutes"), int), (
            f"job {name!r} has no timeout-minutes")
        assert job["timeout-minutes"] <= 60, name


def test_workflow_has_weekly_schedule_trigger():
    """CI runs weekly even without pushes, so drift in the runner image
    or the unpinned test tools shows before the next change does."""
    trig = _triggers(_load())
    crons = [e["cron"] for e in trig.get("schedule", [])]
    assert crons, "no schedule: trigger"
    # weekly: a 5-field cron with a concrete day-of-week
    assert any(c.split()[4] != "*" for c in crons), crons


def test_workflow_test_job_runs_tier1_on_jax_matrix():
    """CI tests one installation: the JAX pinned in the workflow is the
    one installed here, and no job installs another."""
    import jax
    wf = _load()
    job = wf["jobs"]["test"]
    assert "strategy" not in job       # no version matrix
    runs = _all_run_lines(job)
    pin = f'"jax[cpu]=={jax.__version__}"'
    assert pin in runs
    for name, other in wf["jobs"].items():
        for line in _all_run_lines(other).splitlines():
            if "jax[cpu]" in line:
                assert pin in line, (name, line)
    assert "python -m pytest -x -q" in runs
    # without a YAML parser this module skips in CI — the guards would
    # silently stop guarding
    assert "pyyaml" in runs
    # pip caching keeps the job fast
    setups = [s for s in job["steps"]
              if str(s.get("uses", "")).startswith("actions/setup-python")]
    assert setups and setups[0]["with"].get("cache") == "pip"


def test_workflow_has_manual_dispatch_trigger():
    """Re-checking a flaky runner must not require pushing an empty
    commit."""
    trig = _triggers(_load())
    assert "workflow_dispatch" in trig


def test_workflow_lint_ruff_pin_matches_pyproject_dev_extras():
    """CI and `pip install -e .[dev]` must lint with the same ruff —
    an unpinned CI ruff goes red on upstream releases, a drifted local
    pin argues with CI."""
    import re
    lint_run = _all_run_lines(_load()["jobs"]["lint"])
    ci_pin = re.search(r"ruff==([\w.]+)", lint_run)
    assert ci_pin, "lint job must pin ruff (ruff==X.Y.Z)"
    py = (ROOT / "pyproject.toml").read_text()
    pyproject_pin = re.search(r'"ruff==([\w.]+)"', py)
    assert pyproject_pin, "pyproject dev extras must pin ruff"
    assert ci_pin.group(1) == pyproject_pin.group(1)


def _compat_grep(tree: Path) -> int:
    """The exact gate the lint job runs, pointed at ``tree``/src."""
    script = ('hits="$(grep -rn "CompilerParams\\|AxisType" src/ '
              '| grep -v compat.py || true)"; '
              'if [ -n "$hits" ]; then exit 1; fi')
    return subprocess.run(["bash", "-c", script], cwd=tree).returncode


def test_compat_grep_passes_on_clean_tree_and_fails_on_violation(tmp_path):
    wf_run = _all_run_lines(_load()["jobs"]["lint"])
    assert 'grep -rn "CompilerParams\\|AxisType" src/' in wf_run

    assert _compat_grep(ROOT) == 0, "the real tree must satisfy the invariant"

    bad = tmp_path / "src" / "repro"
    bad.mkdir(parents=True)
    (bad / "oops.py").write_text(
        "from jax.experimental.pallas.tpu import TPUCompilerParams\n")
    assert _compat_grep(tmp_path) == 1

    # ...and references inside compat.py stay allowed
    (bad / "oops.py").unlink()
    (bad / "compat.py").write_text("CompilerParams = None\n")
    assert _compat_grep(tmp_path) == 0


def _kernel_grep(tree: Path) -> int:
    """The kernel-boundary gate the lint job runs, pointed at ``tree``."""
    script = ('hits="$(grep -rn "pl\\.BlockSpec\\|pltpu" src/ '
              '| grep -v "src/repro/kernels/" | grep -v compat.py || true)"; '
              'if [ -n "$hits" ]; then exit 1; fi')
    return subprocess.run(["bash", "-c", script], cwd=tree).returncode


def test_kernel_boundary_grep_passes_clean_and_fails_on_leak(tmp_path):
    """Pallas internals (pl.BlockSpec / pltpu) may only appear inside
    src/repro/kernels/ and compat.py — everywhere else must go through
    the dispatcher.  The paged KV work is exactly where this starts
    drifting, so the lint job greps for it and this test keeps the grep
    honest against a synthetic violation."""
    wf_run = _all_run_lines(_load()["jobs"]["lint"])
    assert 'grep -rn "pl\\.BlockSpec\\|pltpu" src/' in wf_run
    assert 'grep -v "src/repro/kernels/"' in wf_run

    assert _kernel_grep(ROOT) == 0, "the real tree must satisfy the invariant"

    bad = tmp_path / "src" / "repro"
    (bad / "serve").mkdir(parents=True)
    (bad / "serve" / "oops.py").write_text(
        "from jax.experimental.pallas import tpu as pltpu\n")
    assert _kernel_grep(tmp_path) == 1

    # ...kernels/ and compat.py stay allowed
    (bad / "serve" / "oops.py").unlink()
    (bad / "kernels").mkdir()
    (bad / "kernels" / "fast.py").write_text(
        "from jax.experimental.pallas import tpu as pltpu\n")
    (bad / "compat.py").write_text(
        "from jax.experimental.pallas import tpu as _pltpu\n")
    assert _kernel_grep(tmp_path) == 0
