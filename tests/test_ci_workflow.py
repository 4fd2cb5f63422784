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
    assert set(wf["jobs"]) == {"lint", "test", "bench-smoke"}
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
    """The perf trajectory must accumulate even without pushes."""
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


def test_workflow_bench_job_uploads_artifact():
    wf = _load()
    job = wf["jobs"]["bench-smoke"]
    runs = _all_run_lines(job)
    assert "benchmarks.perf_iterations" in runs
    # the serving perf trajectory rides the same job/artifact: continuous
    # vs static-oracle (and paged vs dense) lands in BENCH_serving.json
    assert "benchmarks.serving_throughput" in runs
    assert "BENCH_serving.json" in runs
    uploads = [s for s in job["steps"]
               if str(s.get("uses", "")).startswith("actions/upload-artifact")]
    assert uploads and "BENCH_" in uploads[0]["with"]["path"]


def test_workflow_bench_job_gates_on_previous_run():
    """bench-smoke is a regression *gate*, not just an artifact upload:
    the previous run's BENCH_serving.json is restored from a device-kind
    cache key, compared via benchmarks.compare_bench with a 15%%
    tolerance, and this run's report is saved back as the new baseline."""
    wf = _load()
    job = wf["jobs"]["bench-smoke"]
    runs = _all_run_lines(job)
    assert "benchmarks.compare_bench" in runs
    assert "--max-regression 0.15" in runs
    restores = [s for s in job["steps"]
                if str(s.get("uses", "")).startswith("actions/cache/restore")]
    saves = [s for s in job["steps"]
             if str(s.get("uses", "")).startswith("actions/cache/save")]
    assert restores and saves
    # keyed on device kind so a CPU baseline never gates a TPU run
    assert "cpu" in restores[0]["with"]["key"]
    assert "restore-keys" in restores[0]["with"]
    assert "cpu" in saves[0]["with"]["key"]
    # the comparison runs before the baseline refresh: the gate must see
    # the restored previous report, not this run's copy
    names = [s.get("name", "") for s in job["steps"]]
    gate = next(i for i, n in enumerate(names) if "regression gate" in n.lower())
    refresh = next(i for i, n in enumerate(names) if "refresh" in n.lower())
    assert gate < refresh


def test_workflow_bench_job_exercises_searched_phase_plan():
    """The bench-smoke job must search a decode-phase plan on a forced
    multi-device host, run a serve trace under it, and upload the plan
    JSON next to BENCH_serving.json (plan files match the BENCH_* glob
    the artifact step uploads)."""
    wf = _load()
    job = wf["jobs"]["bench-smoke"]
    runs = _all_run_lines(job)
    assert "--strategy searched" in runs
    assert "--save-plan BENCH_serving_plan.json" in runs
    # single-device search is degenerate; the step must force a mesh
    assert "xla_force_host_platform_device_count" in runs
    uploads = [s for s in job["steps"]
               if str(s.get("uses", "")).startswith("actions/upload-artifact")]
    assert uploads and "BENCH_*.json" in uploads[0]["with"]["path"]


def test_workflow_bench_job_searches_staged_train_plan():
    """Both serving-bench steps must price a 2-stage 1F1B train plan so
    stage_count / pipeline_bubble_frac land in the gated report and the
    two-level search stays exercised in CI."""
    wf = _load()
    job = wf["jobs"]["bench-smoke"]
    staged = [s for s in job["steps"]
              if "--train-stages 2" in s.get("run", "")]
    assert len(staged) >= 2, "gated smoke AND phase-plan smoke must stage"
    # the gated report (the one compare_bench reads) carries the fields
    gated = next(s for s in staged
                 if "--out BENCH_serving.json" in s["run"])
    assert "--train-microbatches" in gated["run"]


def test_workflow_has_manual_dispatch_trigger():
    """Re-seeding a perf baseline (or re-checking a flaky runner) must
    not require pushing an empty commit."""
    trig = _triggers(_load())
    assert "workflow_dispatch" in trig


def test_workflow_uploads_artifacts_even_when_the_gate_fails():
    """A failed perf gate is exactly when the report JSONs are needed —
    the upload step must run on failure too."""
    wf = _load()
    job = wf["jobs"]["bench-smoke"]
    uploads = [s for s in job["steps"]
               if str(s.get("uses", "")).startswith("actions/upload-artifact")]
    assert uploads and uploads[0].get("if") == "always()"


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


def test_workflow_bench_job_runs_and_gates_the_int8_quant_pass():
    """The int8-quantized KV pool must stay visible to CI: a third
    serving pass runs the smoke trace with --kv-quant int8 into its own
    report, a second compare_bench invocation gates that report against
    its own baseline, and the refresh step rolls both baselines."""
    wf = _load()
    job = wf["jobs"]["bench-smoke"]
    int8_steps = [s for s in job["steps"]
                  if "--kv-quant int8" in s.get("run", "")]
    assert int8_steps, "no int8 serving-bench step"
    irun = int8_steps[0]["run"]
    assert "--smoke" in irun
    assert "--out BENCH_serving_int8.json" in irun
    gates = [s for s in job["steps"]
             if "benchmarks.compare_bench" in s.get("run", "")]
    int8_gates = [s for s in gates
                  if "bench-baseline/BENCH_serving_int8.json" in s["run"]
                  and "--current BENCH_serving_int8.json" in s["run"]]
    assert int8_gates, "no int8 gate invocation"
    refresh = next(s for s in job["steps"]
                   if "refresh" in s.get("name", "").lower())
    assert "BENCH_serving_int8.json" in refresh["run"]
    # the int8 pass and its gate run before the baseline refresh
    steps = job["steps"]
    assert steps.index(int8_steps[0]) < steps.index(refresh)
    assert steps.index(int8_gates[0]) < steps.index(refresh)


def test_workflow_bench_job_measures_and_feeds_a_device_profile():
    """The bench-smoke job must measure a DeviceProfile on the runner
    (launch.profile --smoke under forced virtual devices, so the
    collective sweep is non-degenerate), feed it into the *gated*
    serving bench via --device-profile (so cost_model_rel_error lands in
    the report compare_bench watches), and upload the profile JSON."""
    wf = _load()
    job = wf["jobs"]["bench-smoke"]
    profile_steps = [s for s in job["steps"]
                     if "repro.launch.profile" in s.get("run", "")]
    assert profile_steps, "no profile-smoke step"
    prun = profile_steps[0]["run"]
    assert "--smoke" in prun
    assert "xla_force_host_platform_device_count" in prun
    assert "--out DEVICE_profile.json" in prun
    gated = next(s for s in job["steps"]
                 if "--out BENCH_serving.json" in s.get("run", ""))
    assert "--device-profile DEVICE_profile.json" in gated["run"]
    # the profile must exist before the bench consumes it
    names = [s.get("name", "") for s in job["steps"]]
    prof_i = job["steps"].index(profile_steps[0])
    bench_i = job["steps"].index(gated)
    assert prof_i < bench_i, names
    uploads = [s for s in job["steps"]
               if str(s.get("uses", "")).startswith("actions/upload-artifact")]
    assert uploads and "DEVICE_profile.json" in uploads[0]["with"]["path"]


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


def test_compare_bench_gate_logic():
    """The regression gate the bench-smoke job runs: strict on the
    deterministic KV bytes, noise-floored on the timing ratio, and loud
    when a watched metric disappears from the current report."""
    import sys
    sys.path.insert(0, str(ROOT))
    from benchmarks.compare_bench import compare

    base = {"continuous_speedup": 1.34,
            "kv_reserved_frac": 0.33,
            "chunked_itl_p99_ratio": 0.55,
            "prefix_hit_rate": 0.71,
            "prefill_tokens_saved": 6144,
            "stage_count": 2,
            "pipeline_bubble_frac": 0.111,
            "cost_model_rel_error": 0.40,
            "quant_kv_reserved_frac": 0.3125,
            "quant_logit_agreement": 0.012,
            "modes": {"continuous": {"kv_bytes_reserved": 1000,
                                     "itl_p99_ms": 40.0}}}

    def cur(speedup=1.34, frac=0.33, kv=1000, itl=40.0, ratio=0.55,
            hit=0.71, saved=6144, stages=2, bubble=0.111, cmerr=0.40,
            qfrac=0.3125, qlogit=0.012):
        return {"continuous_speedup": speedup, "kv_reserved_frac": frac,
                "chunked_itl_p99_ratio": ratio,
                "prefix_hit_rate": hit, "prefill_tokens_saved": saved,
                "stage_count": stages, "pipeline_bubble_frac": bubble,
                "cost_model_rel_error": cmerr,
                "quant_kv_reserved_frac": qfrac,
                "quant_logit_agreement": qlogit,
                "modes": {"continuous": {"kv_bytes_reserved": kv,
                                         "itl_p99_ms": itl}}}

    assert compare(base, cur(), 0.15) == []
    # >15% speedup drop but still >= 1.0: runner jitter, not a failure
    assert compare(base, cur(speedup=1.10), 0.15) == []
    # >15% drop AND below parity: continuous batching stopped paying
    assert any("continuous_speedup" in f
               for f in compare(base, cur(speedup=0.95), 0.15))
    # deterministic KV bytes gate strictly, floor or not
    assert any("kv_bytes_reserved" in f
               for f in compare(base, cur(kv=1200), 0.15))
    assert any("kv_reserved_frac" in f
               for f in compare(base, cur(frac=0.40), 0.15))
    # the ITL tail gates strictly: >15% growth means admissions are
    # stalling decode again
    assert any("itl_p99_ms" in f
               for f in compare(base, cur(itl=50.0), 0.15))
    assert compare(base, cur(itl=44.0), 0.15) == []
    # the chunked/unchunked ratio is noise-floored at parity: any swing
    # below 1.0 is jitter while chunking still beats stall-the-world...
    assert compare(base, cur(ratio=0.95), 0.15) == []
    # ...but growth past both the floor and the tolerance fails
    assert any("chunked_itl_p99_ratio" in f
               for f in compare(base, cur(ratio=1.2), 0.15))
    # prefix_hit_rate is noise-floored at the 0.5 acceptance threshold:
    # a >15% dip that stays at-or-above the floor is trace-composition
    # drift, not a broken cache...
    assert compare(base, cur(hit=0.55), 0.15) == []
    # ...but a drop below both tolerance and floor means prompts stopped
    # matching entirely
    assert any("prefix_hit_rate" in f
               for f in compare(base, cur(hit=0.30), 0.15))
    # prefill_tokens_saved is deterministic for a fixed trace: strict
    assert any("prefill_tokens_saved" in f
               for f in compare(base, cur(saved=4000), 0.15))
    assert compare(base, cur(saved=6000), 0.15) == []
    # the 1F1B bubble is a pure cost-model output: strict, no floor
    assert any("pipeline_bubble_frac" in f
               for f in compare(base, cur(bubble=0.2), 0.15))
    assert compare(base, cur(bubble=0.09), 0.15) == []   # shrinking is fine
    # stage_count is informational: a move never fails the gate
    assert compare(base, cur(stages=4), 0.15) == []
    # calibration error is noise-floored at 1.0: a timed-metric swing
    # that stays under 100% error is runner jitter...
    assert compare(base, cur(cmerr=0.60), 0.15) == []
    # ...but growth past both tolerance and floor means the measured
    # profile stopped predicting the host
    assert any("cost_model_rel_error" in f
               for f in compare(base, cur(cmerr=1.4), 0.15))
    # a metric the baseline proves existed must not vanish silently
    gone = cur()
    del gone["kv_reserved_frac"]
    assert any("missing" in f for f in compare(base, gone, 0.15))
    # ...including the prefix metrics (e.g. the cache silently disabled)
    gone2 = cur()
    del gone2["prefix_hit_rate"]
    assert any("prefix_hit_rate" in f and "missing" in f
               for f in compare(base, gone2, 0.15))
    # ...but a metric absent from the *baseline* is just new: skipped
    part = {"continuous_speedup": 1.3}
    assert compare(part, cur(), 0.15) == []


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
