"""Families: the ``dense`` family gives the numbers the harness gave
before it asked a family for them (``data/dense_family.json``), a family
that only the tests know runs both drivers, and the reference's state
split over four host devices reads as on one."""

import dataclasses
import hashlib
import json
import os
import subprocess
import sys
import textwrap
import types
from pathlib import Path

import jax
import numpy as np
import pytest

import twin_family
from bench import run as bench_run
from bench.core import flops, weights
from bench.core.runtime import CompileCount
from bench.core.spec import BENCH, ROOT, program_arch
from bench.reference import dense

from conftest import toy_config
from toycells import toy_cell

PINNED = json.loads((Path(__file__).parent / "data" /
                     "dense_family.json").read_text())
CONFIGS = ("olmo-1b", "granite-3-2b-d8")
OPT = {"lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
       "clip_norm": 1.0, "warmup_steps": 0, "total_steps": 1000,
       "min_lr_ratio": 0.1}


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def digest(text: bytes) -> str:
    return hashlib.sha256(text).hexdigest()


def readings(cfg, devices, seed=5):
    batches = [weights.make_batch(seed, i, 4, 32, cfg["vocab_size"])["tokens"]
               for i in range(3)]
    return dense.train_readings(
        weights.make_params(cfg, seed), batches, cfg, OPT, cfg["z_loss_coef"],
        lambda p: weights.leaf_change_norms(cfg, seed, p), devices=devices,
        rows_per_block=2)


# --------------------------------------------------------------------------- #
# dense: the parent's numbers
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("name", CONFIGS)
def test_dense_layout_and_weights_are_the_parents(name):
    pin = PINNED[name]
    assert digest(repr(dense.leaf_specs(config(name))).encode()) == pin["layout"]
    toy = toy_config(name)
    assert digest(repr(dense.leaf_specs(toy)).encode()) == pin["layout_toy"]
    params = weights.make_params(toy, 2**33 + 7)
    got = digest(b"".join(np.asarray(x).tobytes()
                          for x in jax.tree.leaves(params)))
    assert got == pin["params"]


@pytest.mark.parametrize("name", CONFIGS)
def test_dense_flop_counts_are_the_parents(name):
    cfg = config(name)
    assert [flops.matmul_params(cfg), flops.attention_flops(cfg, 7, 100, True),
            flops.train_flops_per_token(cfg, 2048),
            flops.serve_flops(cfg, 100, 7),
            list(flops.paged_decode_cost(cfg, [100, 28, 5], 4))] == \
        PINNED[name]["flops"]


@pytest.mark.parametrize("name", CONFIGS)
def test_dense_reference_on_one_device_is_the_parents(name):
    """On one device the split layout is the computation the reference
    made before it had one: every reading equal, to the last bit."""
    assert readings(toy_config(name), jax.devices()[:1]) == \
        PINNED[name]["readings"]


def test_dense_refuses_another_block():
    from repro import configs

    cfg = toy_config("olmo-1b")
    moe = configs.reduced("olmoe_1b_7b")
    cfg.update(program_arch="olmoe_1b_7b", num_hidden_layers=moe.n_layers)
    with pytest.raises(ValueError, match="'block'"):
        program_arch(cfg)


# --------------------------------------------------------------------------- #
# a family the harness does not know: both drivers, by the key alone
# --------------------------------------------------------------------------- #
CELLS = {"serve": "olmo-1b.chat", "train": "granite-3-2b-d8.train"}
CALLED = {
    "serve": {"check_arch", "leaf_specs", "empty_tree", "matmul_params",
              "attention_flops", "served_gap_fn"},
    "train": {"check_arch", "leaf_specs", "empty_tree", "train_readings"},
}


@pytest.mark.parametrize("path", ["serve", "train"])
def test_a_family_of_the_tests_runs_both_drivers(path, capsys, monkeypatch):
    monkeypatch.setitem(sys.modules, "bench.reference.twin", twin_family)
    twin_family.CALLS.clear()
    cell = toy_cell(CELLS[path])
    cfg = dict(cell.config, family="twin")
    cell = dataclasses.replace(cell, config=cfg)
    args = types.SimpleNamespace(seed=2**33 + 11, seconds=2.0, trace=0,
                                 rate=None, control=False, fault=None)
    assert bench_run.run(cell, args, "(none)", CompileCount()) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True, out["compared"]
    assert CALLED[path] <= set(twin_family.CALLS), twin_family.CALLS
    # the twin's leaves draw their keys in another order
    ours = weights.make_params(cfg, 3)["embed"]["table"]
    dense_ = weights.make_params(cell.config | {"family": "dense"},
                                 3)["embed"]["table"]
    assert not np.array_equal(ours, dense_)


def test_optimizer_layout_comes_from_the_program(capsys, monkeypatch):
    """The train driver lays AdamW's state out by the program's own rule
    where ``repro.plans`` has one, so a change of that layout reaches the
    benchmark; without one, by the rule ``launch/train.run`` writes out."""
    from repro import plans

    from bench.drivers import train

    monkeypatch.delattr(plans, "adamw_shardings", raising=False)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))
    p_sh = {"w": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())}
    own = train.adamw_shardings(p_sh, mesh)
    assert own["m"] is p_sh and own["v"] is p_sh
    assert own["step"].spec == jax.sharding.PartitionSpec()

    seen = []

    def rule(p_sh, mesh):
        seen.append(mesh)
        return {"m": p_sh, "v": p_sh, "step": jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())}

    monkeypatch.setattr(plans, "adamw_shardings", rule, raising=False)
    args = types.SimpleNamespace(seed=2**33 + 13, seconds=1.0, trace=0,
                                 rate=None, control=False, fault=None)
    assert bench_run.run(toy_cell(CELLS["train"]), args, "(none)",
                         CompileCount()) == 0
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1])[
        "correct"] is True
    assert len(seen) == 1


def test_the_harness_names_no_family_and_no_dense_leaf():
    """Drivers, core and readers reach a family by the configuration's
    key only.  The leaves' group names (``attn``, ``stack``, ...) are also
    the program's scope names, which the readers do name."""
    leaves = {p[-1] for name in CONFIGS
              for p, _, _ in dense.leaf_specs(config(name))}
    files = [ROOT / "bench" / "run.py"] + [
        f for d in ("drivers", "core", "metrics")
        for f in sorted((ROOT / "bench" / d).glob("*.py"))]
    for f in files:
        text = f.read_text()
        assert "reference.dense" not in text and "import dense" not in text, f
        assert "twin" not in text, f
        for leaf in leaves:
            for q in "\"'":
                assert f"{q}{leaf}{q}" not in text, (f, leaf)


# --------------------------------------------------------------------------- #
# four host devices: the reference split, a cell priced on its mesh, and
# a four-chip training cell driven whole
# --------------------------------------------------------------------------- #
SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses, json, sys
    sys.path[:0] = [{root!r}, {root!r} + "/src", {tests!r}]
    import jax
    import jax.numpy as jnp
    from test_bench_family import readings
    from conftest import toy_config
    from toycells import toy_cell
    from bench.core import scopes
    from bench.reference import dense

    cfg = dict(toy_config("granite-3-2b-d8"), num_hidden_layers=4)
    shapes = {{"rows": (16, 2), "cols": (3, 8), "none": (3, 5)}}
    layout, _ = dense.split_layout(
        {{k: jnp.zeros(s) for k, s in shapes.items()}}, jax.devices())
    out = {{"one": readings(cfg, jax.devices()[:1]),
           "four": readings(cfg, jax.devices()),
           "specs": {{k: str(s.spec) for k, s in layout.items()}}}}
    cell = dataclasses.replace(toy_cell("granite-3-2b-d8.train"), chips=4)
    graph, cm, config_of = scopes.cell_pricing(cell)
    out["mesh"] = [[a.name, a.size] for a in cm.mesh.axes]
    out["predicted"] = scopes.predicted_by_scope(graph, cm, config_of)
    out["scopes"] = sorted({{scopes.node_scope(n) for n in graph.nodes}})
    # the whole harness on a four-chip training cell: the program's
    # sharded step, checked by the reference split over the four devices
    import contextlib, io, types
    from bench import run as bench_run
    from bench.core.runtime import CompileCount
    args = types.SimpleNamespace(seed=2**33 + 17, seconds=1.0, trace=0,
                                 rate=None, control=False, fault=None)
    with contextlib.redirect_stdout(io.StringIO()) as printed:
        assert bench_run.run(cell, args, "(none)", CompileCount()) == 0
    out["run"] = json.loads(printed.getvalue().strip().splitlines()[-1])
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def four_devices():
    tests = str(Path(__file__).parent)
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(root=str(ROOT), tests=tests)],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode == 0, res.stderr[-3000:]
    line = [ln for ln in res.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_split_layout_takes_the_first_axis_the_devices_divide(four_devices):
    assert four_devices["specs"] == {
        "rows": "PartitionSpec('devices', None)",
        "cols": "PartitionSpec(None, 'devices')",
        "none": "PartitionSpec(None, None)"}


def test_reference_over_four_devices_reads_as_on_one(four_devices):
    one, four = four_devices["one"], four_devices["four"]
    # split leaves change only the order of the f32 sums (a gradient's
    # partial sums over the split axis, the clipping norm over leaves), so
    # the readings differ by f32 rounding (1.2e-7 a sum) carried through
    # norms of cancelling sums and three AdamW steps: on the CPU at most
    # 5e-8 (loss), 3.0e-6 (wq's gradient), 1.3e-6 (ln2's change)
    np.testing.assert_allclose(four["loss"], one["loss"], rtol=1e-6)
    for key in ("grad_norm", "change_norm"):
        assert four[key].keys() == one[key].keys()
        for leaf, v in one[key].items():
            np.testing.assert_allclose(four[key][leaf], v, rtol=1e-5,
                                       err_msg=f"{key} {leaf}")


def test_cell_on_four_chips_is_priced_on_its_mesh(four_devices):
    assert four_devices["mesh"] == [["data", 2], ["model", 2]]
    predicted = four_devices["predicted"]
    assert sorted(predicted) == four_devices["scopes"]
    assert all(v > 0 for v in predicted.values())


def test_four_chip_training_cell_is_correct(four_devices):
    run = four_devices["run"]
    assert run["device"]["count"] == 4
    assert run["correct"] is True, run["compared"]
