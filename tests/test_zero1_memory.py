"""ZeRO-1 AdamW moments and the plan search's memory count.

The program lays the moments out by ``repro.plans.adamw_shardings`` and
the search counts them by ``LayerConfig.moment_store_degree``; both take
``core.config.zero1_axes``.  On a virtual 2x2 mesh the count of every
persistent byte (params, grads, the microbatch accumulator, moments)
must equal the bytes the program's own leaves hold per device, and the
ZeRO-1 step must compute what the step with moments laid out as their
parameters computes, and what the dense family's reference computes.

The four-device checks run in one subprocess: the virtual device count
must be fixed before jax initializes.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from repro import configs
from repro.core import AxisSpec, ICI_BW, MeshSpec, SearchOptions, find_strategy
from repro.core.cost_model import strategy_device_bytes
from repro.core.strategies import BASELINES
from repro.models.graph_export import export_graph, phase_shape
from repro.plans import adamw_shardings, to_shardings

ROOT = Path(__file__).resolve().parents[1]
MESH_2X2 = MeshSpec(axes=(AxisSpec("data", 2, ICI_BW),
                          AxisSpec("model", 2, ICI_BW)))
STRATEGIES = ("searched", "data", "model", "owt")


def test_one_device_moments_take_the_parameters_shardings():
    mesh = jax.sharding.Mesh([[jax.devices()[0]]], ("data", "model"))
    P = jax.sharding.PartitionSpec
    like = {"w": jax.ShapeDtypeStruct((4, 6), "float32"),
            "b": jax.ShapeDtypeStruct((6,), "float32")}
    p_sh = to_shardings({"w": P(None, "model"), "b": P("data")}, mesh,
                        like=like)
    o_sh = adamw_shardings(p_sh, mesh)
    assert o_sh["m"]["w"] is p_sh["w"] and o_sh["v"]["b"] is p_sh["b"]
    assert o_sh["step"].spec == P()


def test_olmo_1b_fits_under_the_searched_plan_and_not_under_data():
    """All 16 layers of olmo-1b, 16 x 2048 a step on the 2x2 host: the
    data plan's state (params, grads and accumulator replicated, moments
    split over data) exceeds the budget, so the search splits the heavy
    layers' parameters."""
    arch = configs.get("olmo-1b")
    graph = export_graph(arch, phase_shape("train", seq_len=2048, batch=16))
    budget = SearchOptions().hbm_budget
    searched = find_strategy(graph, MESH_2X2, phase="train")
    assert searched.meta["device_bytes"] <= budget
    data = BASELINES["data"](graph, MESH_2X2)
    assert strategy_device_bytes(graph, data, MESH_2X2, True) > budget
    split = [n for n, c in searched.assignment.items()
             if graph.nodes[n].param_bytes > 1e6 and c.param_axes()]
    assert split, "no parameter-heavy layer is split"


SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import collections, contextlib, dataclasses, io, json, math, sys, types
    sys.path[:0] = [{root!r}, {root!r} + "/src", {root!r} + "/bench/tests"]
    import jax, jax.numpy as jnp, numpy as np
    from repro import compat, configs
    from repro.core import AxisSpec, ICI_BW, MeshSpec, find_strategy
    from repro.core.cost_model import TRAIN_ACTIVATION_ALLOWANCE, node_device_bytes
    from repro.core.sharding import use_mesh
    from repro.core.strategies import BASELINES
    from repro.models import lm, strategy_to_plan
    from repro.models.graph_export import export_graph, phase_shape
    from repro.optim import AdamWConfig, adamw_init
    from repro.plans import adamw_shardings, param_pspecs, to_shardings
    from repro.plans.shardings import _RULES
    from repro.train import TrainConfig, make_train_step

    arch = configs.reduced("olmo_1b")
    S, B = 64, 8
    spec = MeshSpec(axes=(AxisSpec("data", 2, ICI_BW),
                          AxisSpec("model", 2, ICI_BW)))
    mesh = compat.make_mesh((2, 2), ("data", "model"))
    graph = export_graph(arch, phase_shape("train", seq_len=S, batch=B))
    params_abs = jax.eval_shape(
        lambda k: lm.init_lm(k, arch, jnp.float32), jax.random.PRNGKey(0))

    def node_kind(path):
        keys = [getattr(k, "key", None) for k in path]
        return _RULES[(keys[2], keys[3])][0] if keys[0] == "stack" else keys[0]

    def per_device(shardings):
        out = collections.Counter()
        for (path, x), sh in zip(
                jax.tree_util.tree_flatten_with_path(params_abs)[0],
                jax.tree.leaves(shardings)):
            out[node_kind(path)] += (math.prod(sh.shard_shape(x.shape))
                                     * x.dtype.itemsize)
        return out

    out = {{"layouts": {{}}}}
    plans = {{}}
    for name in {strategies!r}:
        strat = (find_strategy(graph, spec, phase="train")
                 if name == "searched" else BASELINES[name](graph, spec))
        plan = plans[name] = strategy_to_plan(strat, arch)
        params, moments = collections.Counter(), collections.Counter()
        total = 0.0
        for key, node in graph.nodes.items():
            cfg, kind = strat[key], key.split(".")[-1]
            params[kind] += node.param_bytes / cfg.param_store_degree(spec)
            moments[kind] += 2 * node.param_bytes / cfg.moment_store_degree(spec)
            total += node_device_bytes(node, cfg, spec, True)
        p_sh = to_shardings(param_pspecs(params_abs, arch, plan), mesh,
                            like=params_abs)
        o_sh = adamw_shardings(p_sh, mesh)
        prog_p, prog_m = per_device(p_sh), per_device(o_sh["m"])
        prog_v = per_device(o_sh["v"])
        out["layouts"][name] = {{
            "segments": len(plan.segments),
            "counted": {{"params": {{k: v for k, v in params.items() if v}},
                        "moments": {{k: v for k, v in moments.items() if v}},
                        "persistent": total,
                        "search": strat.meta.get("device_bytes")}},
            "program": {{"params": dict(prog_p),
                        "moments": {{k: prog_m[k] + prog_v[k] for k in prog_m}},
                        # params, grads and accumulator split alike
                        "persistent": 3 * sum(prog_p.values())
                        + sum(prog_m.values()) + sum(prog_v.values())}}}}
    out["allowance"] = TRAIN_ACTIVATION_ALLOWANCE

    # the step: ZeRO-1 moments against moments laid out as their params
    plan = plans["searched"]
    step_fn = make_train_step(arch, plan, TrainConfig(
        optimizer=AdamWConfig(lr=1e-2, warmup_steps=0), microbatches=2,
        q_chunk=16, loss_chunk=16))
    # on the host, so that each device_put below makes arrays of its own
    # (one put into a replicated layout may reuse the source's buffer,
    # which the donating step then deletes)
    params0 = jax.tree.map(np.asarray, lm.init_lm(jax.random.PRNGKey(0), arch,
                                                  jnp.float32))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (3, B, S), 0,
                                arch.vocab)
    p_sh = to_shardings(param_pspecs(params0, arch, plan), mesh, like=params0)

    def train(o_sh):
        step = jax.jit(step_fn, donate_argnums=(0, 1),
                       out_shardings=(p_sh, o_sh, None))
        p = jax.device_put(params0, p_sh)
        o = jax.device_put(adamw_init(params0), o_sh)
        losses = []
        with use_mesh(mesh):
            for i in range(3):
                p, o, met = step(p, o, {{"tokens": tokens[i]}})
                losses.append(float(met["loss"]))
        return p, o, losses

    zero1 = adamw_shardings(p_sh, mesh)
    as_params = {{"m": p_sh, "v": p_sh, "step": zero1["step"]}}
    p_z, o_z, l_z = train(zero1)
    p_p, o_p, l_p = train(as_params)
    dev = jax.devices()[0]
    held = sum(sh.data.nbytes for leaf in jax.tree.leaves((o_z["m"], o_z["v"]))
               for sh in leaf.addressable_shards if sh.device == dev)
    out["step"] = {{
        "losses": [l_z, l_p],
        "max_param_gap": max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree.leaves(p_z), jax.tree.leaves(p_p))),
        "max_moment_gap": max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree.leaves(o_z), jax.tree.leaves(o_p))),
        "moment_bytes_held": held,
        "moment_bytes_counted": sum(
            out["layouts"]["searched"]["counted"]["moments"].values()),
        "split": sum(leaf.sharding != sh for leaf, sh in zip(
            jax.tree.leaves(o_z["m"]), jax.tree.leaves(p_sh))),
    }}

    # the whole harness on a toy olmo-1b.train-2x2 cell: the program's
    # ZeRO-1 step checked by the dense family's reference over four chips
    from bench import run as bench_run
    from bench.core.runtime import CompileCount
    from toycells import toy_cell
    cell = toy_cell("olmo-1b.train-2x2")
    cell = dataclasses.replace(cell, traffic=dict(cell.traffic, batch=B))
    args = types.SimpleNamespace(seed=2**33 + 19, seconds=1.0, trace=0,
                                 rate=None, control=False, fault=None)
    with contextlib.redirect_stdout(io.StringIO()) as printed:
        assert bench_run.run(cell, args, "(none)", CompileCount()) == 0
    out["run"] = json.loads(printed.getvalue().strip().splitlines()[-1])
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def four_devices():
    res = subprocess.run(
        [sys.executable, "-c",
         SCRIPT.format(root=str(ROOT), strategies=STRATEGIES)],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode == 0, res.stderr[-3000:]
    line = [ln for ln in res.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_adamw_shardings_split_moments_as_counted(four_devices, strategy):
    # the program stores every layer by the plan's dominant segment; the
    # count takes each layer's own config, so where the searched plan
    # has a layer of its own (olmo's first attention) the two agree only
    # if that layer's split stores as many bytes a chip
    got = four_devices["layouts"][strategy]
    assert got["program"]["params"] == pytest.approx(got["counted"]["params"])
    assert got["program"]["moments"] == pytest.approx(
        got["counted"]["moments"])


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_search_count_equals_program_leaves(four_devices, strategy):
    got = four_devices["layouts"][strategy]
    counted = got["counted"]["persistent"]
    assert got["program"]["persistent"] == pytest.approx(counted, rel=1e-12)
    if got["counted"]["search"] is not None:
        assert got["counted"]["search"] == pytest.approx(
            counted + four_devices["allowance"], rel=1e-12)


def test_zero1_step_matches_moments_laid_out_as_params(four_devices):
    got = four_devices["step"]
    # the same f32 arithmetic on differently split arrays: the update's
    # clipping norm sums in another order (one f32 rounding, 1.2e-7)
    z, p = got["losses"]
    assert z == pytest.approx(p, rel=1e-6)
    assert got["max_param_gap"] < 1e-6
    assert got["max_moment_gap"] < 1e-6
    assert got["split"] > 0
    assert got["moment_bytes_held"] == got["moment_bytes_counted"]


def test_zero1_step_matches_the_dense_reference(four_devices):
    run = four_devices["run"]
    assert run["device"]["count"] == 4
    assert run["correct"] is True, run["compared"]
