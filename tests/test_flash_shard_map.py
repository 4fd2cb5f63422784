"""Flash attention shard-mapped under a 2x2 mesh.

Under a multi-device mesh the dispatcher runs a Pallas kernel with
``shard_map`` where the call's specs split no sequence and no more heads
than KH divides; elsewhere it passes the kernel over and counts the skip.
On a virtual ("data" 2, "model" 2) CPU mesh the ``interpret`` backend,
which takes the same rule as the native one, must give the output and
(dq, dk, dv) of the ``xla`` backend.  A call that cannot be mapped falls
back (with the platform described as a TPU, so that the native kernel is
the one passed over) and counts one skip.  The model's own call site
must hand the dispatcher specs it can map: a reduced olmo train step
under the searched 2x2 plan, forced to ``interpret``, maps every
attention call and matches the step on ``xla``.

The four-device runs share one subprocess: the virtual device count must
be fixed before jax initializes.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

# name -> (B, H, KH, S, q spec, k/v spec) in kernel layout (B, H, S, D);
# the k/v spec is what ``constrain`` leaves of the q spec at KH heads
CASES = {
    "batch_over_both": (8, 4, 4, 256, ("data_model",), ("data_model",)),
    "batch_data_heads_model": (4, 4, 4, 256, ("data", "model"),
                               ("data", "model")),
    "gqa_heads_model": (4, 4, 2, 256, ("data", "model"), ("data", "model")),
    "seq_split": (4, 4, 4, 256, ("data", None, "model"),
                  ("data", None, "model")),
    "gqa_heads_past_kh": (4, 4, 1, 256, ("data", "model"), ("data",)),
}
MAPPED = ("batch_over_both", "batch_data_heads_model", "gqa_heads_model")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, sys
    sys.path[:0] = [{root!r} + "/src"]
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import compat, configs
    from repro.core import AxisSpec, ICI_BW, MeshSpec, find_strategy
    from repro.core.sharding import use_mesh
    from repro.kernels import dispatch
    from repro.models import lm, strategy_to_plan
    from repro.models.graph_export import export_graph, phase_shape
    from repro.optim import adamw_init
    from repro.plans import param_pspecs, to_shardings
    from repro.train import TrainConfig, make_train_step

    mesh = compat.make_mesh((2, 2), ("data", "model"))
    D = 64

    def spec(entries):
        return P(*[("data", "model") if e == "data_model" else e
                   for e in entries])

    def run(q, k, v, w, specs, backend):
        def loss(q, k, v):
            o = dispatch.call("flash_attention", q, k, v, causal=True,
                              block_q=128, block_k=128, specs=specs,
                              backend=backend)
            return jnp.sum(o * w), o
        (_, o), g = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return [np.asarray(t) for t in (o, *g)]

    out = {{}}
    for name, (B, H, KH, S, qs, kvs) in {cases!r}.items():
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(keys[0], (B, H, S, D), jnp.float32)
        k = jax.random.normal(keys[1], (B, KH, S, D), jnp.float32)
        v = jax.random.normal(keys[2], (B, KH, S, D), jnp.float32)
        w = jax.random.normal(keys[3], (B, H, S, D), jnp.float32)
        specs = (spec(qs), spec(kvs), spec(kvs))
        want = run(q, k, v, w, None, "xla")
        dispatch.reset_selection_counts()
        placed = [jax.device_put(x, NamedSharding(mesh, s))
                  for x, s in zip((q, k, v), specs)]
        with use_mesh(mesh):
            if name in {mapped!r}:
                got = run(*placed, w, specs, "interpret")
            else:
                compat.default_platform.cache_clear()
                real = compat.default_platform
                compat.default_platform = lambda: "tpu"
                try:
                    got = run(*placed, w, specs, None)
                finally:
                    compat.default_platform = real
        counts = {{key: {{"/".join(k): n for k, n in fn().items()}}
                  for key, fn in (("selected", dispatch.selection_counts),
                                  ("maps", dispatch.spmd_maps),
                                  ("skips", dispatch.spmd_skips))}}
        out[name] = dict(counts, err=[
            float(np.max(np.abs(a - b))) for a, b in zip(got, want)],
            scale=[float(np.max(np.abs(b))) for b in want])

    # the model's call site: reduced olmo, searched 2x2 plan
    arch = configs.reduced("olmo_1b")
    S, B = 128, 8
    spec22 = MeshSpec(axes=(AxisSpec("data", 2, ICI_BW),
                            AxisSpec("model", 2, ICI_BW)))
    graph = export_graph(arch, phase_shape("train", seq_len=S, batch=B))
    plan = strategy_to_plan(find_strategy(graph, spec22, phase="train"),
                            arch)
    params = lm.init_lm(jax.random.PRNGKey(0), arch, jnp.float32)
    params = jax.device_put(params, to_shardings(
        param_pspecs(params, arch, plan), mesh, like=params))
    batch = {{"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S),
                                          0, arch.vocab)}}
    step_out = {{}}
    for backend in ("xla", "interpret"):
        dispatch.reset_selection_counts()
        step = make_train_step(arch, plan, TrainConfig(
            q_chunk=128, kernel_backend=backend))
        with use_mesh(mesh):
            _, opt, met = jax.jit(step)(params, adamw_init(params), batch)
        step_out[backend] = {{
            "loss": float(met["loss"]),
            "grad_norm": float(met["grad_norm"]),
            "maps": {{"/".join(k): n
                      for k, n in dispatch.spmd_maps().items()}},
            "skips": {{"/".join(k): n
                       for k, n in dispatch.spmd_skips().items()}}}}
    out["train_step"] = step_out
    print("RESULT" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def four_devices():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(root=str(ROOT), cases=CASES,
                                             mapped=MAPPED)],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT")]
    return json.loads(line[-1][len("RESULT"):])


@pytest.mark.parametrize("name", MAPPED)
def test_mapped_kernel_matches_xla(four_devices, name):
    res = four_devices[name]
    for err, scale in zip(res["err"], res["scale"]):
        assert err <= 2e-5 * max(1.0, scale), (name, res)
    assert res["maps"] == {"flash_attention/interpret": 1}, res
    assert res["selected"]["flash_attention/interpret"] == 1, res
    assert res["selected"]["flash_attention_bwd/pallas"] == 1, res
    assert res["skips"] == {}, res


@pytest.mark.parametrize("name", ["seq_split", "gqa_heads_past_kh"])
def test_unmappable_split_falls_back_and_counts_a_skip(four_devices, name):
    res = four_devices[name]
    for err, scale in zip(res["err"], res["scale"]):
        assert err <= 2e-5 * max(1.0, scale), (name, res)
    assert res["skips"] == {"flash_attention/pallas": 1}, res
    assert res["maps"] == {}, res
    assert not any(k.startswith("flash_attention/pallas")
                   for k in res["selected"]), res


def test_train_step_maps_every_attention_call(four_devices):
    res = four_devices["train_step"]
    xla, mapped = res["xla"], res["interpret"]
    assert xla["maps"] == {} and mapped["skips"] == {}, res
    assert mapped["maps"].get("flash_attention/interpret", 0) >= 1, res
    assert abs(mapped["loss"] - xla["loss"]) <= 1e-5 * abs(xla["loss"]), res
    assert (abs(mapped["grad_norm"] - xla["grad_norm"])
            <= 1e-4 * xla["grad_norm"]), res
