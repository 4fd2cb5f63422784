"""Every layer of the compiled step carries the name of the graph node the
plan search priced it as.

The model wraps each graph node in ``jax.named_scope`` (named by its plan
key, ``models/plan.py::node_scope``), which JAX carries into every HLO
op's ``op_name``: a device trace then attributes each op to its layer.
Each case compiles one step of a small arch on the CPU and reads the
``op_name`` of every instruction of the optimized HLO, fused ones too:
every matrix product and custom call lies under one of the arch's graph
nodes, and the unit scopes found are exactly the arch's sublayer keys.
The cases cover each set of sublayer keys (dense attention, Mamba with
attention and MoE, RWKV, MoE) in the train step, prefill and decode.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from repro import configs as C
from repro.models import lm
from repro.models.graph_export import export_graph, phase_shape
from repro.models.plan import sublayer_keys
from repro.optim import adamw_init
from repro.train import TrainConfig, make_train_step

B, S = 4, 32
OP = re.compile(r"= \S+ ([a-z-]+)\(.*op_name=\"([^\"]*)\"")
# a path segment is a scope with the transforms it was traced under
# round it: "transpose(jvp(stack))" -> "stack"
SEGMENT = re.compile(r"^(?:[\w.-]+\()*([^()]*)\)*$")


def _scopes(op_name: str) -> list[str]:
    return [m.group(1) if m else s for s in op_name.split("/")
            for m in [SEGMENT.match(s)]]


def _train(arch, params, ids):
    step = make_train_step(arch, None, TrainConfig(
        microbatches=2, q_chunk=16, loss_chunk=16, time_chunk=16))
    opt = jax.eval_shape(adamw_init, params)
    return jax.jit(step).lower(params, opt, {"tokens": ids(B, S)})


def _prefill(arch, params, ids):
    cache = jax.eval_shape(lambda: lm.init_cache(arch, B, S, jnp.float32))
    return jax.jit(lambda p, b, c: lm.prefill(p, b, c, arch, q_chunk=16,
                                              time_chunk=16)).lower(
        params, {"tokens": ids(B, S)}, cache)


def _decode(arch, params, ids):
    cache = jax.eval_shape(lambda: lm.init_cache(arch, B, S, jnp.float32))
    return jax.jit(lambda p, t, c, pos: lm.decode_step(p, t, c, pos, arch)
                   ).lower(params, ids(B, 1), cache, ids(B))


STEPS = {"train": _train, "prefill": _prefill, "decode": _decode}


@pytest.mark.parametrize("step", sorted(STEPS))
@pytest.mark.parametrize("name", ["granite_3_2b", "jamba_1_5_large",
                                  "rwkv6_1b6", "olmoe_1b_7b"])
def test_every_layer_is_scoped_by_its_graph_node(name, step):
    arch = C.reduced(name)
    params = jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0), arch))
    ids = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    hlo = STEPS[step](arch, params, ids).compile().as_text()

    graph = export_graph(arch, phase_shape(step, seq_len=S, batch=B))
    nodes = {n.rsplit(".", 1)[-1] for n in graph.nodes}
    keys = {k for spec in arch.pattern for k in sublayer_keys(spec)}
    ops = OP.findall(hlo)
    assert ops
    unscoped = [(op, path) for op, path in ops
                if op in ("dot", "custom-call")
                and not nodes & set(_scopes(path))]
    assert not unscoped, unscoped[:5]
    found = {s for _, path in ops for s in _scopes(path)} & keys
    assert found == keys
