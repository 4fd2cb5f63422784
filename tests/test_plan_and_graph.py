"""Strategy -> ModelPlan realization and graph-export invariants."""

import dataclasses

import pytest

from repro import configs as C
from repro.core import (LayerConfig, find_strategy, single_pod_mesh_spec,
                        uniform_strategy)
from repro.models import strategy_to_plan, uniform_plan
from repro.models.arch import SHAPES
from repro.models.graph_export import P_BYTES, export_graph
from repro.models.plan import sublayer_keys


@pytest.mark.parametrize("name", C.ALL_ARCHS)
@pytest.mark.parametrize("shape_name", ["train_4k", "decode_32k"])
def test_graph_exports_and_reduces(name, shape_name):
    arch = C.get(name)
    shape = SHAPES[shape_name]
    if not arch.supports_shape(shape):
        pytest.skip("assigned skip")
    g = export_graph(arch, shape)
    g.validate_dag()
    # every non-source node reachable; flops non-negative; param bytes sane
    assert g.num_edges >= g.num_nodes - 2
    total_params = sum(n.param_bytes for n in g.nodes.values())
    expected = arch.param_count()["total"] * P_BYTES  # f32
    assert total_params == pytest.approx(expected, rel=0.35)
    # strategy search reduces the graph completely
    mesh = single_pod_mesh_spec(2, 2)
    s = find_strategy(g, mesh, training=shape.kind == "train")
    assert s.meta["stats"].final_nodes <= 2


@pytest.mark.parametrize("name", C.ALL_ARCHS)
def test_strategy_to_plan_covers_every_sublayer(name):
    arch = C.get(name)
    g = export_graph(arch, SHAPES["train_4k"])
    mesh = single_pod_mesh_spec(2, 2)
    s = find_strategy(g, mesh, training=True)
    plan = strategy_to_plan(s, arch)
    n_units = sum(seg.n_units for seg in plan.segments)
    assert n_units == arch.n_units
    for seg in plan.segments:
        for j, spec in enumerate(arch.pattern):
            for key in sublayer_keys(spec):
                assert key in seg.plan[j], (name, j, key)
    if arch.enc_layers:
        assert sum(s_.n_units for s_ in plan.enc_segments) == arch.enc_layers
    # every graph node assignment must surface in the plan or the heads
    assert plan.embed == s.assignment["embed"]
    assert plan.lm_head == s.assignment["lm_head"]


def test_segments_group_identical_unit_plans():
    arch = C.get("llama3_2_1b")
    g = export_graph(arch, SHAPES["train_4k"])
    # uniform strategy -> single segment
    s = uniform_strategy(g, lambda n: LayerConfig.make(batch=("data",)))
    plan = strategy_to_plan(s, arch)
    assert len(plan.segments) == 1
    assert plan.segments[0].n_units == arch.n_units
    # perturb one middle layer -> three segments
    s.assignment["L7.attn"] = LayerConfig.make(heads=("model",))
    plan = strategy_to_plan(s, arch)
    assert len(plan.segments) == 3
    assert [g.n_units for g in plan.segments] == [7, 1, 8]


def test_decode_graph_uses_cache_dims():
    arch = C.get("phi3_5_moe_42b")
    g = export_graph(arch, SHAPES["decode_32k"])
    attn = g.nodes["L0.attn"]
    assert attn.extra["decode"] is True
    # decode heads capped at KV heads (cache is the dominant tensor)
    assert attn.extra["dim_sizes"]["heads"] == arch.n_kv_heads
    assert attn.extra["kv_bytes"] > 0
    # train graph is not capped
    gt = export_graph(arch, SHAPES["train_4k"])
    assert gt.nodes["L0.attn"].extra["dim_sizes"]["heads"] == arch.n_heads


def test_encdec_graph_has_cross_attention_chain():
    arch = C.get("seamless_m4t_v2")
    g = export_graph(arch, SHAPES["train_4k"])
    assert "enc.L0.attn" in g.nodes
    assert "dec.L0.xattn" in g.nodes
    # decoder entry joins token embeddings and encoder memory
    entry_in = {e.src for e in g.in_edges("dec_entry")}
    assert "embed" in entry_in and "enc_norm" in entry_in
