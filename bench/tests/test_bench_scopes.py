"""The scope reduction and its three readers against a hand-made trace
whose details copy the ``op_name`` paths of a real train step."""

import importlib.util

import pytest

from bench.core import scopes as SC
from bench.core import trace as T
from bench.core.spec import ROOT

from toycells import toy_cell

STEP = "jit(train_step)/while/body/closed_call/"
FWD = STEP + "jvp(stack)/while/body/closed_call/"
BWD = STEP + "transpose(jvp(stack))/while/body/closed_call/checkpoint/"
REC = BWD + "rematted_computation/"


def op(name, s, e, path):
    # detail as the trace reduction writes it: tf_op, long_name, category
    return (f"%{name} = f32[4,32]{{1,0}} fusion()", s, e,
            f"{path} %{name} = f32[4,32]{{1,0}} fusion(), kind=kLoop "
            f"convolution")


# one chip, one traced step: a loop spans the step, a call the forward
# unit; 10 ns idle between the optimizer and the embedding's gradient
OPS = [
    op("while.1", 0, 100, STEP + "while"),
    op("closed_call.2", 0, 60, FWD + "closed_call"),
    op("fusion.3", 0, 20, FWD + "attn/dot_general"),
    op("fusion.4", 20, 30, FWD + "dynamic_slice"),
    op("fusion.5", 30, 50, FWD + "attn_out/dot_general"),
    op("fusion.6", 60, 70, REC + "attn/dot_general"),
    op("fusion.7", 70, 90, BWD + "attn/jvp()/while/body/dot_general"),
    op("fusion.8", 100, 110, "jit(train_step)/adamw/mul"),
    op("fusion.9", 120, 125,
       STEP + "transpose(jvp(embed))/scatter-add"),
]
BUSY = 115
KNOWN = {"embed", "attn", "attn_out", "lm_head"} | set(SC.OUTSIDE_GRAPH)


def test_self_time_counts_each_nested_op_once():
    own = SC.self_ns(OPS)
    # the loop keeps its 10 ns not covered by the call and the two
    # backward ops; the call its 10 ns after the forward ops
    assert own == [10, 10, 20, 10, 20, 10, 20, 10, 5]
    assert sum(own) == T.busy_ns(OPS, 0, 125) == BUSY


def test_self_time_of_identical_and_overlapping_intervals():
    ops = [op("call.1", 0, 10, ""), op("custom-call.2", 0, 10, ""),
           op("fusion.3", 5, 15, "")]
    own = SC.self_ns(ops)
    assert own == [0, 5, 10] and sum(own) == T.busy_ns(ops, 0, 15)


def test_table_splits_scopes_and_phases():
    tab = SC.table(OPS, KNOWN)
    assert tab["attn"] == {"forward": 20, "recompute": 10, "backward": 20}
    assert tab["attn_out"] == {"forward": 20, "recompute": 0, "backward": 0}
    assert tab[SC.OVERHEAD] == {"forward": 20, "recompute": 0,
                                "backward": 0}
    assert tab[SC.UNSCOPED]["forward"] == 10
    assert tab["adamw"]["forward"] == 10
    assert tab["embed"]["backward"] == 5
    assert sum(sum(p.values()) for p in tab.values()) == BUSY


def test_path_parts():
    assert SC.op_path("") == []
    assert SC.phase(SC.op_path(REC + "attn/x")) == "recompute"
    assert SC.phase(SC.op_path(BWD + "attn/x")) == "backward"
    assert SC.phase(SC.op_path(FWD + "attn/x")) == "forward"
    assert SC.row(["jit(f)", "transpose(jvp(lm_head))", "dot_general"],
                  KNOWN) == "lm_head"
    # a unit scope inside the stack is the unit's, not overhead
    assert SC.row(SC.op_path(FWD + "attn_out/x"), KNOWN) == "attn_out"


def reader(name):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def record(ops, steps=2):
    return {"cell": toy_cell("granite-3-2b-d8.train"),
            "trace": T.Trace(device_ops=[list(ops), list(ops)]),
            "traced_walls": [1.0] * steps}


def toy_pricing():
    """Three scopes of a toy graph on a chip of 1 GFLOP/s and 1 GB/s at
    full efficiency: attn 20 + max(10, 30) ns, attn_out 10 ns, embed
    40 ns (its bytes bound it)."""
    from repro.core.config import LayerConfig
    from repro.core.cost_model import CostModel
    from repro.core.device import AxisSpec, ChipSpec, MeshSpec
    from repro.core.graph import CompGraph, LayerNode, TensorSpec

    chip = ChipSpec("toy", peak_flops=1e9, hbm_bw=1e9, hbm_bytes=1e9,
                    vmem_bytes=1e6, mxu_efficiency=1.0, hbm_efficiency=1.0)
    g = CompGraph()
    out = TensorSpec.make(batch=1)
    for name, kind, flops, act in [("embed", "embed", 1, 40),
                                   ("L0.attn", "attn", 20, 0),
                                   ("L1.attn", "attn", 10, 30),
                                   ("L0.attn_out", "attn_out", 10, 0)]:
        g.add_node(LayerNode(name, kind, out, flops=flops, act_bytes=act))
    mesh = MeshSpec(axes=(AxisSpec("data", 1, 1e9),), chip=chip)
    return (g, CostModel(mesh, phase="train"),
            lambda name, node: LayerConfig.REPLICATED)


def test_predicted_by_scope_by_hand():
    assert SC.predicted_by_scope(*toy_pricing()) == pytest.approx(
        {"embed": 40e-9, "attn": 50e-9, "attn_out": 10e-9})


def test_readers_by_hand(monkeypatch):
    rec = record(OPS)
    assert reader("stack_overhead_share.train")(rec) == pytest.approx(
        100 * 20 / BUSY)
    assert reader("attention_backward_share.train")(rec) == pytest.approx(
        100 * 20 / BUSY)
    # measured a step (2 traced): attn 25 ns, attn_out 10, embed 2.5
    monkeypatch.setattr(SC, "cell_pricing", lambda cell: toy_pricing())
    want = 100 * (abs(50 - 25) + abs(10 - 10) + abs(40 - 2.5)) / (
        25 + 10 + 2.5)
    assert reader("cost_model_error.train")(rec) == pytest.approx(want)


def test_cost_model_error_prices_the_cell_graph():
    """Unpatched, the reader prices the toy cell's own graph: finite, and
    larger than 0 since the trace holds no norm or residual time."""
    got = reader("cost_model_error.train")(record(OPS))
    assert got is not None and got > 0


@pytest.mark.parametrize("name", ["stack_overhead_share.train",
                                  "attention_backward_share.train",
                                  "cost_model_error.train"])
def test_readers_are_silent_without_scopes(name):
    read = reader(name)
    assert read(dict(record(OPS), trace=None)) is None
    assert read(dict(record(OPS), trace=T.Trace())) is None
    # a program that names no scope: every op unscoped
    bare = [(n, s, e, STEP + "dot_general") for n, s, e, _ in OPS]
    assert read(record(bare)) is None
    if name == "cost_model_error.train":
        assert read(record(OPS, steps=0)) is None


def test_traced_toy_train_run_leaves_the_scope_metrics_out(capsys,
                                                           monkeypatch):
    """Through the whole harness on the CPU: the trace has no device
    plane, so the three readers find nothing and their metrics are left
    out of the result, beside ``mfu.train``."""
    import json
    import types

    from bench import run as bench_run
    from bench.core import peaks
    from bench.core.runtime import CompileCount

    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    args = types.SimpleNamespace(seed=2**33 + 7, seconds=1.0, trace=1,
                                 rate=None, control=False, fault=None)
    cell = toy_cell("granite-3-2b-d8.train")
    assert bench_run.run(cell, args, "(none)", CompileCount()) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True
    assert set(out["metrics"]) == {"mfu.train"}
    assert {m["name"] for m in cell.per_layer} >= {
        "cost_model_error.train", "attention_backward_share.train",
        "stack_overhead_share.train"}


# --------------------------------------------------------------------------- #
# reading op names from the profile itself
# --------------------------------------------------------------------------- #
def _ld(number, payload):
    """A length-delimited protobuf field."""
    if isinstance(payload, str):
        payload = payload.encode()
    out, n = bytearray([number << 3 | 2]), len(payload)
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out) + payload


def hlo_proto(ops):
    """An ``HloProto`` of one computation whose instructions are
    ``(name, op_name or None)``."""
    insts = b"".join(_ld(2, _ld(1, name) + (_ld(7, _ld(2, op)) if op else b""))
                     for name, op in ops)
    return _ld(1, _ld(3, _ld(1, "main") + insts))


def _escaped(data: bytes) -> str:
    return "".join(f"\\{b:03o}" for b in data)


def xspace(events, programs):
    """A serialized XSpace: one TPU's ``XLA Ops`` line of ``events``
    (name, offset ns, duration ns, stats dict), and the metadata plane's
    HLO of ``programs`` ({id: [(instruction, op_name)]})."""
    from jax.profiler import ProfileData

    stat_ids = {"Hlo Proto": 1, "program_id": 2, "tf_op": 3}
    smd = " ".join(f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                   for n, i in stat_ids.items())

    def stat(k, v):
        kind = "uint64_value" if isinstance(v, int) else "str_value"
        val = v if isinstance(v, int) else f'"{v}"'
        return f"stats {{ metadata_id: {stat_ids[k]} {kind}: {val} }}"

    meta = " ".join(
        f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} name: '
        f'"jit_step({p})" stats {{ metadata_id: 1 bytes_value: '
        f'"{_escaped(hlo_proto(ops))}" }} }} }}'
        for i, (p, ops) in enumerate(programs.items()))
    evs = " ".join(
        f"events {{ metadata_id: {i + 1} offset_ps: {s * 1000} "
        f"duration_ps: {d * 1000} }}" for i, (_, s, d, _) in enumerate(events))
    emd = " ".join(
        f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} name: '
        f'"%{n} = f32[4] fusion()" '
        + " ".join(stat(k, v) for k, v in st.items()) + " } }"
        for i, (n, _, _, st) in enumerate(events))
    text = (f'planes {{ id: 1 name: "/host:metadata" {meta} {smd} }} '
            f'planes {{ id: 2 name: "/device:TPU:0" lines {{ id: 1 '
            f'name: "XLA Ops" timestamp_ns: 1000 {evs} }} {emd} {smd} }}')
    return ProfileData.text_proto_to_serialized_xspace(text)


PROGRAMS = {7: [("fusion.3", FWD + "attn/dot_general"),
                ("copy.4", BWD + "attn/dynamic_slice"),
                ("copy.4.remat_uncompressed", None),
                ("fusion.5", None)],
            9: [("fusion.3", "jit(make_batch)/iota")]}


def test_hlo_op_names_of_a_compiled_module():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("attn"):
            return jnp.tanh(x @ x)

    mod = f.lower(jnp.ones((8, 8))).compile().runtime_executable(
    ).hlo_modules()[0]
    names = SC.hlo_op_names(_ld(1, mod.as_serialized_hlo_module_proto()))
    assert any(SC.row(SC.op_path(p), {"attn"}) == "attn"
               for p in names.values())
    # a copy the compiler made without metadata takes its source's name
    names = SC.hlo_op_names(hlo_proto(PROGRAMS[7]))
    assert names["copy.4.remat_uncompressed"] == BWD + "attn/dynamic_slice"
    assert names["fusion.5"] == ""


def test_device_ops_take_op_names_from_the_profile():
    data = xspace([("fusion.3", 0, 20, {"program_id": 7}),
                   ("copy.4.remat_uncompressed", 20, 10, {"program_id": 7}),
                   ("fusion.3", 40, 5, {"program_id": 9}),
                   ("fusion.6", 50, 5, {"tf_op": STEP + "adamw/mul"}),
                   ("fusion.5", 60, 5, {})],
                  PROGRAMS)
    [ops] = SC.device_ops(data)
    assert [(T.short_name(o[0]), o[1], o[2], o[3]) for o in ops] == [
        ("fusion.3", 1000, 1020, FWD + "attn/dot_general"),
        ("copy.4.remat_uncompressed", 1020, 1030, BWD + "attn/dynamic_slice"),
        ("fusion.3", 1040, 1045, "jit(make_batch)/iota"),
        ("fusion.6", 1050, 1055, STEP + "adamw/mul"),
        ("fusion.5", 1060, 1065, "")]
    # a bare trace keeps no op name: the profile is read again
    from jax.profiler import ProfileData
    plane = [p for p in ProfileData.from_serialized_xspace(data).planes
             if p.name == "/device:TPU:0"][0]
    assert all(not list(e.stats) for ln in plane.lines for e in ln.events)


def test_readers_read_the_profile_of_a_traced_run(tmp_path):
    data = xspace([("fusion.3", 0, 20, {"program_id": 7}),
                   ("copy.4.remat_uncompressed", 20, 10, {"program_id": 7}),
                   ("fusion.5", 30, 10, {"program_id": 7})],
                  PROGRAMS)
    run = tmp_path / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(data)
    rec = dict(record([]), trace_dir=str(tmp_path), traced_walls=[1.0])
    # 40 ns busy: attn backward 10, forward 20, unscoped 10
    assert reader("attention_backward_share.train")(rec) == pytest.approx(25)
    assert reader("stack_overhead_share.train")(rec) is None
