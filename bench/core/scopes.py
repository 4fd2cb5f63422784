"""Attribute device time to the program's named scopes.

The program names each graph node of the layer-wise search with
``jax.named_scope`` (``models/plan.py::node_scope``: ``embed``, every key
of ``sublayer_keys``, ``final_norm``, ``lm_head``), and three scopes
beside them: ``stack`` (the scan over units), ``adamw`` and
``accumulate`` (the microbatch gradient sum).  JAX carries the scope path
into each HLO instruction's ``op_name``.  ``trace.load`` keeps no op name
of a TPU's events (their stats sit in the event metadata, which
``ProfileData`` does not show), so this module reads the profile itself:
each program's optimized HLO, which the profiler keeps in its metadata
plane, gives every instruction's ``op_name``.  On a train step a path
reads, for one ``attn`` op in each phase:

    jit(train_step)/.../jvp(stack)/while/body/closed_call/attn/dot_general
    .../transpose(jvp(stack))/.../checkpoint/rematted_computation/attn/...
    .../transpose(jvp(stack))/.../checkpoint/attn/dot_general

so a segment is a scope with the transforms it was traced under wrapped
round it, ``rematted_computation`` marks the recompute of a checkpointed
body, and ``transpose(`` the backward pass.  Ops outside differentiation
(the optimizer, the gradient sum) count as forward.
"""

from __future__ import annotations

import functools
import re
import sys
from collections import defaultdict
from pathlib import Path

from bench.core.trace import (DEVICE_PLANE, OPS_LINE, Trace, busy_ns,
                              short_name)

PHASES = ("forward", "recompute", "backward")
# the program's scopes that are not graph nodes
STACK = "stack"
OUTSIDE_GRAPH = (STACK, "adamw", "accumulate")
# the rows of the table besides the scopes
OVERHEAD = "stack overhead"       # under ``stack``, outside every unit scope
UNSCOPED = "unscoped"

_SEGMENT = re.compile(r"^(?:[\w.-]+\()*([^()]*)\)*$")


def _scope_name(segment: str) -> str:
    m = _SEGMENT.match(segment)
    return m.group(1) if m else segment


def op_path(detail: str) -> list[str]:
    """The ``op_name`` path in an op's detail (the token with the most
    ``/``), split into its segments; empty where the detail has none."""
    tokens = [t for t in detail.split() if "/" in t]
    if not tokens:
        return []
    return max(tokens, key=lambda t: t.count("/")).split("/")


def phase(path: list[str]) -> str:
    if "rematted_computation" in path:
        return "recompute"
    if any(s.startswith("transpose(") for s in path):
        return "backward"
    return "forward"


def row(path: list[str], scopes) -> str:
    """The innermost of ``scopes`` on the path (with the transforms
    round each segment taken off); ``stack`` alone is the stack's
    overhead."""
    inner = [n for n in map(_scope_name, path) if n in scopes]
    if not inner:
        return UNSCOPED
    return OVERHEAD if inner[-1] == STACK else inner[-1]


def self_ns(ops) -> list[int]:
    """Each op's duration less the part of it that the ops nested inside
    it on the same line cover: a loop or a call spans the ops it runs,
    whatever its name, so nesting and not the name decides."""
    own = [e - s for _, s, e, _ in ops]
    open_: list[int] = []
    for i in sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2])):
        s, e = ops[i][1], ops[i][2]
        while open_ and ops[open_[-1]][2] <= s:
            open_.pop()
        if open_:
            parent = open_[-1]
            own[parent] -= min(e, ops[parent][2]) - s
        open_.append(i)
    return own


def table(ops, scopes) -> dict[str, dict[str, int]]:
    """Self time in ns of one chip's ops, by row (innermost scope,
    ``stack overhead`` or ``unscoped``) and phase."""
    out: dict[str, dict[str, int]] = defaultdict(lambda: dict.fromkeys(
        PHASES, 0))
    for op, ns in zip(ops, self_ns(ops)):
        path = op_path(op[3])
        out[row(path, scopes)][phase(path)] += ns
    return dict(out)


def trace_table(trace, scopes):
    """(rows, busy): the table in seconds and the busy device time, each
    averaged over the chips; ``None`` without a trace."""
    if trace is None or not trace.device_ops:
        return None
    n = len(trace.device_ops)
    rows: dict[str, dict[str, float]] = defaultdict(lambda: dict.fromkeys(
        PHASES, 0.0))
    busy = 0.0
    for ops in trace.device_ops:
        for r, by_phase in table(ops, scopes).items():
            for p, ns in by_phase.items():
                rows[r][p] += ns / n / 1e9
        if ops:
            busy += busy_ns(ops, ops[0][1], max(o[2] for o in ops)) / n / 1e9
    return dict(rows), busy


# --------------------------------------------------------------------------- #
# op names from the profile: each program's HLO, kept in the metadata plane
# --------------------------------------------------------------------------- #
def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        out |= (b & 0x7F) << shift
        i, shift = i + 1, shift + 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of each field of a serialized protobuf
    message: a varint as an int, anything length-delimited as a
    memoryview of its bytes; fixed-width fields are skipped."""
    buf, i = memoryview(buf), 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {kind} not read here")
        yield key >> 3, value


def _first(buf, number: int, default=None):
    return next((v for n, v in _fields(buf) if n == number), default)


def _name(msg) -> str:
    """Field 2: the name of a plane, a line, an event or stat metadata."""
    return bytes(_first(msg, 2, b"")).decode(errors="replace")


def _map_values(msg, number: int) -> list:
    """The values of map field ``number`` (entries: key 1, value 2)."""
    return [_first(entry, 2, b"") for n, entry in _fields(msg) if n == number]


def _stats(msg, number: int, names: dict) -> dict:
    """{stat name: value} of the ``XStat`` fields ``number`` of ``msg``
    (metadata_id 1; uint64 3, int64 4, str 5, bytes 6, ref 7 to a stat
    name)."""
    out = {}
    for n, stat in _fields(msg):
        if n != number:
            continue
        key = value = None
        for n2, v in _fields(stat):
            if n2 == 1:
                key = names.get(v)
            elif n2 in (3, 4, 6):
                value = v
            elif n2 == 5:
                value = bytes(v).decode(errors="replace")
            elif n2 == 7:
                value = names.get(v)
        out[key] = value
    return out


def hlo_op_names(hlo_proto) -> dict[str, str]:
    """Instruction name -> ``op_name`` of every instruction of a serialized
    ``HloProto`` (module 1 > computations 3 > instructions 2 > name 1,
    metadata 7 > op_name 2).  A copy the compiler made without metadata
    (``<name>.remat_uncompressed``, ``<name>.clone``) takes the op_name of
    the instruction it copies."""
    names: dict[str, str] = {}
    for n, comp in _fields(_first(hlo_proto, 1, b"")):
        for n2, inst in _fields(comp) if n == 3 else ():
            if n2 == 2:
                meta = _first(inst, 7)
                names[bytes(_first(inst, 1, b"")).decode()] = (
                    "" if meta is None else _name(meta))
    for name, op_name in names.items():
        base = name
        while not op_name and re.search(r"\.[^.\d][^.]*$", base):
            base = base.rsplit(".", 1)[0]
            op_name = names.get(base, "")
        names[name] = op_name
    return names


def device_ops(xspace) -> list[list[tuple[str, int, int, str]]]:
    """Each chip's ``XLA Ops`` events as (name, start_ns, end_ns,
    op_name), read from a serialized ``XSpace``: planes 1 > {name 2, lines
    3 > {name 2, timestamp_ns 3, events 4 > {metadata_id 1, offset_ps 2,
    duration_ps 3, stats 4}}, event_metadata 4 > {id 1, name 2, stats 5},
    stat_metadata 5 > {id 1, name 2}}.  The op_name is the event's
    ``tf_op`` stat where it has one, else its instruction's in the
    program's HLO: the metadata plane holds each program's as an ``Hlo
    Proto`` stat of event metadata named ``<module>(<program id>)``; an
    event without a ``program_id`` takes the largest program that has
    its instruction."""
    planes = [p for n, p in _fields(xspace) if n == 1]
    programs: dict[str, dict[str, str]] = {}
    for plane in planes:
        if _name(plane) != "/host:metadata":
            continue
        names = {_first(md, 1, 0): _name(md) for md in _map_values(plane, 5)}
        for md in _map_values(plane, 4):
            program = re.search(r"\((\d+)\)$", _name(md))
            hlo = _stats(md, 5, names).get("Hlo Proto")
            if program and hlo is not None:
                programs[program.group(1)] = hlo_op_names(hlo)
    merged: dict[str, str] = {}
    for names in sorted(programs.values(), key=len):
        merged.update(names)
    chips = []
    for plane in planes:
        if not DEVICE_PLANE.match(_name(plane)):
            continue
        names = {_first(md, 1, 0): _name(md) for md in _map_values(plane, 5)}
        metas = {_first(md, 1, 0): (_name(md), _stats(md, 5, names))
                 for md in _map_values(plane, 4)}
        ops = []
        for n, line in _fields(plane):
            if n != 3 or _name(line) != OPS_LINE:
                continue
            t0 = _first(line, 3, 0)
            for n2, ev in _fields(line):
                if n2 != 4:
                    continue
                f = dict(_fields(ev))
                name, st = metas.get(f.get(1, 0), ("", {}))
                st = {**st, **_stats(ev, 4, names)}
                op_name = st.get("tf_op") or programs.get(
                    str(st.get("program_id")), merged).get(
                    short_name(name), "")
                start = t0 + f.get(2, 0) // 1000
                ops.append((name, start, start + f.get(3, 0) // 1000,
                            op_name))
        chips.append(sorted(ops, key=lambda o: o[1]))
    return chips


@functools.lru_cache(maxsize=1)
def _profile_ops(path: str):
    """``device_ops`` of one profile file, read once for all readers."""
    return device_ops(Path(path).read_bytes())


def scoped_trace(rec):
    """The record's device ops with each op's detail its ``op_name``: read
    from the profile itself where the record names one (the reduction in
    ``trace.py`` keeps no op name of a TPU's events), else the record's
    trace as it is."""
    files = (sorted(Path(rec["trace_dir"]).rglob("*.xplane.pb"))
             if rec.get("trace_dir") else [])
    if not files:
        return rec.get("trace")
    try:
        return Trace(device_ops=_profile_ops(str(files[-1])))
    except (ValueError, IndexError) as e:   # a profile this reader misreads
        print(f"bench: no op names read from {files[-1]}: {e!r}",
              file=sys.stderr)
        return rec.get("trace")


# --------------------------------------------------------------------------- #
# the cell's graph, as the plan search priced it
# --------------------------------------------------------------------------- #
def cell_graph(cell):
    """(arch, graph): the program's arch for the cell and its exported
    train graph at the cell's sequence and batch."""
    from repro.models.graph_export import export_graph, phase_shape

    from bench.core.spec import program_arch

    arch = program_arch(cell.config)
    tr = cell.traffic
    return arch, export_graph(arch, phase_shape(
        "train", seq_len=tr["seq"], batch=tr["batch"]))


def node_scope(name: str) -> str:
    """The scope a graph node's ops run under: its key (``L3.attn`` ->
    ``attn``)."""
    return name.rsplit(".", 1)[-1]


def cell_pricing(cell):
    """(graph, cost model, config_of) for a training cell: the cell's
    graph, the program's cost model for the cell's host mesh
    (``launch.mesh.host_mesh``, priced for the chip in use), and
    ``config_of(name, node)``, the config each node gets from the plan
    that ``bench/drivers/train.py`` resolves on that mesh."""
    from repro.core.cost_model import CostModel
    from repro.launch.mesh import host_mesh
    from repro.plans import resolve_plan

    tr = cell.traffic
    arch, graph = cell_graph(cell)
    _, mesh = host_mesh(cell.chips)
    plan = resolve_plan(arch, mesh if cell.chips > 1 else None,
                        phases=("train",), strategy=tr["strategy"],
                        train_seq=tr["seq"], train_batch=tr["batch"],
                        log=lambda *a, **k: None).plan_for("train")

    def config_of(name, node):
        if "." not in name:                   # embed, final_norm, lm_head
            return getattr(plan, name)
        layer, key = name.split(".")
        seg = next(s for s in plan.segments
                   if s.start <= node.extra["unit"] < s.end)
        return seg.plan[int(layer[1:]) % arch.period][key]

    return graph, CostModel(mesh, phase="train"), config_of


def predicted_by_scope(graph, cost_model, config_of) -> dict[str, float]:
    """Seconds a step the cost model gives each scope: the sum of
    ``roofline_time`` over the graph nodes under it."""
    out: dict[str, float] = defaultdict(float)
    for name, node in graph.nodes.items():
        out[node_scope(name)] += cost_model.roofline_time(
            node, config_of(name, node))
    return dict(out)


def error_share(predicted: dict[str, float],
                measured: dict[str, float]) -> float:
    """100 x the sum over scopes of |predicted - measured|, over the sum
    of measured."""
    return 100.0 * sum(abs(p - measured.get(s, 0.0))
                       for s, p in predicted.items()) / sum(
        measured.get(s, 0.0) for s in predicted)


def rec_table(rec):
    """The scope table and busy time of a reader's record (see
    ``trace_table``), with the cell's graph nodes and the program's other
    scopes known; ``None`` where the record has no trace, or its trace
    none of the program's scopes (a program without them)."""
    trace = scoped_trace(rec)
    if trace is None:
        return None
    _, graph = cell_graph(rec["cell"])
    scopes = {node_scope(n) for n in graph.nodes} | set(OUTSIDE_GRAPH)
    got = trace_table(trace, scopes)
    if got is None or set(got[0]) <= {UNSCOPED} or got[1] <= 0:
        return None
    return got
