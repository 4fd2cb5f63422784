"""Reduce a JAX profiler trace to what the per-layer metrics read.

The profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation the chip ran.  The host plane holds the harness's own
``TraceAnnotation`` spans (``engine.step``, ``train.step``, ...).  Both
are on one clock, so an idle gap on the device can be named by the host
span that was open across it.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.IGNORECASE)

Interval = tuple[int, int]            # [start_ns, end_ns)


@dataclass
class Trace:
    """Device operations per chip as (name, start_ns, end_ns, detail),
    where detail holds the event's framework op and category (what a
    pattern for a kernel matches besides the HLO name), and the harness's
    host spans as (name, start_ns, end_ns)."""
    device_ops: list[list[tuple[str, int, int, str]]] = field(
        default_factory=list)
    host_spans: list[tuple[str, int, int]] = field(default_factory=list)

    @property
    def window(self) -> Interval:
        """From the first harness span's start to the last one's end."""
        return (min(s for _, s, _ in self.host_spans),
                max(e for _, _, e in self.host_spans))


def load(path: Path, span_names) -> Trace:
    """Read the newest ``.xplane.pb`` under ``path`` (or ``path`` itself)."""
    from jax.profiler import ProfileData

    path = Path(path)
    files = [path] if path.is_file() else sorted(path.rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    data = ProfileData.from_file(str(files[-1]))
    spans = set(span_names)
    tr = Trace()
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [(e.name, int(e.start_ns), int(e.end_ns), _detail(e))
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            tr.device_ops.append(sorted(ops, key=lambda o: o[1]))
        elif plane.name.startswith("/host:"):
            tr.host_spans += [(e.name, int(e.start_ns), int(e.end_ns))
                              for line in plane.lines for e in line.events
                              if e.name in spans]
    tr.host_spans.sort(key=lambda s: s[1])
    return tr


DETAIL_STATS = ("tf_op", "long_name", "hlo_category")


def _detail(event) -> str:
    return " ".join(str(v) for k, v in event.stats if k in DETAIL_STATS)


def union(intervals) -> list[Interval]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_ns(ops, lo: int, hi: int) -> int:
    """Time in [lo, hi) in which at least one operation ran."""
    return sum(e - s for s, e in clip(union(o[1:3] for o in ops), lo, hi))


def op_ns(ops, pattern: str) -> tuple[int, int]:
    """(total ns, count) of the operations whose name or detail matches
    ``pattern``."""
    hits = [e - s for _, s, e, _ in matching(ops, pattern)]
    return sum(hits), len(hits)


def matching(ops, pattern: str) -> list:
    """The operations whose HLO text or detail matches ``pattern``."""
    rx = re.compile(pattern)
    return [o for o in ops if rx.search(o[0]) or rx.search(o[3])]


def pallas_call(out_shape) -> str:
    """A pattern for a Pallas kernel's event by its output shape: the
    custom call the kernel compiles to carries no kernel name, so the
    logical shape (any dtype, layout suffix allowed) identifies it; a
    ``None`` dimension matches any size."""
    dims = ",".join(r"\d+" if d is None else str(d) for d in out_shape)
    return rf"= \w+\[{dims}\]\S* custom-call\(.*tpu_custom_call"


# control flow whose event spans the operations nested inside it
CONTAINER = re.compile(r"^%?(while|conditional|call)[.\d]* ")


def short_name(name: str) -> str:
    """An HLO event's instruction name (``%fusion.12 = f32[...] ...`` ->
    ``fusion.12``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def top_ops(ops, k: int = 10) -> list[list]:
    """The ``k`` operations that took most device time, in seconds, by
    instruction name; loops and calls are left out, since the operations
    nested in them are counted themselves."""
    tot: dict[str, int] = defaultdict(int)
    for name, s, e, _ in ops:
        if not CONTAINER.match(name):
            tot[short_name(name)] += e - s
    return [[n, t / 1e9] for n, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(ops, spans, lo: int, hi: int, k: int = 10) -> list[list]:
    """Idle device time in [lo, hi), summed by the innermost harness span
    open at each gap's midpoint (``"none"`` where no span was open);
    the ``k`` largest, in seconds."""
    busy = clip(union(o[1:3] for o in ops), lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    tot: dict[str, int] = defaultdict(int)
    for s, e in zip(edges[::2], edges[1::2]):
        if e <= s:
            continue
        mid = (s + e) // 2
        open_ = [(ss, name) for name, ss, se in spans if ss <= mid < se]
        tot[max(open_)[1] if open_ else "none"] += e - s
    return [[n, t / 1e9] for n, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_share(trace: "Trace") -> float | None:
    """Percent of the traced window in which no operation ran, averaged
    over the chips."""
    if trace is None or not trace.device_ops or not trace.host_spans:
        return None
    lo, hi = trace.window
    busy = [busy_ns(ops, lo, hi) for ops in trace.device_ops]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))


def exposed_collective_ns(ops, lo: int, hi: int) -> int:
    """Time in [lo, hi) spent in collective operations while no other
    operation ran on the same chip."""
    coll = union(o[1:3] for o in ops if COLLECTIVE.search(o[0]))
    comp = union(o[1:3] for o in ops if not COLLECTIVE.search(o[0]))
    exposed = 0
    for s, e in clip(coll, lo, hi):
        covered = sum(min(e, ce) - max(s, cs) for cs, ce in comp
                      if min(e, ce) > max(s, cs))
        exposed += (e - s) - covered
    return exposed
