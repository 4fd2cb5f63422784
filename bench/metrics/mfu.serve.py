"""mfu.serve: model FLOPs of the prompt and output tokens that the traced
window's engine steps processed (2·N per token plus attention over each
token's context), over those steps' host wall time times the chips' bf16
peak.  Moves itl_p95_ms."""


def read(rec):
    steps = _traced_steps(rec)
    if not steps:
        return None
    wall = sum(s["te"] - s["ts"] for s in steps)
    work = sum(s["flops"] for s in steps)
    return 100.0 * work / (wall * rec["cell"].chips * rec["peaks"]["flops"])


def _traced_steps(rec):
    if rec.get("trace_window") is None:
        return []
    lo, hi = rec["trace_window"]
    return [s for s in rec["steps"] if s["ts"] >= lo and s["te"] <= hi]
