"""paged_decode_roofline.serve: the least time the chip needs for the
single-token paged-decode attention of the traced steps of width 1 (each
decoding slot reads its live KV length, keys and values at the pool's
width, on every layer), over the device time of the Pallas paged-decode
kernel's calls in the trace, found by their output shape (slots, KV
heads, group, head_dim).  Only a width-1 step (no prompt tokens) runs
that kernel; a step that carries a prompt chunk attends through the
XLA gather of the mixed step, whose work is in neither side.  Moves
itl_p95_ms."""

from bench.core.flops import paged_decode_cost
from bench.core.peaks import least_time
from bench.core.trace import op_ns, pallas_call

ITEMSIZE = {"float32": 4, "bfloat16": 2}


def read(rec):
    tr = rec["trace"]
    if tr is None or rec.get("trace_window") is None:
        return None
    cfg, traffic = rec["cell"].config, rec["cell"].traffic
    H, KH, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    ns, calls = op_ns([o for d in tr.device_ops for o in d],
                      pallas_call((traffic["slots"], KH, H // KH, D)))
    lo, hi = rec["trace_window"]
    steps = [s for s in rec["steps"] if s["ts"] >= lo and s["te"] <= hi
             and s["width"] == 1 and s["decode_kv_lens"]]
    if not calls or not steps:
        return None
    least = sum(least_time(rec["peaks"], *paged_decode_cost(
        cfg, s["decode_kv_lens"], ITEMSIZE[cfg["dtype"]])) for s in steps)
    return 100.0 * least / (ns / 1e9)
