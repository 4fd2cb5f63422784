"""flash_attention_roofline.train: the least time the chip needs for the
forward flash-attention calls in the trace (from each call's logical
shape B, H, S, D, causal, at the chip's peaks), over the device time of
those calls.  A call is the Pallas kernel's custom call, found by its
output shape (B, H, S, D); with recomputation the kernel runs twice a
layer a microbatch, and every run counts.  Moves train_tokens_per_s."""

import re

from bench.core.flops import flash_attention_cost
from bench.core.peaks import least_time
from bench.core.trace import matching, pallas_call


def read(rec):
    tr = rec["trace"]
    if tr is None:
        return None
    cfg, traffic = rec["cell"].config, rec["cell"].traffic
    H, KH, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    S = traffic["seq"]
    calls = matching([o for d in tr.device_ops for o in d],
                     pallas_call((None, H, S, D)))
    if not calls:
        return None
    least = ns = 0.0
    for name, s, e, _ in calls:
        batch = int(re.search(r"= \w+\[(\d+),", name).group(1))
        least += least_time(rec["peaks"], *flash_attention_cost(
            batch, H, KH, S, D, causal=True, itemsize=4))
        ns += e - s
    return 100.0 * least / (ns / 1e9)
