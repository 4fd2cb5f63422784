"""attention_backward_share.train: percent of busy device time spent in
the ``attn`` scope's backward phase: the attention backward (the blocked
Pallas kernels ``flash_attention_bwd_dq`` and ``_dkv``, or the reference
VJP for a shape with no block), with the q/k/v products' gradients (self
time of each op, profiler trace, averaged over chips; the recompute of
the forward is not counted).  Moves train_tokens_per_s."""

from bench.core import scopes


def read(rec):
    got = scopes.rec_table(rec)
    if got is None or "attn" not in got[0]:
        return None
    rows, busy = got
    return 100.0 * rows["attn"]["backward"] / busy
