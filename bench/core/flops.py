"""Operations and bytes from logical shapes, for MFU and roofline shares.

The block's own counts come from the configuration's family
(``bench/reference/<family>.py``: the parameters that multiply every
token, the attention layers' shape and their score and sum operations,
counted by hand from the block's equations); the sums here are the same
for every block (copied in spirit from the repo's
``benchmarks/roofline.py::model_flops``: 6·N per trained token plus
causal attention, which 6·N leaves out).  Recomputation is never
counted: these are the operations the model needs, not the ones a
schedule happens to run.
"""

from __future__ import annotations

from bench.reference import family


def matmul_params(config: dict) -> int:
    """Parameters that multiply every token (the family's count)."""
    return family(config).matmul_params(config)


def attention_flops(config: dict, q_tokens: int, kv_len: int,
                    causal: bool) -> float:
    """Forward attention operations of one sequence over every attention
    layer (the family's count)."""
    return family(config).attention_flops(config, q_tokens, kv_len, causal)


def train_flops_per_token(config: dict, seq: int) -> float:
    """Forward and backward (3x forward) of one token of a ``seq``-long
    causal row: 6·N plus the attention triangle."""
    attn = 3 * attention_flops(config, seq, seq, causal=True) / seq
    return 6.0 * matmul_params(config) + attn


def serve_flops(config: dict, pos: int, tokens: int) -> float:
    """Forward operations of ``tokens`` new tokens of one request whose
    cache already holds ``pos`` tokens."""
    return (2.0 * matmul_params(config) * tokens
            + attention_flops(config, tokens, pos + tokens, causal=True))


def flash_attention_cost(batch: int, heads: int, kv_heads: int, seq: int,
                         head_dim: int, causal: bool,
                         itemsize: int) -> tuple[float, float]:
    """(operations, bytes) of one forward flash-attention call: the
    triangle's scores and sums; reading q, k, v and writing o once."""
    pairs = seq * (seq + 1) / 2 if causal else seq * seq
    flops = 4.0 * batch * heads * head_dim * pairs
    nbytes = batch * seq * head_dim * (2 * heads + 2 * kv_heads) * itemsize
    return flops, nbytes


def paged_decode_cost(config: dict, kv_lens, itemsize: int) -> tuple[float, float]:
    """(operations, bytes) of one single-token paged-decode attention
    pass over every attention layer: each live slot reads its ``kv_len``
    keys and values at the pool's width and its query, and writes its
    output."""
    n, h, kh, hd = family(config).attention_shape(config)
    total = float(sum(kv_lens))
    flops = 4.0 * h * hd * total * n
    nbytes = n * (2 * kh * hd * total + 2 * h * hd * len(kv_lens)) * itemsize
    return flops, nbytes
