"""mfu.train: model FLOPs of the traced training steps (forward and
backward, 6·N per token plus the causal attention triangle; recompute not
counted) per second of their wall time, over the chips' bf16 peak.
Moves train_tokens_per_s."""

from bench.core.flops import train_flops_per_token


def read(rec):
    walls = rec.get("traced_walls")
    if not walls:
        return None
    cell = rec["cell"]
    tokens_per_s = rec["tokens_per_step"] * len(walls) / sum(walls)
    per_token = train_flops_per_token(cell.config, cell.traffic["seq"])
    return 100.0 * per_token * tokens_per_s / (cell.chips * rec["peaks"]["flops"])
