"""cost_model_error.train: how far the plan search's prices are from
what the chip charges, per layer (the paper's Table 4, measured): 100 x
the sum over the scopes of the cell's train graph (``embed``, each
sublayer key, ``final_norm``, ``lm_head``) of |predicted - measured|,
over the sum measured.  Predicted: the sum over the scope's graph nodes
of ``CostModel(phase="train").roofline_time`` under the plan that
``bench/drivers/train.py`` resolves on the cell's mesh.  Measured: the
scope's self time in every phase (profiler trace, averaged over chips)
per traced step.  Moves train_tokens_per_s."""

from bench.core import scopes


def read(rec):
    got = scopes.rec_table(rec)
    steps = len(rec.get("traced_walls") or ())
    if got is None or not steps:
        return None
    predicted = scopes.predicted_by_scope(*scopes.cell_pricing(rec["cell"]))
    measured = {s: sum(got[0][s].values()) / steps
                for s in predicted if s in got[0]}
    if not any(measured.values()):
        return None
    return scopes.error_share(predicted, measured)
