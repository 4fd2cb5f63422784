"""Small cells for driving the whole harness on the CPU: a real cell of
BENCHMARK.json with its configuration cut to the program's CPU preset and
its traffic to a few short requests or rows.  The serving cell, which
BENCHMARK.json does not hold yet, is made from its files in the same
way, with its metrics from ``data/serve_metrics.json``."""

import dataclasses
import json
from pathlib import Path

from bench.core.spec import Cell, load_cell, load_traffic

from conftest import toy_config

TOY_TRAFFIC = {
    "serve": {"rate": 6.0, "ramp_s": 1.0, "granule": 8,
              "prompt": {"median": 24, "sigma": 0.6, "min": 8, "max": 48},
              "output": {"median": 12, "sigma": 0.5, "min": 4, "max": 24},
              "shared_prefix": {"tokens": 16, "share": 0.6},
              "slots": 4, "kv_block_size": 16, "prefill_chunk_tokens": 32,
              "q_chunk": 16, "trace_seconds": 1, "check": {"requests": 4}},
    "train": {"seq": 32, "batch": 4, "q_chunk": 16, "loss_chunk": 16,
              "trace_after_steps": 1, "trace_steps": 2, "reference_rows": 2},
}


SERVE_CELL = "olmo-1b.chat"


def serve_cell() -> Cell:
    m = json.loads((Path(__file__).parent / "data" /
                    "serve_metrics.json").read_text())
    cfg = json.loads((Path(__file__).parents[1] / "configs" /
                      "olmo-1b.json").read_text())
    return Cell(name=SERVE_CELL, chips=1, config=cfg,
                traffic=load_traffic("chat"),
                end_to_end=tuple(m["end_to_end"]),
                per_layer=tuple(m["per_layer"]))


def toy_cell(name: str):
    cell = serve_cell() if name == SERVE_CELL else load_cell(name)
    traffic = dict(cell.traffic, **TOY_TRAFFIC[cell.traffic["driver"]])
    return dataclasses.replace(cell, config=toy_config(cell.config["name"]),
                               traffic=traffic)
