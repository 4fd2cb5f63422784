"""Find a cell in ``BENCHMARK.json`` and load what it names.

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``).  The configuration holds
the model's numbers as run; ``program_arch`` builds the program's own
``ArchConfig`` from them and has the configuration's family refuse one
whose shape disagrees.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass
from pathlib import Path

from bench.reference import family

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[dict, ...]   # the metrics this cell reports
    per_layer: tuple[dict, ...]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = load_traffic(w["traffic"], root)
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=tuple(m for m in spec["end_to_end"] if _reports(m, name)),
        per_layer=tuple(m for m in spec["per_layer"] if _reports(m, name)))


def load_traffic(name: str, root: Path = ROOT) -> dict:
    return json.loads((root / "bench" / "traffic" / f"{name}.json").read_text())


def program_arch(config: dict):
    """The program's ``ArchConfig`` for this configuration: the arch the
    program registers under ``program_arch`` (its small CPU preset where
    ``program_preset`` is ``"reduced"``, as the tests run it), cut to the
    configuration's depth.  The configuration's family refuses it where
    any other number or the block disagrees (``check_arch``)."""
    from repro import configs

    get = (configs.reduced if config.get("program_preset") == "reduced"
           else configs.get)
    arch = get(config["program_arch"])
    arch = dataclasses.replace(arch, n_layers=config["num_hidden_layers"])
    family(config).check_arch(config, arch)
    return arch
