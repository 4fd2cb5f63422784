"""Find a cell in ``BENCHMARK.json`` and load what it names.

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``).  The configuration holds
the model's numbers as run; ``program_arch`` builds the program's own
``ArchConfig`` from them and refuses one whose shape disagrees.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass
from pathlib import Path

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


# the configuration's key -> the program's ArchConfig field
_ARCH_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "hd", "intermediate_size": "d_ff", "vocab_size": "vocab",
    "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
}


def program_arch(config: dict):
    """The program's ``ArchConfig`` for this configuration: the arch the
    program registers under ``program_arch`` (its small CPU preset where
    ``program_preset`` is ``"reduced"``, as the tests run it), cut to the
    configuration's depth.  Every other number has to agree already."""
    from repro import configs
    from repro.models.arch import LayerSpec

    get = (configs.reduced if config.get("program_preset") == "reduced"
           else configs.get)
    arch = get(config["program_arch"])
    arch = dataclasses.replace(arch, n_layers=config["num_hidden_layers"])
    wrong = {k: (config[k], getattr(arch, f))
             for k, f in _ARCH_FIELDS.items() if config[k] != getattr(arch, f)}
    if arch.nonparam_norm == config["norm_with_affine"]:
        wrong["norm_with_affine"] = (config["norm_with_affine"],
                                     not arch.nonparam_norm)
    if (set(arch.pattern) != {LayerSpec("attn", "dense")} or arch.qkv_bias
            or arch.qk_norm or arch.n_experts or arch.frontend):
        wrong["block"] = ("dense decoder", arch.pattern)
    if wrong:
        raise ValueError(f"{config['name']}: the program's arch "
                         f"{config['program_arch']!r} differs from the "
                         f"configuration (config, program): {wrong}")
    return arch
