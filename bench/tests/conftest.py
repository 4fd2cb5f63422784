"""Put the repo root (for ``bench``) and ``src`` (for the program) on the
path, and give the tests small configurations of the benchmark's models."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def toy_config(name: str) -> dict:
    """The benchmark configuration ``name`` at the program's CPU preset
    sizes (``configs.reduced``): 2 layers of width 64, vocabulary 128."""
    from repro import configs

    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    arch = configs.reduced(cfg["program_arch"])
    cfg.update(program_preset="reduced", num_hidden_layers=arch.n_layers,
               hidden_size=arch.d_model, intermediate_size=arch.d_ff,
               num_attention_heads=arch.n_heads,
               num_key_value_heads=arch.n_kv_heads, head_dim=arch.hd,
               vocab_size=arch.vocab)
    if "attention_multiplier" in cfg:
        cfg["attention_multiplier"] = arch.hd ** -0.5
    return cfg


@pytest.fixture(params=["olmo-1b", "granite-3-2b-d8"])
def toy(request):
    return toy_config(request.param)
