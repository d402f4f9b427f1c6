"""Where the benchmark's data files are and what must be in them.

``BENCHMARK.json`` is the one place that says which cells there are: each
workload's configuration, traffic mix, chips and reason, which end-to-end
metrics a cell reports and which per-layer metrics are read in it. The
files it names hold the rest, and are found by those names:
``cells/<workload>.json`` (what only a run needs: the fixed rate, the
latency limits, the drain time), the configuration's ``file``,
``traffic/<traffic>.json`` (a generator module and its parameters),
``golden/<config>.json`` and ``layer_metrics/<metric, dots as
underscores>.py``. The stand-in cells of the dry run have a manifest of the
same form, ``testdata/BENCHMARK.json``, and their files under ``testdata/``.
Adding a cell is adding files and entries; no code names one.
"""

from __future__ import annotations

import json
import zlib
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

CHECKOUT = Path(__file__).resolve().parents[2]
BENCH = CHECKOUT / "benchmark"
TESTDATA = BENCH / "testdata"

# the served tokenizer is the program's ByteTokenizer: one token per byte,
# token id = byte + 4. Prompts are lower-case ASCII, so length in bytes is
# length in tokens.
BYTE_TOKEN_OFFSET = 4

# published config.json key -> attribute of the program's ModelConfig. What
# the file says and what the loaded engine holds must agree key by key.
PUBLISHED_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_norm_eps",
    "sliding_window": "sliding_window",
    "tie_word_embeddings": "tie_word_embeddings",
    "attention_bias": "attention_bias",
    "num_local_experts": "num_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "max_position_embeddings": "max_position_embeddings",
}


class SpecError(ValueError):
    """A data file is missing or does not say what it must."""


def _read(path: Path) -> Dict[str, Any]:
    if not path.is_file():
        raise SpecError(f"no such file: {path.relative_to(CHECKOUT)}")
    with open(path) as f:
        data = json.load(f)
    if not isinstance(data, dict):
        raise SpecError(f"{path.relative_to(CHECKOUT)} is not a JSON object")
    return data


def _need(data: Dict[str, Any], keys: List[str], what: str) -> None:
    missing = [k for k in keys if k not in data]
    if missing:
        raise SpecError(f"{what} lacks {missing}")


def _applies(entry: Dict[str, Any], cell: str) -> bool:
    """A metric with no ``workloads`` list is reported in every cell."""
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str) -> Dict[str, Any]:
    """The cell as a run needs it: its entry in the manifest that lists it
    (``BENCHMARK.json``, else the stand-ins'), its own file, its
    configuration and traffic files, and the metrics it reports."""
    for base in (BENCH, TESTDATA):
        path = (CHECKOUT if base == BENCH else base) / "BENCHMARK.json"
        manifest = _read(path) if path.is_file() else {}
        entry = next((w for w in manifest.get("workloads", [])
                      if w["name"] == name), None)
        if entry is not None:
            break
    else:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    cell = _read(base / "cells" / f"{name}.json")
    _need(cell, ["drain_s"], f"cell {name}")
    config = next((c for c in manifest["configs"]
                   if c["name"] == entry["config"]), None)
    if config is None:
        raise SpecError(f"{name}: no configuration {entry['config']!r} "
                        "under configs")
    cell.update(
        name=name, chips=entry["chips"], why=entry["why"],
        end_to_end={m["name"]: m for m in manifest["end_to_end"]
                    if _applies(m, name)},
        per_layer=[m for m in manifest["per_layer"] if _applies(m, name)],
    )
    cell["_stand_in"] = base == TESTDATA
    cell["_path"] = str(base / "cells" / f"{name}.json")
    cell["_golden"] = base / "golden" / f"{entry['config']}.json"
    cell["_config"] = load_config(CHECKOUT / config["file"])
    cell["_traffic"] = load_traffic(base / "traffic"
                                    / f"{entry['traffic']}.json")
    return cell


def load_config(path: Path) -> Dict[str, Any]:
    cfg = _read(path)
    _need(cfg, ["name", "source", "reduced", "registry_model", "weights_seed",
                "worker_engine", "max_concurrent_jobs", "serving_geometry",
                "probes", "hidden_size", "num_hidden_layers"],
          f"configuration {path.name}")
    cfg["_path"] = str(path)
    return cfg


def load_traffic(path: Path) -> Dict[str, Any]:
    tr = _read(path)
    _need(tr, ["name", "generator", "params"], f"traffic {path.name}")
    return tr


def published(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The published sizes a configuration file states, by ModelConfig
    attribute."""
    return {attr: cfg[key] for key, attr in PUBLISHED_KEYS.items()
            if key in cfg}


def text_of(n_tokens: int, rng: np.random.Generator) -> str:
    """``n_tokens`` lower-case letters: one served token each. Drawn at
    random, so two prompts share a first block with probability 26**-16."""
    return bytes(
        (rng.integers(0, 26, int(n_tokens)) + ord("a")).astype(np.uint8)
    ).decode("ascii")


def probe_prompts(cfg: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The configuration's fixed probe prompts: the same text in every run
    and in ``make_golden.py``, whatever ``--seed`` is."""
    out = []
    for p in cfg["probes"]:
        rng = np.random.default_rng(zlib.crc32(p["name"].encode()))
        text = text_of(p["tokens"], rng)
        out.append({"name": p["name"], "prompt": text,
                    "token_ids": [b + BYTE_TOKEN_OFFSET
                                  for b in text.encode()]})
    return out


def out_dir(asked: Optional[str]) -> Path:
    """Where a run's detail files go: ``--out``, or a git-ignored place
    inside the checkout."""
    path = Path(asked) if asked else CHECKOUT / ".cache" / "benchmark" / "out"
    path.mkdir(parents=True, exist_ok=True)
    return path
