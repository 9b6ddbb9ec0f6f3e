"""Find a cell's files by name.

A cell ``bench/workloads/<cell>.json`` names its configuration
(``bench/configs/<config>.json``) and its traffic mix
(``bench/traffic/<mix>.json``); per-layer metric readers are
``bench/metrics/<metric>.py``. Adding any of them adds a file and edits
none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    workload: Dict          # the cell file
    config: Dict            # the configuration file (published keys)
    traffic: Dict           # the traffic mix file

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    @property
    def limits(self) -> Dict[str, float]:
        return self.workload["limits"]


def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Optional[Path] = None) -> Cell:
    """The cell ``name`` with its configuration and traffic, read from
    ``root`` (default: this directory)."""
    root = Path(root) if root is not None else BENCH
    wl = _json(root / "workloads" / f"{name}.json")
    return Cell(name=name, workload=wl,
                config=_json(root / "configs" / f"{wl['config']}.json"),
                traffic=_json(root / "traffic" / f"{wl['traffic']}.json"))


def metric_readers(root: Optional[Path] = None) -> Dict[str, object]:
    """Every per-layer metric reader module, by metric name."""
    root = Path(root) if root is not None else BENCH
    out = {}
    for path in sorted((root / "metrics").glob("*.py")):
        if path.stem.startswith("_"):
            continue
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{path.stem.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[path.stem] = mod
    return out


def declared_metrics(cell: str, kind: str, root: Optional[Path] = None) -> List[str]:
    """The ``kind`` (``end_to_end`` or ``per_layer``) metrics that
    ``BENCHMARK.json`` declares for ``cell``: those whose ``workloads``
    name it, or that have no ``workloads``."""
    spec = _json((Path(root) if root is not None else ROOT) / "BENCHMARK.json")
    return [m["name"] for m in spec[kind] if cell in m.get("workloads", [cell])]


def reference_module(config: Dict):
    """The plain reference named by a configuration's ``reference`` key."""
    return importlib.import_module(f"bench.reference.{config['reference']}")


def arch_config(config: Dict, **overrides):
    """The system's ``ArchConfig`` for a configuration file, as served
    (weights in the file's ``torch_dtype``)."""
    from repro.models.config import ArchConfig

    arch = config["architecture"]
    heads = int(config["num_attention_heads"])
    fields = dict(
        name=config["name"], family="dense",
        vocab=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]), n_heads=heads,
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config.get("head_dim") or config["hidden_size"] // heads),
        d_ff=int(config["intermediate_size"]),
        qkv_bias=bool(arch["qkv_bias"]), qk_norm=bool(arch["qk_norm"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        param_dtype=config["torch_dtype"], compute_dtype=config["torch_dtype"],
        kv_block_tokens=int(config["assumed"]["kv_block_tokens"]))
    fields.update(overrides)
    return ArchConfig(**fields)
