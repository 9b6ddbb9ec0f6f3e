"""Find a cell's files by name.

A cell ``bench/workloads/<cell>.json`` names its configuration
(``bench/configs/<config>.json``) and its traffic mix
(``bench/traffic/<mix>.json``). The configuration names its model
family (``bench/families/<family>.py``) and its plain reference
(``bench/reference/<reference>.py``); per-layer metric readers are
``bench/metrics/<metric>.py``. Adding any of them adds a file and edits
none. Every lookup takes ``root``, the directory that holds those
subdirectories (default: this one).
"""
from __future__ import annotations

import dataclasses
import functools
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
    root: Path = BENCH      # where the files were found

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    @property
    def limits(self) -> Dict[str, float]:
        return self.workload["limits"]


def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def _root(root: Optional[Path]) -> Path:
    return Path(root) if root is not None else BENCH


def _load(path: Path, prefix: str):
    spec = importlib.util.spec_from_file_location(
        f"{prefix}_{path.stem.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _module(path: Path, prefix: str):
    """The module in ``path``, loaded once (its jitted functions keep
    their compiled programs)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    return _load(path, prefix)


def load_cell(name: str, root: Optional[Path] = None) -> Cell:
    """The cell ``name`` with its configuration and traffic, read from
    ``root``."""
    root = _root(root)
    wl = _json(root / "workloads" / f"{name}.json")
    return Cell(name=name, workload=wl,
                config=_json(root / "configs" / f"{wl['config']}.json"),
                traffic=_json(root / "traffic" / f"{wl['traffic']}.json"),
                root=root)


def metric_readers(root: Optional[Path] = None) -> Dict[str, object]:
    """Every per-layer metric reader module, by metric name."""
    return {path.stem: _load(path, "bench_metric")
            for path in sorted((_root(root) / "metrics").glob("*.py"))
            if not path.stem.startswith("_")}


def declared_metrics(cell: str, kind: str, root: Optional[Path] = None) -> List[str]:
    """The ``kind`` (``end_to_end`` or ``per_layer``) metrics that
    ``BENCHMARK.json`` declares for ``cell``: those whose ``workloads``
    name it, or that have no ``workloads``."""
    spec = _json((Path(root) if root is not None else ROOT) / "BENCHMARK.json")
    return [m["name"] for m in spec[kind] if cell in m.get("workloads", [cell])]


def reference_module(config: Dict, root: Optional[Path] = None):
    """The plain reference named by a configuration's ``reference`` key."""
    return _module(_root(root) / "reference" / f"{config['reference']}.py",
                   "bench_reference")


def family_module(family: str, root: Optional[Path] = None):
    """The model family module ``families/<family>.py``. ``family`` is a
    configuration's ``family`` key, which is also the ``family`` of the
    ``ArchConfig`` its module makes (the program's taxonomy)."""
    return _module(_root(root) / "families" / f"{family}.py", "bench_family")


def arch_config(config: Dict, root: Optional[Path] = None, **overrides):
    """The system's ``ArchConfig`` for a configuration file, as served
    (weights in the file's ``torch_dtype``)."""
    arch = family_module(config["family"], root).arch_config(config, **overrides)
    if arch.family != config["family"]:
        raise ValueError(f"family {config['family']!r} made an ArchConfig of "
                         f"family {arch.family!r}")
    return arch
