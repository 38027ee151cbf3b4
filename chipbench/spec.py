"""Find a cell and everything it names, by name, from ``BENCHMARK.json``.

A cell's configuration, traffic mix, own parameters, model kind and
per-layer metric readers each sit in a file of their own under the
benchmark's directory, so a later cell, configuration, kind or metric is
added by adding files and a ``BENCHMARK.json`` entry, never by editing
code here.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable

PKG = Path(__file__).resolve().parent
ROOT = PKG.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with the files it names loaded."""

    name: str
    chips: int
    config_name: str
    config: dict[str, Any]
    traffic: dict[str, Any]
    params: dict[str, Any]
    end_to_end: tuple[dict[str, Any], ...]
    per_layer: tuple[dict[str, Any], ...]
    pkg: Path


def _load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Load cell ``name`` from ``root/BENCHMARK.json`` and its files under
    ``root/chipbench``.  Raises ``KeyError`` for an unknown cell and
    ``ValueError`` for a configuration that names no ``kind``."""
    bench = _load_json(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}; "
                       f"known: {sorted(entries)}")
    entry = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    pkg = root / "chipbench"
    config_file = root / configs[entry["config"]]["file"]
    config = _load_json(config_file)
    if "kind" not in config:
        raise ValueError(f"{config_file}: the configuration names no "
                         f"\"kind\" (a file kinds/<kind>.py)")
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config_name=entry["config"],
        config=config,
        traffic=_load_json(pkg / "traffic" / f"{entry['traffic']}.json"),
        params=_load_json(pkg / "workloads" / f"{name}.json"),
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)),
        pkg=pkg,
    )


@functools.cache
def _load_module(path: Path, tag: str):
    """The module at ``path``, executed once per process, so that its
    jitted functions compile once and a test can patch what a run uses."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{tag}_{path.stem.replace('.', '_')}", path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(cell: Cell, metric: str) -> Callable[[Any], float | None]:
    """The ``read`` function of ``metrics/<metric>.py``."""
    return _load_module(cell.pkg / "metrics" / f"{metric}.py", "metric").read


def load_kind(cell: Cell):
    """The configuration's model kind, ``kinds/<kind>.py`` (its interface
    is in ``chipbench/harness.py``)."""
    return _load_module(cell.pkg / "kinds" / f"{cell.config['kind']}.py",
                        "kind")


def load_reference(cell: Cell):
    """The configuration's plain reference, ``references/<name>.py``."""
    name = cell.config["reference"]
    return _load_module(cell.pkg / "references" / f"{name}.py", "reference")
