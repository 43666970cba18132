"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, so that a later change adds a cell
or a metric by adding files and entries only:

* a configuration: the ``file`` of its ``configs`` entry, which names its
  plain reference, ``reference/<name>.py``;
* a traffic mix: ``traffic/<name>.json``;
* a cell's limits for the comparison: ``limits/<cell>.json``;
* a per-layer metric: ``metrics/<name>.py``, whose ``read(ctx)`` returns a
  number, or None where it finds nothing to read.

A configuration file names the program's architecture (``arch``) and
changes its settings: ``model`` the ``ModelConfig``'s, ``parallel`` the
``ParallelConfig``'s.  An object under a key that holds a group of
settings (``moe``, ``mamba``) changes that group's fields and keeps the
rest (``harness.with_settings``).  Every key changed from the published
model is a cut, named by a dotted key (``num_layers``, ``moe.num_experts``)
in the file's ``reduced`` (why) and ``published`` (the published value),
and in the ``configs`` entry's ``reduced``.  A chip's share of a
deployment is written as such cuts: ``model.moe.num_experts`` is the
number of experts held here and ``published["moe.num_experts"]`` the
number the router spans; a sliced vocabulary is a smaller
``vocab_size``.  Widths are never cut.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict | None   # None until the cell's limits are set
    end_to_end: list      # manifest entries of the cell's end-to-end metrics
    per_layer: list       # manifest entries of the cell's per-layer metrics


def load_manifest(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _in_cell(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    man = load_manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"choose from {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in man["configs"]}[w["config"]]
    limits = HERE / "limits" / f"{name}.json"
    return Cell(
        name=name, chips=w["chips"],
        config_name=w["config"], config=_read_json(root / conf["file"]),
        traffic_name=w["traffic"],
        traffic=_read_json(HERE / "traffic" / f"{w['traffic']}.json"),
        limits=_read_json(limits) if limits.exists() else None,
        end_to_end=[m for m in man["end_to_end"] if _in_cell(m, name)],
        per_layer=[m for m in man["per_layer"] if _in_cell(m, name)])


def _module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(config: dict):
    """The plain reference module a configuration names."""
    return _module(HERE / "reference" / f"{config['reference']}.py")


def metric_reader(name: str):
    """``read(ctx)`` of the per-layer metric ``name``."""
    return _module(HERE / "metrics" / f"{name}.py").read
