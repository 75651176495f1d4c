"""Cells, configurations, traffic mixes, model families and per-layer
metrics, found by name.

``BENCHMARK.json`` at the root of the checkout names every cell; each cell
names a configuration (``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``).  The configuration names its model family,
``bench/families/<family>.py``; the traffic file names the cell's
``(data, model)`` mesh over exactly the cell's chips and, for training,
whether the training state is sharded over it (``fsdp``).  Each per-layer metric is a reader in
``bench/metrics/<name>.py``.  Adding a cell, a configuration, a family or a
metric adds files and entries and edits none.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import sys
from pathlib import Path
from typing import Callable, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    config: dict          # the configuration file, as run
    traffic: dict         # the traffic file
    chips: int
    end_to_end: list      # metric entries of BENCHMARK.json this cell reports
    per_layer: list
    limits: dict = dataclasses.field(default_factory=dict)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration and traffic loaded."""
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    data, model = traffic["mesh"]
    if data * model != w["chips"]:
        raise ValueError(
            f"cell {name!r}: traffic {w['traffic']!r} asks for a "
            f"{data} x {model} mesh, but the cell has {w['chips']} chip(s)")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name) and m["moves"] in e2e_names]
    return Cell(name=name, config=config, traffic=traffic, chips=w["chips"],
                end_to_end=e2e, per_layer=per_layer)


@functools.cache
def _module(path: Path):
    """The Python file at ``path``, imported once per process (and listed
    in ``sys.modules``, where a dataclass looks its module up)."""
    name = "bench_" + "_".join(path.parts[-2:]).replace(".", "_").replace(
        "-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> Callable:
    """``read(run) -> float | None`` from ``bench/metrics/<name>.py``."""
    return _module(BENCH / "metrics" / f"{name}.py").read


def family(cell: Cell):
    """The model family the cell's configuration names
    (``bench/families/<family>.py``): the program's configuration, the plain
    reference and the FLOP counts of that architecture."""
    return _module(BENCH / "families" / f"{cell.config['family']}.py")


def mesh(cell: Cell, devices):
    """The cell's ``(data, model)`` mesh over exactly its own chips."""
    from repro.launch.mesh import make_mesh_shape

    return make_mesh_shape(tuple(cell.traffic["mesh"]),
                           devices=list(devices)[:cell.chips])


def limits(name: str) -> dict:
    """The cell's limits on the numbers compared with the reference
    (``bench/limits/<cell>.json``)."""
    return {k: v["limit"] for k, v in
            load_json(BENCH / "limits" / f"{name}.json")["limits"].items()}


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def read_per_layer(cell: Cell, run) -> dict:
    """{name: {"value", "unit"}} for every per-layer metric that reads
    something in this run; a reader that finds nothing is left out."""
    out = {}
    for m in cell.per_layer:
        v: Optional[float] = metric_reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
