"""Find a cell's pieces by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, per-layer
metric or model sits in a file of its own under the benchmark directory:

    configs/<config>.json    sizes, spec fields, reference model, assumed
    traffic/<traffic>.json   channel and chunk parameters, burst length
    limits/<cell>.json       the limit of each number compared for correct
    metrics/<metric>.py      ``read(art) -> float | None``
    models/<reference>.py    plain init, loss and FLOP count of a model

so a later change adds a cell, a mix, a metric or a model by adding files
and entries, and edits none.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib

BENCH_DIR = "chipbench"


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple
    per_layer: tuple
    root: pathlib.Path

    @property
    def bench_dir(self) -> pathlib.Path:
        return self.root / BENCH_DIR

    def model(self):
        return load_module(self.bench_dir / "models" / f"{self.config['reference']}.py")

    def reader(self, metric: str):
        return load_module(self.bench_dir / "metrics" / f"{metric}.py")


def load_benchmark(root) -> dict:
    return json.loads((pathlib.Path(root) / "BENCHMARK.json").read_text())


def _read_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"missing benchmark file: {path}")
    return json.loads(path.read_text())


def load_module(path: pathlib.Path):
    """The module at ``path``, loaded once per process (so that its
    functions, and what JAX compiled for them, are the same each time)."""
    if not path.is_file():
        raise FileNotFoundError(f"missing benchmark module: {path}")
    return _load(str(path.resolve()))


@functools.lru_cache(maxsize=None)
def _load(file: str):
    path = pathlib.Path(file)
    name = f"chipbench_{path.parent.name}_{path.stem}"
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(root, name: str) -> Cell:
    """The cell called ``name`` with every file it names loaded."""
    root = pathlib.Path(root)
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r} (known: {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    bench_dir = root / BENCH_DIR
    config = _read_json(root / configs[w["config"]]["file"])
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=_read_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        limits=_read_json(bench_dir / "limits" / f"{name}.json"),
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)),
        root=root,
    )
