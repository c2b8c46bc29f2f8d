"""Find a cell's parts by name.  Each configuration, traffic mix, limit,
reference and per-layer metric is a file of its own under ``bench/``:

  configs/<config>.json        sizes, scheduler, quantization recipe
  reference/<module>.py        plain reference named by the configuration
  traffic/<mix>.json           parameters of a mix, naming its generator
  generators/<generator>.py    ``make(mix, seed, vocab)`` (``lib/traffic.py``)
  checks/<cell>.json           limits of the numbers the cell compares
  layer_metrics/<metric>.py    ``read(run)`` for one per-layer metric

A new one is a new file; nothing here changes."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def _json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file missing: {path}")
    return json.loads(path.read_text())


def _module(path: Path):
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file missing: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_")
        .replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: Path) -> dict:
    return _json(Path(root) / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for c in bench["workloads"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[c['name'] for c in bench['workloads']]}")


def metrics_for(metrics: list, cell_name: str) -> list:
    """The metrics of a ``BENCHMARK.json`` list that ``cell_name`` reports:
    those that name it under ``workloads``, and those that name no cells."""
    return [m for m in metrics
            if cell_name in m.get("workloads", [cell_name])]


def config(name: str, base: Path = BENCH) -> dict:
    return _json(Path(base) / "configs" / f"{name}.json")


def traffic(name: str, base: Path = BENCH) -> dict:
    return _json(Path(base) / "traffic" / f"{name}.json")


def generator(name: str, base: Path = BENCH):
    return _module(Path(base) / "generators" / f"{name}.py")


def limits(cell_name: str, base: Path = BENCH) -> dict:
    return _json(Path(base) / "checks" / f"{cell_name}.json")


def reference(name: str, base: Path = BENCH):
    return _module(Path(base) / "reference" / f"{name}.py")


def layer_metric(name: str, base: Path = BENCH):
    return _module(Path(base) / "layer_metrics" / f"{name}.py")
