"""Finds a cell's configuration, traffic mix, reference and metric
readers by name.

``BENCHMARK.json`` names each cell's configuration and traffic; the
files are ``<bench>/configs/<config>.json``, ``<bench>/traffic/
<traffic>.json``, the loop the mix names, ``<bench>/loops/<loop>.py``,
the reference the configuration names (its ``"reference"`` key),
``<bench>/references/<reference>.py``, and ``<bench>/metrics/
<metric>.py``.  Adding a configuration, a mix, a loop, a reference or
a metric is adding files: nothing here lists them.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Callable

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    source: str
    read: Callable            # read(run) -> float | None


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    drive: Callable           # drive(window), from the mix's loop
    reference: ModuleType     # layout(m) and gaps(...), REFERENCE_API
    end_to_end: list[Metric] = field(default_factory=list)
    per_layer: list[Metric] = field(default_factory=list)


# what a reference module defines:
#   layout(m) -> {path: (shape, std)}, the weight tree (bench/weights.py)
#   gaps(params, m, prompt, served, kv_bits, control=False)
#       -> {"program": (n,) gaps[, "control": (n,) gaps]}
REFERENCE_API = ("layout", "gaps")


def _module(bench_dir: Path, kind: str, name: str, *fns: str) -> ModuleType:
    """``<bench_dir>/<kind>/<name>.py``, which must define ``fns``."""
    path = bench_dir / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{kind} {name!r}: no file at {path}")
    mod_name = f"bench_{kind}_" + re.sub(r"\W", "_", name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for fn in fns:
        if not callable(getattr(mod, fn, None)):
            raise AttributeError(f"{path} defines no {fn}()")
    return mod


def _function(bench_dir: Path, kind: str, name: str, fn: str) -> Callable:
    """``fn`` of ``<bench_dir>/<kind>/<name>.py``."""
    return getattr(_module(bench_dir, kind, name, fn), fn)


def _load_json(path: Path, what: str) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"{what}: no file at {path}")
    with open(path) as f:
        return json.load(f)


def _metrics(entries, cell: str, bench_dir: Path) -> list[Metric]:
    out = []
    for m in entries:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        out.append(Metric(m["name"], m["unit"], m["source"],
                          _function(bench_dir, "metrics", m["name"], "read")))
    return out


def load_cell(name: str, bench_json: Path | None = None,
              bench_dir: Path | None = None) -> Cell:
    """The cell ``name`` of ``bench_json`` with its files from
    ``bench_dir`` (default: this package's directory)."""
    bench_json = Path(bench_json or ROOT / "BENCHMARK.json")
    bench_dir = Path(bench_dir or BENCH_DIR)
    spec = _load_json(bench_json, "benchmark")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    config = _load_json(bench_dir / "configs" / f"{w['config']}.json",
                        f"config {w['config']!r}")
    traffic = _load_json(bench_dir / "traffic" / f"{w['traffic']}.json",
                         f"traffic {w['traffic']!r}")
    for what, d, want in (("config", config, w["config"]),
                          ("traffic", traffic, w["traffic"])):
        if d.get("name") != want:
            raise ValueError(f"{what} file names itself {d.get('name')!r}, "
                             f"not {want!r}")
    if "reference" not in config:
        raise KeyError(f"config {w['config']!r} names no reference "
                       "(its \"reference\" key, a file in references/)")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                drive=_function(bench_dir, "loops", traffic["loop"], "drive"),
                reference=_module(bench_dir, "references", config["reference"],
                                  *REFERENCE_API),
                end_to_end=_metrics(spec["end_to_end"], name, bench_dir),
                per_layer=_metrics(spec["per_layer"], name, bench_dir))
