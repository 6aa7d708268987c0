"""Where the harness finds a cell's pieces: ``BENCHMARK.json`` and files by name.

A cell names a configuration and a traffic mix; the harness reads

  configs/<config>.json     the dataset (its ``kind`` names kinds/<kind>.py)
  mixes/<traffic>.json      the job mix (its ``app`` names refs/ and counts/)
  kinds/<kind>.py           device generator, block view, estimate front
  refs/<app>.py             plain reference of the app, its control, compare
  refs/estimate_<kind>.py   plain reference of the estimate front
  counts/<name>.py          least bytes of an app's program or of a kernel
  metrics/<metric>.py       ``read(run)`` of one metric

all under ``<root>/benchmarks/chip``.  A cell, mix or metric is added by
adding such files and ``BENCHMARK.json`` entries, with no edit here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

__all__ = ["BENCH_DIR", "Cell", "repo_root", "load_cell", "load_module",
           "load_json"]

BENCH_DIR = Path("benchmarks") / "chip"


def repo_root() -> Path:
    return Path(__file__).resolve().parents[2]


@dataclasses.dataclass(frozen=True)
class Cell:
    root: Path
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: tuple     # BENCHMARK.json metric entries reported by this cell
    per_layer: tuple


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(root: Path, workload: str) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(cells: {sorted(by_name)})")
    w = by_name[workload]
    cfg_file = {c["name"]: c["file"] for c in spec["configs"]}[w["config"]]
    config = json.loads((root / cfg_file).read_text())
    mix = json.loads((root / BENCH_DIR / "mixes" / f"{w['traffic']}.json")
                     .read_text())
    return Cell(root=root, name=workload, chips=int(w["chips"]),
                config=config, mix=mix,
                end_to_end=tuple(m for m in spec["end_to_end"]
                                 if _applies(m, workload)),
                per_layer=tuple(m for m in spec["per_layer"]
                                if _applies(m, workload)))


def load_module(root: Path, group: str, name: str):
    """Import ``<root>/benchmarks/chip/<group>/<name>.py`` by path."""
    path = root / BENCH_DIR / group / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{group}_{name.replace('-', '_')}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(root: Path, name: str) -> dict:
    return json.loads((root / BENCH_DIR / name).read_text())
