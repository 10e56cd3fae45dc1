"""Find a cell, and everything that belongs to it, by the names in
``BENCHMARK.json``.

A cell names a configuration and a traffic mix.  Each lives in a file of
its own under the benchmark's directory (``configs/``, ``traffic/``), and
each per-layer metric is a reader in ``metrics/<name>.py``; traffic names
its generator module in ``generators/``.  A new cell, configuration,
traffic mix or metric is therefore new files and new entries, and no
edit to any file that is already there.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path("benchmarks") / "chip"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    root: Path

    def module(self, kind: str, name: str):
        """The module ``<kind>/<name>.py`` of this checkout's benchmark."""
        return load_module(self.root / BENCH_DIR / kind / f"{name}.py")


def load_module(path: Path):
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem.replace('.', '_')}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: Dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def resolve(root: Path, workload: str) -> Cell:
    """The cell ``workload`` of the ``BENCHMARK.json`` at ``root``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text()
    )
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        root=root,
    )


def readers(cell: Cell) -> Dict[str, Callable[..., Optional[float]]]:
    """Each per-layer metric of the cell -> its ``read(run)`` function."""
    return {m["name"]: cell.module("metrics", m["name"]).read for m in cell.per_layer}
