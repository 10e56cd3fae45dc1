"""Helpers for the benchmark's CPU tests: a checkout-shaped directory that
holds a new cell made of new files only, and a run of it on the CPU."""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[3]
BENCH = REPO / "benchmarks" / "chip"
DATA = Path(__file__).resolve().parent / "data"

for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def new_cell_root(tmp: Path, config: str = "tiny-olmo", traffic: str = "tiny-chat") -> Path:
    """A copy of the benchmark with one more configuration file, one more
    traffic file and one more cell, and no other file changed."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(DATA / f"{config}.json", root / "benchmarks/chip/configs" / f"{config}.json")
    shutil.copy(DATA / f"{traffic}.json", root / "benchmarks/chip/traffic" / f"{traffic}.json")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    name = f"{config}.{traffic}"
    bench["configs"].append({"name": config, "source": "tests/data", "reduced": [],
                             "file": f"benchmarks/chip/configs/{config}.json", "why": "test"})
    bench["workloads"].append({"name": name, "config": config, "traffic": traffic,
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run_on_cpu(root: Path, workload: str, seed: int, seconds: float, **kw):
    """One run of the cell with the chip requirement skipped."""
    from benchmarks.chip import cells, harness

    cell = cells.resolve(root, workload)
    return harness.serve(cell, seed, seconds, False, time.perf_counter(),
                         on_chip=False, **kw)
