"""Helpers for the benchmark's CPU tests: a checkout-shaped directory that
holds a new cell made of new files only, a run of it on the CPU, and a
run whose launches are made up, for the readers."""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

REPO = Path(__file__).resolve().parents[3]
BENCH = REPO / "benchmarks" / "chip"
DATA = Path(__file__).resolve().parent / "data"

for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def config(name: str) -> dict:
    """A configuration file of the benchmark, or else of the tests' data."""
    path = BENCH / "configs" / f"{name}.json"
    return json.loads((path if path.exists() else DATA / f"{name}.json").read_text())


def program_shapes(arch, conf: dict) -> dict:
    """Shapes of the program's own ``init_params`` for ``conf``."""
    import functools

    import jax
    from repro.models import api as model_api

    cfg = arch.program_config(conf)
    return jax.eval_shape(functools.partial(model_api.get_api(cfg).init_params, cfg),
                          jax.random.PRNGKey(0))


def new_cell_root(tmp: Path, config: str = "tiny-olmo", traffic: str = "tiny-chat",
                  arch: str = None) -> Path:
    """A copy of the benchmark with one more configuration file, one more
    traffic file and one more cell, and with ``arch`` one more
    architecture module; no other file changed."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(DATA / f"{config}.json", root / "benchmarks/chip/configs" / f"{config}.json")
    shutil.copy(DATA / f"{traffic}.json", root / "benchmarks/chip/traffic" / f"{traffic}.json")
    if arch is not None:
        shutil.copy(DATA / f"{arch}.py", root / "benchmarks/chip/archs" / f"{arch}.py")
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


def launch_run(programs, names, arch: str = "dense", config: str = "tiny-olmo"):
    """What a reader sees of a traced run: three steps with two admissions
    and three decode blocks, matched to launches by order, the launches
    named ``names[kind]``, at the shapes of the test configuration
    ``config`` with its architecture module ``arch`` on a v5e."""
    from benchmarks.chip import harness, trace
    from benchmarks.chip.cells import load_module

    steps = [harness.Step(0.0, 1.0, 4, [("admit", 0.05), ("decode", 0.25)]),
             harness.Step(1.0, 2.0, 2, [("admit", 1.05), ("decode", 1.45)]),
             harness.Step(2.0, 3.0, 8, [("decode", 2.05)])]
    req = lambda first, n: SimpleNamespace(first_token_at=first, done_at=None, out_tokens=[])
    recs = [harness.Record(0, 0, 0, np.zeros(30, np.int32), 20, req(0.5, 20)),
            harness.Record(1, 0, 0, np.zeros(31, np.int32), 20, req(0.6, 20)),
            harness.Record(2, 0, 0, np.zeros(100, np.int32), 20, req(1.5, 20))]
    L = lambda k, s, e: trace.Launch(names[k], int(s * 1e9), int(e * 1e9))
    launches = [L("admit", -0.001, 0.2), L("decode", 0.3, 0.6), L("read", 0.6, 0.61),
                L("admit", 1.1, 1.4), L("decode", 1.5, 1.7), L("decode", 2.1, 2.9)]
    red = trace.Reduction(window_ns=(0, int(3e9)), busy_s={"d0": 2.0},
                          launches={"d0": launches}, collective_s={}, top_ops=[], idle_gaps=[])
    conf = json.loads((DATA / f"{config}.json").read_text())
    path = BENCH / "archs" / f"{arch}.py"
    module = load_module(path if path.is_file() else DATA / f"{arch}.py")
    return harness.ServeRun(SimpleNamespace(arch=module), module.dims(conf),
                            {"bf16_flop_per_s": 197e12, "hbm_byte_per_s": 819e9},
                            4, (32, 64, 128), (0.0, 3.0), (0.0, 3.0), steps, recs, red,
                            programs=frozenset(programs))
