"""Find a cell, and everything that belongs to it, by the names in
``BENCHMARK.json``.

A cell names a configuration and a traffic mix.  Each lives in a file of
its own under the benchmark's directory (``configs/``, ``traffic/``), and
each per-layer metric is a reader in ``metrics/<name>.py``; traffic names
its generator module in ``generators/``, and a configuration names its
architecture module in ``archs/`` (``"bench_arch": "<module>"``; a file
that names none is ``dense``).

Everything that depends on an architecture goes through that module,
and nothing else in the benchmark reads a configuration's shapes.  The
module gives:

- ``dims(conf)``: a hashable, frozen shape record with at least
  ``vocab``, the ids the traffic draws from;
- ``program_config(conf)``: the program's ``ModelConfig``;
- ``layout(m)``: ``{leaf path: (shape, kind)}``, kind ``normal``,
  ``scale`` or ``bias``, from which ``weights.make_weights`` makes the
  seeded tree and which ``weights.check_layout`` holds to the program's
  ``init_params``;
- ``param_count(m)``, ``prefill_flops(m, n)`` (a prompt of ``n``
  tokens), ``decode_flops(m, positions)`` and ``decode_bytes(m,
  positions)`` (one decode round, a lane writing at each position): the
  work the roofline and MFU readers divide;
- ``logit_gaps(m, conf, weights, prompt, served, control)``: the plain
  float32 reference's gap at each served token, and with ``control``
  the float8 control's (``check.py``).

A new cell, configuration, architecture, traffic mix or metric is
therefore new files (an ``archs/`` module, a configuration file, a
traffic file, a reader) and new entries in ``BENCHMARK.json``, and no
edit to any file that is already there.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path("benchmarks") / "chip"

# what an architecture module gives (see the module's docstring)
ARCH_CONTRACT = ("dims", "program_config", "layout", "param_count", "prefill_flops",
                 "decode_flops", "decode_bytes", "logit_gaps")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    root: Path
    arch: ModuleType         # the configuration's archs/<bench_arch>.py

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
    # registered while it runs, as an import would: a dataclass (an
    # architecture's shape record) looks its module up by name
    sys.modules[spec.name] = mod
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
    config_file = root / configs[w["config"]]["file"]
    config = json.loads(config_file.read_text())
    arch_file = root / BENCH_DIR / "archs" / f"{config.get('bench_arch', 'dense')}.py"
    if not arch_file.is_file():
        raise FileNotFoundError(
            f"{config_file.name}: its architecture module {arch_file} does not exist")
    arch = load_module(arch_file)
    missing = [f for f in ARCH_CONTRACT if not callable(getattr(arch, f, None))]
    if missing:
        raise AttributeError(f"{config_file.name}: its architecture module {arch_file} "
                             f"lacks {', '.join(missing)}")
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
        arch=arch,
    )


def readers(cell: Cell) -> Dict[str, Callable[..., Optional[float]]]:
    """Each per-layer metric of the cell -> its ``read(run)`` function."""
    return {m["name"]: cell.module("metrics", m["name"]).read for m in cell.per_layer}
