"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json`` at the root of the
checkout; its configuration, the configuration's architecture module,
its traffic mix and its per-layer metrics are files under
``benchmarks/chip/``.  The run refuses (exit code 3, no result) a
host whose JAX finds no TPU or fewer chips than the cell asks for.  The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
a ``breakdown`` of device time, and last the ``checks``, each number
compared beside its limit.  The same checks are the last lines of
standard error.

JAX's persistent compilation cache lives in ``.jax_cache`` at the root
of the checkout unless ``JAX_COMPILATION_CACHE_DIR`` names another.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from benchmarks.chip import cells, harness
    from benchmarks.chip.device import NoChip

    cell = cells.resolve(ROOT, args.workload)
    try:
        out = harness.serve(cell, args.seed, args.seconds, bool(args.trace), T_START)
    except NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 3
    line = {k: out[k] for k in ("correct", "attempted", "failed", "metrics", "device")}
    if "breakdown" in out:
        line["breakdown"] = out["breakdown"]
    line["checks"] = out["checks"]
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
