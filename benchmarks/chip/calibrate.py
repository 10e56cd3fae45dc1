"""Readings that set a cell's arrival rate and its limits.  They are made
once, when a cell is defined, and PERF.md records them; the benchmark's
own runs (``run.py``) never make them.

    python3 benchmarks/chip/calibrate.py sweep --workload <name> \
        --rates 2,3,4 --seconds 20 --seed <n>
    python3 benchmarks/chip/calibrate.py readings --workload <name> \
        --seeds 1,2,3 --seconds 20 [--control]

``sweep`` runs the cell's traffic at each arrival rate, with the window
opening at the end of the lead-in, and prints per rate the requests
completed per second, the queue left at the close and the latency tails:
the highest rate whose queue does not grow is the knee.  ``readings``
runs the cell on each seed and prints the numbers that decide
``correct``; with ``--control`` the float8 control is judged in the
program's place on the same requests (its verdict, ``correct``, should
be false), and the program's own widest gap is printed beside it.  Everything runs in one process on the chip, so each
program compiles (or loads from the cache) once.
"""
from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _ints(text: str):
    return [int(x) for x in text.split(",")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("sweep", "readings"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from benchmarks.chip import cells, harness

    base = cells.resolve(ROOT, args.workload)
    if args.mode == "sweep":
        runs = []
        for rate in [float(x) for x in args.rates.split(",")]:
            cell = dataclasses.replace(base, traffic=copy.deepcopy(base.traffic))
            cell.traffic["arrivals"]["rate_per_s"] = rate
            cell.traffic["window_opens"] = "after_lead"
            runs.append(({"rate_per_s": rate}, cell, args.seed))
    else:
        runs = [({"seed": s}, base, s) for s in _ints(args.seeds)]
    for label, cell, seed in runs:
        out = harness.serve(cell, seed, args.seconds, False, time.perf_counter(),
                            control=args.control)
        w, info = out["window_metrics"], out["info"]
        line = dict(label, correct=out["correct"],
                    checks={k: v["value"] for k, v in out["checks"].items()})
        if "program_logit_gap_max" in out:
            line["program_logit_gap_max"] = out["program_logit_gap_max"]
        line.update(w, **info, done_per_s=info["completed_in_window"] / args.seconds)
        print("calibrate " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
