"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample
drawn from the seed of the requests finished in the window (the longest
of them always among it) is run through the plain float32 reference of
the cell's architecture module (``archs/<name>.py``, ``logit_gaps``):
each prompt with the tokens the engine served.  At every served token
the reference's best logit less its logit of that token is the token's
gap; a greedy engine that computes what the configuration states serves
only near-ties, and the widest gap over the sample is compared with the
configuration's limit (``"check": {"logit_gap_limit": ...}``, set from
program and control readings on the chip as PERF.md records).

The control puts the reference in float8 in the program's place: at the
same positions of the same prompts and served tokens its first choice
is judged by the same gap and the same limit, so a control run comes out
not correct where the comparison can tell float8 from what the program
serves.

The run also holds the engine to zero compilations after warm-up.
"""
from __future__ import annotations

import sys
import time
from typing import Dict, List, Tuple

import numpy as np

from benchmarks.chip.weights import make_weights


def sample(records, w0: float, w1: float, seed: int, k: int) -> List[Tuple[np.ndarray, np.ndarray]]:
    """``k`` requests finished in the window: the longest, then a draw."""
    done = [r for r in records if r.done is not None and w0 < r.done <= w1]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i].prompt) + len(done[i].req.out_tokens))
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng([seed, 1])
    picked = [longest] + list(rng.choice(rest, size=min(k - 1, len(rest)), replace=False))
    return [(done[i].prompt, np.asarray(done[i].req.out_tokens, np.int32)) for i in picked]


def compare(cell, m, seed: int, requests, info: Dict, control: bool = False) -> Dict:
    """``correct``, ``failed`` and the numbers compared with their limits;
    ``m`` is the architecture's ``dims`` of the cell's configuration.
    With ``control`` the float8 control's gaps are judged in place of the
    program's, and the program's widest gap is returned beside them as
    ``program_logit_gap_max``."""
    limit = float(cell.config["check"]["logit_gap_limit"])
    t0 = time.perf_counter()
    weights = make_weights(cell.arch.layout(m), seed)
    widest, program_widest, failed, tokens = 0.0, 0.0, 0, 0
    for prompt, served in requests:
        g, cg = cell.arch.logit_gaps(m, cell.config, weights, prompt, served, control=control)
        program_widest = max(program_widest, float(g.max()))
        judged = float((cg if control else g).max())
        widest = max(widest, judged)
        failed += int(judged > limit)
        tokens += len(served)
    del weights
    checks = {
        "logit_gap_max": {"value": widest, "limit": limit},
        "retraces_max": {"value": info["retraces_after_warmup"], "limit": 0},
        "requests_checked_min": {"value": len(requests), "limit": 1},
    }
    correct = (widest <= limit and info["retraces_after_warmup"] <= 0
               and len(requests) >= 1)
    out = {"correct": bool(correct), "failed": failed}
    if control:
        out["program_logit_gap_max"] = program_widest
    print(f"reference: {len(requests)} requests, {tokens} served tokens, "
          f"{time.perf_counter() - t0:.1f} s" + (" (control judged)" if control else ""),
          file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    out["checks"] = checks
    return out
