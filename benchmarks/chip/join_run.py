"""One traced run of a cell, with the engine's spans joined to the
device launches they issued.

    python3 benchmarks/chip/join_run.py --workload <name> --seed <n> \
        --seconds <s>

Runs the cell as ``run.py --trace 1`` does and prints the same result
line; then one more line, ``join {...}``, with what ``engine_trace``
reads from the same trace: the launches the ``run_id`` join attributes
to the engine's dispatch spans and how many of them a match by order
gives to the same span, the clock offset and its bound, the device's
idle share of the window and inside the engine's steps, the padding of
the admissions, and the idle time by the span that covers it.  The
harness deletes its trace once it has reduced it, so the join reads the
trace file at that step.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parents[2]


def summary(et) -> dict:
    steps = sum(s.name == "serve.step" for s in et.spans)
    ms = lambda ns: None if ns is None else ns * 1e-6
    return {
        "launches": len(et.joined) + et.unjoined,
        "unjoined": et.unjoined,
        "engine_launches": len(et.engine_launches()),
        "attributed": et.attributed(),
        "agree_by_order": et.order_agreement(),
        "clock_offset_ms": ms(et.clock_offset_ns),
        "callback_bound_ms": ms(et.callback_bound_ns),
        "impossible": et.impossible,
        "device_idle": et.device_idle(),
        "device_idle_engine": et.device_idle_engine(),
        "prefill_pad_share": et.prefill_pad_share(),
        "steps": steps,
        "spans_per_step": len(et.spans) / steps if steps else None,
        "idle_by_span_s": dict(list(et.idle_by_span().items())[:8]),
        "idle_gaps": et.idle_gaps()[:10],
    }


def main(argv=None) -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import run

    from benchmarks.chip import engine_trace, trace

    joined = []
    reduce = trace.reduce

    def reduce_and_join(xplane, *args, **kw):
        joined.append(engine_trace.read(xplane))
        return reduce(xplane, *args, **kw)

    with mock.patch.object(trace, "reduce", reduce_and_join):
        rc = run.main(list(argv if argv is not None else sys.argv[1:]) + ["--trace", "1"])
    if joined:
        print("join " + json.dumps(summary(joined[0])))
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
