"""One run of a serving cell: set-up, the measured window, the check.

The window drives the program's own entry points, ``ServingEngine.submit``
and ``ServingEngine.step``, on an engine built as ``repro.launch.serve``
builds one, under an open-loop schedule from the cell's traffic file.
Every request's due time is kept here: the engine's ``submitted_at`` is
when ``submit`` was called, which under a synchronous ``step`` can be
later than when the request was due.  The configuration's architecture
module (``Cell.arch``, from ``archs/``) gives the shapes, the program's
``ModelConfig`` and the layout of the seeded weights.

After the window the run reads the device's peak memory, frees the
program's state, and compares a seeded sample of the requests finished
in the window with the architecture's plain float32 reference
(``check.py``).
"""
from __future__ import annotations

import dataclasses
import functools
from collections import Counter
import gc
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

import jax

from benchmarks.chip import check, device, trace
from benchmarks.chip.cells import Cell, readers
from benchmarks.chip.weights import check_layout, make_weights

# the engine's callables whose launches the traced run tells apart
DISPATCHED = {"_admit_block": "admit", "_decode_block": "decode"}

def prompt_buckets(traffic: Dict) -> tuple:
    """Power-of-two prompt buckets covering the traffic's prompt lengths."""
    spec = traffic["prompt_tokens"]
    lo = spec.get("min", spec.get("value"))
    hi = spec.get("max", spec.get("value"))
    b = 1 << max(lo - 1, 0).bit_length()
    out = []
    while True:
        out.append(b)
        if b >= hi:
            return tuple(out)
        b *= 2


def bucket_of(buckets: tuple, n: int) -> int:
    return next(b for b in buckets if b >= n)


@dataclasses.dataclass
class Step:
    start: float
    end: float
    rounds: int
    calls: List[tuple] = dataclasses.field(default_factory=list)  # (kind, host time)


@dataclasses.dataclass
class Record:
    uid: int
    due: float
    submitted: float
    prompt: np.ndarray
    n_out: int
    req: object        # the engine's Request: first_token_at, done_at, out_tokens

    @property
    def first(self) -> Optional[float]:
        return self.req.first_token_at

    @property
    def done(self) -> Optional[float]:
        return self.req.done_at


@dataclasses.dataclass
class ServeRun:
    """What a per-layer metric reader sees of one run."""
    cell: Cell
    dims: Any                        # the cell's architecture's dims(config)
    peaks: Optional[Dict]
    max_batch: int
    buckets: tuple
    window: tuple                    # (open, close), host clock
    traced: Optional[tuple]          # (open, close) of the traced part
    steps: List[Step]
    records: List[Record]
    trace: Optional[trace.Reduction] = None
    programs: frozenset = frozenset()    # module names of the engine's programs

    def lane_positions(self, step_index: int) -> List[List[int]]:
        """For each decode round of step ``step_index``, the position each
        active lane writes at.  A request is admitted in the step in
        which its first token arrived and decodes from that step on, one
        position per round, until its ``n_out - 1`` decode rounds are
        done."""
        steps = self.steps
        out = [[] for _ in range(steps[step_index].rounds)]
        for r in self.records:
            if r.first is None:
                continue
            admitted = _step_at(steps, r.first)
            if admitted is None or admitted > step_index:
                continue
            before = sum(steps[j].rounds for j in range(admitted, step_index))
            left = r.n_out - 1 - before
            for k in range(min(steps[step_index].rounds, max(left, 0))):
                out[k].append(len(r.prompt) + before + k)
        return out

    def admissions(self, step_index: int) -> List[Record]:
        s = self.steps[step_index]
        return [r for r in self.records if r.first is not None and s.start < r.first <= s.end]

    def traced_step_indices(self) -> List[int]:
        a, b = self.traced
        return [i for i, s in enumerate(self.steps) if a <= s.start and s.end <= b]

    def engine_launches(self) -> List[trace.Launch]:
        """The engine's program launches on the first device, in order."""
        first = next(iter(self.trace.launches.values()), [])
        return [x for x in first if x.name.split("(")[0] in self.programs]

    def matched_calls(self) -> Optional[List[tuple]]:
        """``(kind, host time, launch)`` for each engine call in the
        traced steps.  Each step records which of the engine's admission
        and decode-block callables it called, in order (``Step.calls``);
        no engine program runs between steps, so the engine's launches in
        the trace are those calls, one for one, in the same order.  Where
        the counts differ nothing is returned."""
        calls = [c for i in self.traced_step_indices() for c in self.steps[i].calls]
        launches = self.engine_launches()
        if not calls or len(calls) != len(launches):
            return None
        return [(kind, at, x) for (kind, at), x in zip(calls, launches)]

    def program_seconds(self) -> Optional[Dict]:
        """Device seconds of the admission and the decode programs in the
        traced steps, and the indices of those steps (``"steps"``)."""
        matched = self.matched_calls() if self.trace is not None else None
        if matched is None:
            return None
        out = {"admit": 0.0, "decode": 0.0, "steps": self.traced_step_indices()}
        for kind, _, x in matched:
            out[kind] += x.seconds
        return out

    def device_lag_ms(self) -> Optional[tuple]:
        """Largest and median lead of each call's host time over its
        launch's start, both on the trace's clock (the host's put there
        through the window span).  A launch starts after its call, so a
        positive lead is the least by which the device's clock in the
        trace lags the host's."""
        matched = self.matched_calls()
        if matched is None:
            return None
        lo, t0 = self.trace.window_ns[0], self.traced[0]
        lead = sorted((lo + (at - t0) * 1e9 - x.start_ns) * 1e-6 for _, at, x in matched)
        return lead[-1], lead[len(lead) // 2]


def _step_at(steps: List[Step], t: float) -> Optional[int]:
    for i, s in enumerate(steps):
        if s.start < t <= s.end:
            return i
    return None


def _p95(values) -> Optional[float]:
    return float(np.percentile(np.asarray(values, np.float64), 95)) if len(values) else None


def serve(cell: Cell, seed: int, seconds: float, traced: bool, t_start: float,
          on_chip: bool = True, control: bool = False, corrupt=None) -> Dict:
    """Run ``cell`` once; return the result line's fields.  With
    ``control`` the float8 control is judged in the program's place
    (``check.compare``).
    ``corrupt`` wraps the engine after warm-up, for tests that break the
    timed path on purpose."""
    from repro.models import api as model_api
    from repro.runtime.serving import ServeConfig, ServingEngine

    dev = device.require_chips(cell.chips) if on_chip else device.describe(cell.chips)
    pk = device.peaks(dev["kind"]) if on_chip else None
    m = cell.arch.dims(cell.config)
    cfg = cell.arch.program_config(cell.config)
    tr, eng_conf = cell.traffic, cell.traffic["engine"]
    api = model_api.get_api(cfg)
    weights = make_weights(cell.arch.layout(m), seed)
    check_layout(
        jax.eval_shape(lambda: weights),
        jax.eval_shape(functools.partial(api.init_params, cfg), jax.random.PRNGKey(0)),
    )
    buckets = prompt_buckets(tr)
    engine = ServingEngine(cfg, weights, ServeConfig(
        max_batch=eng_conf["max_batch"],
        max_len=eng_conf["max_len"],
        max_new_tokens=tr["output_tokens"].get("max", tr["output_tokens"].get("value")),
        prefill_buckets=buckets,
        max_decode_block=eng_conf.get("max_decode_block", 32),
    ))
    generator = cell.module("generators", tr["generator"])
    schedule = generator.generate(tr, seed, seconds, m.vocab)
    engine.warmup()
    _warm_token_reads(engine, {s["n_out"] for s in schedule})
    if corrupt is not None:
        corrupt(engine)
    calls: List[tuple] = []
    programs = _record_calls(engine, calls) if traced else frozenset()
    B = eng_conf["max_batch"]
    opens = tr["window_opens"]
    lead_s = float(tr["lead_s"])
    trace_s = min(float(tr.get("trace_s", seconds)), seconds)
    compiles = _CompileCount()
    jax.monitoring.register_event_duration_secs_listener(compiles)

    records: List[Record] = []
    steps: List[Step] = []
    nxt = 0
    w0 = w1 = None
    trace_dir = None
    t_opened = t_closed = None
    ann = None
    t_sched = time.perf_counter()
    while True:
        now = time.perf_counter()
        while nxt < len(schedule) and t_sched + schedule[nxt]["due_s"] <= now:
            s = schedule[nxt]
            uid = engine.submit(s["prompt"], s["n_out"])
            req = engine._queue[-1]          # the engine fills in its timestamps
            assert req.uid == uid
            records.append(Record(uid, t_sched + s["due_s"], now, s["prompt"], s["n_out"], req))
            nxt += 1
        if w0 is None:
            # the window opens when every lane is taken (where the cell
            # says so), and at the end of the lead-in at the latest
            full = engine.active + engine.pending >= B
            if (opens == "lanes_full" and full) or now - t_sched >= lead_s:
                w0 = now
                lanes_full_at_open = full
                setup_s = w0 - t_start
                tokens_open = _tokens_out(engine)
                compiles.on = True
                if traced:
                    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
                    jax.profiler.start_trace(trace_dir, profiler_options=_profile_options())
                    ann = jax.profiler.TraceAnnotation(trace.WINDOW)
                    ann.__enter__()
                    t_opened = time.perf_counter()
        else:
            if ann is not None and now >= t_opened + trace_s:
                t_closed = time.perf_counter()
                ann.__exit__(None, None, None)
                ann = None
                jax.profiler.stop_trace()
            if now >= w0 + seconds:
                w1 = now
                compiles.on = False
                tokens_close = _tokens_out(engine)
                break
        if engine.pending or engine.active:
            r0, a = engine.rounds, time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.step"):
                engine.step()
            steps.append(Step(a, time.perf_counter(), engine.rounds - r0, calls[:]))
            calls.clear()
        else:
            due = t_sched + schedule[nxt]["due_s"] if nxt < len(schedule) else now + 0.01
            with jax.profiler.TraceAnnotation("bench.wait_for_arrival"):
                time.sleep(max(0.0, min(due, (w0 or now) + seconds) - now))
    if ann is not None:
        t_closed = time.perf_counter()
        ann.__exit__(None, None, None)
        jax.profiler.stop_trace()

    jax.monitoring.unregister_event_duration_listener(compiles)
    stats = engine.stats()
    lines = _end_to_end(records, w0, w1, setup_s, tokens_close - tokens_open)
    result = {
        "attempted": lines.pop("attempted"),
        "device": dict(dev, memory_peak_bytes=device.memory_peak_bytes(cell.chips)),
    }
    sample = check.sample(records, w0, w1, seed, tr["check"]["requests"])
    run = ServeRun(cell, m, pk, B, buckets, (w0, w1),
                   (t_opened, t_closed) if traced else None, steps, records,
                   programs=programs)
    info = {
        "completed_in_window": lines.pop("completed"),
        "completed_tok_s": lines.pop("completed_tok_s"),
        "ttft_p50_ms": lines.pop("ttft_p50_ms"),
        "ttft_p95_ms": lines["ttft_p95_ms"],
        "queue_at_close": engine.pending,
        "lanes_full_at_open": lanes_full_at_open,
        "lateness_p95_ms": lines.pop("lateness_p95_ms"),
        "compiles_in_window": compiles.n,
        "retraces_after_warmup": stats["retraces_after_warmup"],
    }
    # free the program's state before the reference takes the device
    del engine, weights
    gc.collect()

    if traced:
        run.trace = trace.reduce(trace.find_xplane(Path(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        first = next(iter(run.trace.launches.values()), [])
        names = Counter(x.name for x in first).most_common(8)
        n_calls = sum(len(run.steps[i].calls) for i in run.traced_step_indices())
        print(f"trace: {len(first)} launches {names}; engine launches "
              f"{len(run.engine_launches())}, engine calls {n_calls} in "
              f"{len(run.traced_step_indices())} steps; call lead over launch ms "
              f"(largest, median) {run.device_lag_ms()}", file=sys.stderr)
        metrics = {}
        read = readers(cell)
        for mdef in cell.per_layer:
            value = read[mdef["name"]](run)
            if value is not None:
                metrics[mdef["name"]] = {"value": value, "unit": mdef["unit"]}
        result["metrics"] = metrics
        result["device"]["busy_s"] = run.trace.mean_busy_s
        result["device"]["window_s"] = run.trace.window_s
        result["breakdown"] = {
            "device_ops": [[n[:160], s] for n, s in run.trace.top_ops],
            "idle_gaps": [[n, s] for n, s in run.trace.idle_gaps],
        }
    else:
        result["metrics"] = {
            mdef["name"]: {"value": lines[mdef["name"]], "unit": mdef["unit"]}
            for mdef in cell.end_to_end
        }
    print("info " + " ".join(f"{k}={v}" for k, v in info.items()), file=sys.stderr)

    verdict = check.compare(cell, m, seed, sample, info, control=control)
    result.update(verdict, info=info, window_metrics=lines)
    return result


def _record_calls(engine, calls: List[tuple]) -> frozenset:
    """Wrap the engine's admission and decode-block callables so that each
    call appends ``(kind, host time)`` to ``calls``; return the module
    names their programs carry in a trace."""
    names = set()
    for attr, kind in DISPATCHED.items():
        fn = getattr(engine, attr)
        names.add("jit_" + getattr(fn, "__name__", ""))

        def call(*args, _fn=fn, _kind=kind, **kw):
            calls.append((_kind, time.perf_counter()))
            return _fn(*args, **kw)

        setattr(engine, attr, call)
    return frozenset(names)


def _profile_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


class _CompileCount:
    """Counts XLA compilations (persistent-cache loads included) while on."""

    def __init__(self):
        self.on, self.n = False, 0

    def __call__(self, event: str, duration: float, **kw):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def _warm_token_reads(engine, lengths) -> None:
    """The engine reads a finished request's tokens with an eager slice as
    long as the request's output, one program per length: compile those
    of this schedule in set-up, so that none compiles in the window."""
    buf = engine._state["out_buf"]
    for n in sorted(lengths):
        np.asarray(buf[0, :n])


def _tokens_out(engine) -> int:
    """Output tokens produced so far: every finished request's, and each
    busy lane's count, which the engine syncs to the host after every
    block."""
    done = sum(len(r.out_tokens) for r in engine.completed)
    return done + sum(int(engine._slot_emitted[i])
                      for i, r in enumerate(engine._slots) if r is not None)


def _end_to_end(records: List[Record], w0: float, w1: float, setup_s: float,
                tokens: int) -> Dict:
    """The window's end-to-end numbers; ``tokens`` is the count of output
    tokens produced between its open and its close."""
    done = [r for r in records if r.done is not None and w0 < r.done <= w1]
    due = [r for r in records if w0 <= r.due <= w1]
    ttft = [((r.first if r.first is not None and r.first <= w1 else w1) - r.due) * 1e3
            for r in due]
    tpot = [(r.done - r.first) / (len(r.req.out_tokens) - 1) * 1e3
            for r in done if len(r.req.out_tokens) > 1]
    late = [(r.submitted - r.due) * 1e3 for r in records if r.submitted <= w1]
    return {
        "attempted": len(due),
        "completed": len(done),
        "output_tok_s": tokens / (w1 - w0),
        "completed_tok_s": sum(len(r.req.out_tokens) for r in done) / (w1 - w0),
        "ttft_p95_ms": _p95(ttft),
        "ttft_p50_ms": float(np.median(ttft)) if ttft else None,
        "tpot_p95_ms": _p95(tpot),
        "lateness_p95_ms": _p95(late),
        "setup_s": setup_s,
    }
