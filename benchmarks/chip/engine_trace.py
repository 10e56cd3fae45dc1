"""The serving engine's spans in a profiler trace, joined to the device
launches they issued, on one clock.

The engine (``runtime/serving.py``) marks what it does with host spans
named ``serve.*`` and carries its counts on them as event stats:

- ``serve.step`` (``queue``, ``lanes``): one engine step;
- ``serve.admit`` (``bucket``, ``n``, ``n_pad``, ``tokens``): one batched
  prefill, its array prep and the dispatch of the admission program;
- ``serve.admit_sync``: the host's read of the admission's results;
- ``serve.decode_block`` (``rounds``, ``lanes``): the dispatch of a fused
  decode block;
- ``serve.block_sync``: the host's read of the per-lane flags after it;
- ``serve.drain`` (``tokens``): the read of a finished request's tokens.

A launch on a device's ``XLA Modules`` line and the host's
``DoEnqueueProgram`` event that enqueued it carry the same ``run_id``.
An enqueue whose inputs were not ready when the program was dispatched
runs later on a runtime thread, inside an event that a trace flow (the
``_c`` and ``_p`` stats) links back to the dispatch; the innermost
``serve.*`` span that covers the dispatch issued the launch.

The device's events in a trace read early against the host's.  The least
shift that puts every joined launch's start at or after its enqueue is
``clock_offset_ns``.  A host ``CompleteCallbacks`` event with the
launch's ``run_id`` follows the launch's end, which bounds the shift from
above (``callback_bound_ns``).  Device intervals here are put on the
host's clock by adding ``clock_offset_ns``.  All device readings are of
the first device.
"""
from __future__ import annotations

import dataclasses
import warnings
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from benchmarks.chip.trace import WINDOW, Interval, Launch, union

ENGINE = "serve."
BENCH = "bench."
DISPATCH = ("serve.admit", "serve.decode_block")
# the engine's admission and decode-block programs, as a trace names them
PROGRAMS = ("jit__admit_impl", "jit__decode_block_impl")
ENQUEUE = "DoEnqueueProgram"
CALLBACK = "CompleteCallbacks"


@dataclasses.dataclass
class Span:
    name: str
    start_ns: int
    end_ns: int
    args: Dict[str, int]


@dataclasses.dataclass
class Joined:
    launch: Launch              # on the device's clock
    run_id: int
    enqueue_ns: int             # start of its DoEnqueueProgram, host clock
    issued_ns: int              # the dispatch that issued the enqueue, host clock
    span: Optional[Span]        # innermost serve.* span covering the dispatch


@dataclasses.dataclass
class EngineTrace:
    window_ns: Tuple[int, int]          # the window span, host clock
    spans: List[Span]                   # serve.* spans in the window, by start
    joined: List[Joined]                # launches with an enqueue, by start
    unjoined: int                       # launches with no enqueue of their run_id
    clock_offset_ns: Optional[int]
    callback_bound_ns: Optional[int]
    impossible: int                     # callbacks that would end before the offset allows
    busy: List[Interval]                # op intervals, host clock, in the window
    host: List[Tuple[str, int, int]]    # every host event (gap naming)

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    def engine_launches(self) -> List[Joined]:
        """The engine's launches dispatched in the window."""
        lo, hi = self.window_ns
        return [j for j in self.joined
                if j.launch.name.split("(")[0] in PROGRAMS and lo <= j.issued_ns <= hi]

    def attributed(self) -> int:
        """Engine launches the join gives to a dispatch span."""
        return sum(j.span is not None and j.span.name in DISPATCH
                   for j in self.engine_launches())

    def order_agreement(self) -> Optional[int]:
        """Engine launches for which the join and a match by order agree:
        the k-th dispatch span in the window, by host start, against the
        k-th engine launch, by device start.  None where the counts
        differ."""
        calls = [s for s in self.spans if s.name in DISPATCH]
        launches = self.engine_launches()
        if len(calls) != len(launches):
            return None
        return sum(j.span is c for c, j in zip(calls, launches))

    def idle_ns(self, lo: int, hi: int) -> int:
        """Time in [lo, hi] (host clock) in which no operation ran."""
        busy = sum(max(0, min(e, hi) - max(s, lo)) for s, e in self.busy)
        return max(0, hi - lo) - busy

    def device_idle(self) -> float:
        """Idle share of the window, % (on the host's clock)."""
        return 100.0 * self.idle_ns(*self.window_ns) * 1e-9 / self.window_s

    def device_idle_engine(self) -> float:
        """Idle time inside the ``serve.step`` spans over the window, %:
        the idle the engine causes, apart from waiting for arrivals."""
        lo, hi = self.window_ns
        idle = sum(self.idle_ns(max(s.start_ns, lo), min(s.end_ns, hi))
                   for s in self.spans if s.name == "serve.step")
        return 100.0 * idle * 1e-9 / self.window_s

    def prefill_pad_share(self) -> Optional[float]:
        """Padding among the prompt positions the admissions in the window
        computed, %: one less real tokens over bucket x padded rows."""
        admits = [s.args for s in self.spans if s.name == "serve.admit"]
        computed = sum(a["bucket"] * a["n_pad"] for a in admits)
        if not computed:
            return None
        return 100.0 * (1.0 - sum(a["tokens"] for a in admits) / computed)

    def idle_gaps(self, min_gap_ns: int = 100_000) -> List[Tuple[str, float]]:
        """Idle gaps in the window, longest first, each named after the
        innermost ``serve.*`` or ``bench.*`` span that covers its middle,
        or where none does, the shortest host span that covers it."""
        lo, hi = self.window_ns
        # these spans nest (one thread's context managers): sweep them in
        # time order with a stack of the open ones, innermost last
        marks = sorted((h for h in self.host if h[0].startswith((ENGINE, BENCH)) and h[0] != WINDOW),
                       key=lambda h: (h[1], -h[2]))
        stack: List[Tuple[str, int, int]] = []
        k = 0
        edges = [lo] + [x for iv in self.busy for x in iv] + [hi]
        gaps = []
        for s, e in zip(edges[0::2], edges[1::2]):
            if e - s < min_gap_ns:
                continue
            mid = (s + e) // 2
            while k < len(marks) and marks[k][1] <= mid:
                while stack and stack[-1][2] < marks[k][1]:
                    stack.pop()
                stack.append(marks[k])
                k += 1
            while stack and stack[-1][2] < mid:
                stack.pop()
            cover = stack[-1:] or [h for h in self.host if h[1] <= mid <= h[2] and h[0] != WINDOW]
            name = min(cover, key=lambda h: h[2] - h[1])[0] if cover else "(no host span)"
            gaps.append((name, (e - s) * 1e-9))
        return sorted(gaps, key=lambda g: -g[1])

    def idle_by_span(self, min_gap_ns: int = 0) -> Dict[str, float]:
        """Idle seconds in the window by the span that names each gap."""
        out: Dict[str, float] = {}
        for name, s in self.idle_gaps(min_gap_ns):
            out[name] = out.get(name, 0.0) + s
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def _stats(ev) -> Dict:
    # the first read of an event's stats builds its binding type, which
    # warns of a missing __module__
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(ev.stats)


def _issued(flows_in: List[Tuple[int, int, tuple]], flow_start: Dict[tuple, int], at: int) -> int:
    """When the host issued an enqueue made at ``at``: an enqueue that
    waited for its inputs runs later on a runtime thread, inside an event
    that a flow links back to the dispatch that issued it."""
    cover = [f for f in flows_in if f[0] <= at <= f[1] and f[2] in flow_start]
    if not cover:
        return at
    return flow_start[min(cover, key=lambda f: f[1] - f[0])[2]]


def _innermost(spans: List[Span], t: int) -> Optional[Span]:
    cover = [s for s in spans if s.start_ns <= t <= s.end_ns]
    return min(cover, key=lambda s: s.end_ns - s.start_ns) if cover else None


def read(xplane: Path) -> EngineTrace:
    """The engine's spans, the run_id join and the clock offset of the
    ``.xplane.pb`` at ``xplane``, over its ``bench.window`` span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xplane))
    host: List[Tuple[str, int, int]] = []
    engine: List[Span] = []
    enqueued: Dict[int, Tuple[int, int]] = {}        # run_id -> (start, line)
    callbacks: Dict[int, int] = {}
    flows_in: List[List[Tuple[int, int, tuple]]] = []  # per line: (start, end, flow)
    flow_start: Dict[tuple, int] = {}                # flow -> start of the event issuing it
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                into_line: List[Tuple[int, int, tuple]] = []
                flows_in.append(into_line)
                for ev in line.events:
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    st = _stats(ev)
                    if "_p" in st:
                        flow_start.setdefault((st.get("_pt"), st["_p"]), s)
                    if "_c" in st:
                        into_line.append((s, e, (st.get("_ct"), st["_c"])))
                    if ev.name == ENQUEUE and "run_id" in st:
                        enqueued.setdefault(int(st["run_id"]), (s, len(flows_in) - 1))
                    elif ev.name == CALLBACK and "run_id" in st:
                        callbacks.setdefault(int(st["run_id"]), s)
                    if e > s:
                        host.append((ev.name, s, e))
                        if ev.name.startswith(ENGINE):
                            engine.append(Span(ev.name, s, e, {
                                k: v for k, v in st.items() if not k.startswith("_")
                            }))
        elif plane.name.startswith("/device:TPU:") and plane.name[12:].isdigit():
            devices.append(plane)
    windows = [h for h in host if h[0] == WINDOW]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW!r} host span")
    lo, hi = windows[0][1], windows[0][2]
    engine.sort(key=lambda s: s.start_ns)

    launches: List[Tuple[Launch, int]] = []
    ops: List[Interval] = []
    if devices:
        first = min(devices, key=lambda p: int(p.name[12:]))
        for line in first.lines:
            for ev in line.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if line.name == "XLA Modules":
                    launches.append((Launch(ev.name, s, e), int(_stats(ev).get("run_id", -1))))
                elif line.name == "XLA Ops":
                    ops.append((s, e))
    launches.sort(key=lambda x: x[0].start_ns)

    joined = []
    for x, r in launches:
        if r in enqueued:
            at, line = enqueued[r]
            issued = _issued(flows_in[line], flow_start, at)
            joined.append(Joined(x, r, at, issued, _innermost(engine, issued)))
    offset = max((j.enqueue_ns - j.launch.start_ns for j in joined), default=None)
    bound, impossible = None, 0
    if offset is not None:
        ends = {j.run_id: j.launch.end_ns for j in joined}
        room = [callbacks[r] - end for r, end in ends.items() if r in callbacks]
        impossible = sum(b < offset for b in room)
        bound = min((b for b in room if b >= offset), default=None)
    shift = offset or 0
    busy = [(max(s + shift, lo), min(e + shift, hi)) for s, e in union(ops)
            if e + shift > lo and s + shift < hi]
    return EngineTrace(
        window_ns=(lo, hi),
        spans=[s for s in engine if s.end_ns > lo and s.start_ns < hi],
        joined=joined,
        unjoined=len(launches) - len(joined),
        clock_offset_ns=offset,
        callback_bound_ns=bound,
        impossible=impossible,
        busy=busy,
        host=host,
    )
