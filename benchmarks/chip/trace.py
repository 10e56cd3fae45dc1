"""Reduction of a profiler trace to device time.

The benchmark records a trace with ``jax.profiler`` around part of its
window and marks that part with a host span named ``WINDOW``.  This
module reads the ``.xplane.pb`` with ``jax.profiler.ProfileData`` and
gives, for the window:

- busy time per device: the union of the intervals in which an
  operation ran (the device's ``XLA Ops`` line);
- device time per program (the ``XLA Modules`` line), in launch order,
  so a caller can attribute each launch to the call that made it: every
  launch in the trace, since the device's clock in a trace lags the
  host's by about a millisecond (the recorded v5e trace's first launch
  reads before the window span that its dispatch lies in), so a launch
  at the window's edge would otherwise be lost;
- time in collective operations;
- the longest idle gaps, each named after the innermost host span that
  covers its middle (what the host was doing while the device waited).

Host and device events of one trace share its clock, up to the device's
lag.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW = "bench.window"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"allreduce|allgather|reducescatter",
    re.IGNORECASE,
)

Interval = Tuple[int, int]


@dataclasses.dataclass
class Launch:
    name: str
    start_ns: int
    end_ns: int

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


@dataclasses.dataclass
class Reduction:
    window_ns: Tuple[int, int]               # the window span, trace clock
    busy_s: Dict[str, float]                 # device -> busy seconds
    launches: Dict[str, List[Launch]]        # device -> every program, in order
    collective_s: Dict[str, float]
    top_ops: List[Tuple[str, float]]         # all devices, longest first
    idle_gaps: List[Tuple[str, float]]       # first device, longest first

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / max(len(self.busy_s), 1)


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _clip(s: int, e: int, lo: int, hi: int) -> Optional[Interval]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _events(line):
    for ev in line.events:
        s = int(ev.start_ns)
        yield ev.name, s, s + int(ev.duration_ns)


def reduce(xplane: Path, min_gap_s: float = 1e-4, top: int = 10) -> Reduction:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xplane))
    host: List[Tuple[str, int, int]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(ev for ev in _events(line) if ev[2] > ev[1])
        elif plane.name.startswith("/device:TPU:") and plane.name[12:].isdigit():
            devices.append(plane)
    spans = [h for h in host if h[0] == WINDOW]
    if not spans:
        raise ValueError(f"the trace has no {WINDOW!r} host span")
    lo, hi = spans[0][1], spans[0][2]

    busy: Dict[str, float] = {}
    launches: Dict[str, List[Launch]] = {}
    coll: Dict[str, float] = {}
    op_time: Dict[str, float] = {}
    first_busy: List[Interval] = []
    for plane in sorted(devices, key=lambda p: int(p.name[12:])):
        ops: List[Interval] = []
        coll_iv: List[Interval] = []
        mods: List[Launch] = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                for name, s, e in _events(line):
                    iv = _clip(s, e, lo, hi)
                    if iv is None:
                        continue
                    ops.append(iv)
                    op_time[name] = op_time.get(name, 0.0) + (iv[1] - iv[0]) * 1e-9
                    if COLLECTIVE.search(name):
                        coll_iv.append(iv)
            elif line.name == "XLA Modules":
                mods.extend(Launch(n, s, e) for n, s, e in _events(line))
        merged = union(ops)
        busy[plane.name] = sum(e - s for s, e in merged) * 1e-9
        coll[plane.name] = sum(e - s for s, e in union(coll_iv)) * 1e-9
        launches[plane.name] = sorted(mods, key=lambda m: m.start_ns)
        if not first_busy:
            first_busy = merged or [(lo, lo)]
    gaps = _idle_gaps(first_busy, lo, hi, host, int(min_gap_s * 1e9))
    return Reduction(
        window_ns=(lo, hi),
        busy_s=busy,
        launches=launches,
        collective_s=coll,
        top_ops=sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=gaps[:top],
    )


def _idle_gaps(busy: List[Interval], lo: int, hi: int, host, min_gap: int):
    """Gaps between busy intervals inside [lo, hi], longest first, each
    named after the shortest host span (other than the window's) that
    covers the gap's middle."""
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = []
    for s, e in zip(edges[0::2], edges[1::2]):
        if e - s < min_gap:
            continue
        mid = (s + e) // 2
        cover = [h for h in host if h[1] <= mid <= h[2] and h[0] != WINDOW]
        name = min(cover, key=lambda h: h[2] - h[1])[0] if cover else "(no host span)"
        gaps.append((name, (e - s) * 1e-9))
    return sorted(gaps, key=lambda g: -g[1])
