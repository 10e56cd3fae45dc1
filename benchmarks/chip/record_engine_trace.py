"""Record the small engine trace that the engine-span tests read.

    python3 benchmarks/chip/record_engine_trace.py <out_dir>

A serving engine at olmo-1b's layout and a test's size admits prompts of
known lengths (``FIRST``, then ``LATE`` after a host sleep) and decodes
them, one ``bench.step`` host span per engine step inside a
``bench.window`` span.  The trace then holds the engine's ``serve.*``
spans with their args, its admission and decode-block launches, and
idle time both inside its steps and outside them
(``bench.wait_for_arrival``).  The trace is written to
``<out_dir>/engine_steps.xplane.pb`` without what neither ``trace`` nor
``engine_trace`` reads (``strip``), and the tests keep that file in
``tests/data/``.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]

# (prompt tokens, output tokens) of each request; buckets 16 and 32
FIRST = ((5, 6), (9, 6), (12, 4), (20, 8))
LATE = ((7, 4), (30, 4))
BUCKETS = (16, 32)


# XSpace / XPlane / XLine / XEvent / XEventMetadata field numbers
# (tsl/profiler/protobuf/xplane.proto)
SPACE_PLANE = 1
PLANE_NAME, PLANE_LINE, PLANE_EVENT_META = 2, 3, 4
LINE_NAME, LINE_EVENT = 2, 4
EVENT_STATS = 4
META_ID, META_NAME = 1, 2
DEVICE_LINES = ("XLA Modules", "XLA Ops")
NAME_BYTES = 48


def _varint(b: bytes, i: int):
    out = shift = 0
    while True:
        x = b[i]
        i += 1
        out |= (x & 0x7F) << shift
        shift += 7
        if x < 0x80:
            return out, i


def _fields(b: bytes):
    """``(number, wire type, raw bytes of the whole field, payload)``."""
    i = 0
    while i < len(b):
        start = i
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            _, i = _varint(b, i)
            payload = None
        elif wire == 1:
            i += 8
            payload = None
        elif wire == 5:
            i += 4
            payload = None
        elif wire == 2:
            n, i = _varint(b, i)
            payload, i = b[i:i + n], i + n
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, wire, b[start:i], payload


def _bytes_field(number: int, payload: bytes) -> bytes:
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    return varint(number << 3 | 2) + varint(len(payload)) + payload


def _name(message: bytes, number: int) -> str:
    return next((p.decode() for f, w, _, p in _fields(message) if f == number and w == 2), "")


def _without(message: bytes, number: int) -> bytes:
    return b"".join(raw for f, _, raw, _ in _fields(message) if f != number)


def _device_plane(plane: bytes) -> bytes:
    out = bytearray()
    for f, _, raw, p in _fields(plane):
        if f == PLANE_LINE:
            name = _name(p, LINE_NAME)
            if name not in DEVICE_LINES:
                continue
            if name == "XLA Ops":       # of op events only the times are read
                raw = _bytes_field(f, b"".join(
                    _bytes_field(lf, _without(lp, EVENT_STATS)) if lf == LINE_EVENT else lraw
                    for lf, _, lraw, lp in _fields(p)))
        elif f == PLANE_EVENT_META:     # a map entry: key (1), XEventMetadata (2)
            key = b"".join(mraw for mf, _, mraw, _ in _fields(p) if mf == 1)
            meta = next(mp for mf, _, _, mp in _fields(p) if mf == 2)
            kept = b"".join(
                _bytes_field(META_NAME, mp[:NAME_BYTES]) if mf == META_NAME else mraw
                for mf, _, mraw, mp in _fields(meta) if mf in (META_ID, META_NAME))
            raw = _bytes_field(f, key + _bytes_field(2, kept))
        out += raw
    return bytes(out)


def strip(space: bytes) -> bytes:
    """The trace without what the reductions never read: the programs'
    metadata plane, the device lines other than programs and ops, the
    ops' event stats, and the device event metadata's stats, with its
    names cut to ``NAME_BYTES`` (program names stay whole)."""
    out = bytearray()
    for f, _, raw, p in _fields(space):
        if f == SPACE_PLANE:
            name = _name(p, PLANE_NAME)
            if name == "/host:metadata":
                continue
            if name.startswith("/device:"):
                raw = _bytes_field(f, _device_plane(p))
        out += raw
    return bytes(out)


def engine():
    import jax

    from repro.configs import get_config, smoke_variant
    from repro.models import api as model_api
    from repro.runtime.serving import ServeConfig, ServingEngine

    cfg = smoke_variant(get_config("olmo-1b"))
    params = model_api.get_api(cfg).init_params(cfg, jax.random.PRNGKey(0))
    return cfg, ServingEngine(cfg, params, ServeConfig(
        max_batch=4, max_len=64, max_new_tokens=8, prefill_buckets=BUCKETS,
        max_decode_block=8,
    ))


def main(out_dir: str) -> int:
    import jax

    cfg, eng = engine()
    eng.warmup()
    # the engine reads a finished request's tokens with one program per
    # output length: compile those before the trace
    for _, n in FIRST + LATE:
        np.asarray(eng._state["out_buf"][0, :n])
    rng = np.random.default_rng(0)

    def submit(reqs):
        for length, n_out in reqs:
            eng.submit(rng.integers(0, cfg.vocab, length).astype(np.int32), n_out)

    def drain():
        while eng.pending or eng.active:
            with jax.profiler.TraceAnnotation("bench.step"):
                eng.step()

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    submit(FIRST)
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        drain()
        with jax.profiler.TraceAnnotation("bench.wait_for_arrival"):
            time.sleep(0.005)
        submit(LATE)
        drain()
    jax.profiler.stop_trace()
    recorded = sorted(Path(out_dir).glob("plugins/profile/*/*.xplane.pb"))[-1]
    (Path(out_dir) / "engine_steps.xplane.pb").write_bytes(strip(recorded.read_bytes()))
    return 0


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src")]
    raise SystemExit(main(sys.argv[1]))
