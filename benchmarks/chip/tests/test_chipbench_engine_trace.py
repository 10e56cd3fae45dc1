"""The engine's spans joined to device launches (``engine_trace.py``) on
traces recorded on a TPU v5e, the launch metrics under the engine's
program names, and the ``prefill_pad_share`` reader."""
from types import SimpleNamespace
from unittest import mock

import numpy as np
import pytest

from chipbench_helpers import BENCH, DATA, new_cell_root
from benchmarks.chip import cells, engine_trace, harness, record_engine_trace, trace, work
from benchmarks.chip.cells import load_module

TWO_PROGRAMS = DATA / "two_programs.xplane.pb"
ENGINE_STEPS = DATA / "engine_steps.xplane.pb"
LAUNCH_METRICS = ("prefill_share", "decode_round_ms", "prefill_ms_per_ktok",
                  "decode_hbm_roofline", "prefill_flop_roofline", "decode_step_mfu")


@pytest.fixture(scope="module")
def two():
    return engine_trace.read(TWO_PROGRAMS)


@pytest.fixture(scope="module")
def steps():
    return engine_trace.read(ENGINE_STEPS)


def test_every_launch_joins_by_run_id(two):
    assert len(two.joined) == 8 and two.unjoined == 0
    assert [j.run_id for j in two.joined] == list(range(5, 13))
    # no engine span in this trace
    assert all(j.span is None for j in two.joined)


def test_clock_offset_and_its_callback_bound(two):
    assert two.clock_offset_ns * 1e-6 == pytest.approx(1.543, abs=0.01)
    assert two.callback_bound_ns * 1e-6 == pytest.approx(1.737, abs=0.01)
    assert two.impossible == 0
    # aligned, every launch starts at or after its enqueue
    assert all(j.launch.start_ns + two.clock_offset_ns >= j.enqueue_ns for j in two.joined)


@pytest.mark.parametrize("path", [TWO_PROGRAMS, ENGINE_STEPS], ids=["two_programs", "engine_steps"])
def test_the_join_reads_what_the_reduction_reads(path):
    et, red = engine_trace.read(path), trace.reduce(path)
    assert et.window_ns == red.window_ns
    first = next(iter(red.launches.values()))
    assert [j.launch for j in et.joined] == first
    # the same busy time, up to what the shift moves across the window's edges
    busy = sum(e - s for s, e in et.busy) * 1e-9
    assert busy == pytest.approx(red.mean_busy_s, abs=2 * et.clock_offset_ns * 1e-9)


def test_stripping_a_trace_keeps_what_the_reductions_read(tmp_path):
    stripped = tmp_path / "two.xplane.pb"
    stripped.write_bytes(record_engine_trace.strip(TWO_PROGRAMS.read_bytes()))
    assert stripped.stat().st_size < TWO_PROGRAMS.stat().st_size
    a, b = trace.reduce(TWO_PROGRAMS), trace.reduce(stripped)
    assert (a.window_ns, a.busy_s, a.launches, a.idle_gaps) == (
        b.window_ns, b.busy_s, b.launches, b.idle_gaps)
    # op names are cut, so ops that share a prefix add up under one name
    assert sum(s for _, s in a.top_ops) == pytest.approx(sum(s for _, s in b.top_ops))
    a, b = engine_trace.read(TWO_PROGRAMS), engine_trace.read(stripped)
    assert (a.joined, a.busy, a.clock_offset_ns, a.callback_bound_ns, a.idle_gaps()) == (
        b.joined, b.busy, b.clock_offset_ns, b.callback_bound_ns, b.idle_gaps())


def test_an_impossible_callback_is_counted_and_not_used(tmp_path):
    # a callback that reads before its launch could have ended, given the
    # offset the enqueues set, bounds nothing
    class Ev(SimpleNamespace):
        pass

    def ev(name, s, d, **stats):
        return Ev(name=name, start_ns=s, duration_ns=d, stats=stats)

    line = lambda name, evs: SimpleNamespace(name=name, events=evs)
    host = SimpleNamespace(name="/host:CPU", lines=[line("python", [
        ev(trace.WINDOW, 0, 1000),
        ev("serve.step", 10, 500, queue=1, lanes=0),
        ev("serve.admit", 20, 100, bucket=16, n=3, n_pad=4, tokens=26),
        ev("serve.decode_block", 200, 50, rounds=4, lanes=3),
    ]), line("main", [
        ev("DoEnqueueProgram", 100, 5, run_id=1),
        ev("DoEnqueueProgram", 220, 5, run_id=2),
        ev("CompleteCallbacks", 230, 5, run_id=1),     # 30 after end, < offset
        ev("CompleteCallbacks", 400, 5, run_id=2),
    ])])
    dev = SimpleNamespace(name="/device:TPU:0", lines=[line("XLA Modules", [
        ev("jit__admit_impl(1)", 50, 150, run_id=1),
        ev("jit__decode_block_impl(2)", 210, 100, run_id=2),
    ]), line("XLA Ops", [ev("op", 60, 140), ev("op", 215, 90)])])
    with mock.patch("jax.profiler.ProfileData.from_file",
                    return_value=SimpleNamespace(planes=[host, dev])):
        et = engine_trace.read(tmp_path / "x.xplane.pb")
    assert et.clock_offset_ns == 50                  # 100 - 50 (launch 2 needs 10)
    assert et.impossible == 1                        # 230 - 200 = 30 < 50
    assert et.callback_bound_ns == 400 - 310
    assert [j.span.name for j in et.joined] == ["serve.admit", "serve.decode_block"]
    assert et.attributed() == 2 and et.order_agreement() == 2
    # busy on the host's clock: [110, 250] and [265, 355]; the step spans
    # [10, 510]: idle 100 + 15 + 155
    assert et.busy == [(110, 250), (265, 355)]
    assert et.device_idle_engine() == pytest.approx(100.0 * 270 / 1000)
    assert et.prefill_pad_share() == pytest.approx(100.0 * (1 - 26 / 64))
    # gaps [355, 1000], [0, 110], [250, 265], named at their middles
    gaps = et.idle_gaps(min_gap_ns=1)
    assert [n for n, _ in gaps] == ["(no host span)", "serve.admit", "serve.step"]
    assert [s for _, s in gaps] == pytest.approx([645e-9, 110e-9, 15e-9])


def test_engine_launches_join_to_their_dispatch_spans(steps):
    launches = steps.engine_launches()
    assert launches and steps.unjoined == 0
    kind = {"jit__admit_impl": "serve.admit", "jit__decode_block_impl": "serve.decode_block"}
    assert [j.span.name for j in launches] == [kind[j.launch.name.split("(")[0]] for j in launches]
    assert steps.attributed() == len(launches)
    assert steps.order_agreement() == len(launches)
    # the engine's enqueues wait for their inputs and run after the
    # dispatch span has closed: the flow back to the dispatch names it
    assert all(j.enqueue_ns > j.span.end_ns for j in launches)
    assert all(j.span.start_ns <= j.issued_ns <= j.span.end_ns for j in launches)
    # every other launch of the window (the token reads) lies in a drain
    others = [j for j in steps.joined if j not in launches
              and steps.window_ns[0] <= j.enqueue_ns <= steps.window_ns[1]]
    assert others and all(j.span.name == "serve.drain" for j in others)
    assert steps.clock_offset_ns > 0 and steps.impossible == 0
    if steps.callback_bound_ns is not None:
        assert steps.clock_offset_ns <= steps.callback_bound_ns


def test_engine_spans_carry_the_admissions(steps):
    admits = [s.args for s in steps.spans if s.name == "serve.admit"]
    first = [(16, 3, 4, 26), (32, 1, 1, 20)]
    late = [(16, 1, 1, 7), (32, 1, 1, 30)]
    assert [(a["bucket"], a["n"], a["n_pad"], a["tokens"]) for a in admits] == first + late
    reqs = record_engine_trace.FIRST + record_engine_trace.LATE
    tokens = sum(length for length, _ in reqs)
    assert steps.prefill_pad_share() == pytest.approx(100.0 * (1 - tokens / (64 + 32 + 16 + 32)))


def test_engine_idle_lies_inside_the_window_idle(steps):
    red = trace.reduce(ENGINE_STEPS)
    serve_idle = load_module(BENCH / "metrics" / "device_idle.serve.py").read(
        SimpleNamespace(trace=red))
    assert 0 < steps.device_idle_engine() <= steps.device_idle()
    assert steps.device_idle_engine() <= serve_idle
    # the host sleep between the two bursts is idle outside every step
    names = {n for n, _ in steps.idle_gaps()}
    assert "bench.wait_for_arrival" in names
    assert any(n.startswith("serve.") for n in names)


def _run(programs, names):
    """The by-order fixture of test_chipbench_harness, its launches named
    ``names[kind]``, at tiny-olmo's shapes on a v5e."""
    import json

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
    conf = json.loads((DATA / "tiny-olmo.json").read_text())
    return harness.ServeRun(None, work.dims(conf), {"bf16_flop_per_s": 197e12, "hbm_byte_per_s": 819e9},
                            4, (32, 64, 128), (0.0, 3.0), (0.0, 3.0), steps, recs, red,
                            programs=frozenset(programs))


@pytest.mark.parametrize("metric", LAUNCH_METRICS)
def test_launch_metrics_read_the_same_under_the_program_names(metric):
    read = load_module(BENCH / "metrics" / f"{metric}.py").read
    before = read(_run({"jit_traced"}, {"admit": "jit_traced(1)", "decode": "jit_traced(2)",
                                        "read": "jit__getitem"}))
    after = read(_run({"jit__admit_impl", "jit__decode_block_impl"},
                      {"admit": "jit__admit_impl(1)", "decode": "jit__decode_block_impl(2)",
                       "read": "jit_dynamic_slice"}))
    assert before is not None and after == before


def test_record_calls_names_the_engine_programs():
    from repro.configs import get_config, smoke_variant
    from repro.runtime.serving import ServeConfig, ServingEngine

    cfg = smoke_variant(get_config("olmo-1b"))
    eng = ServingEngine(cfg, None, ServeConfig(max_batch=2, max_len=32))
    assert harness._record_calls(eng, []) == {"jit__admit_impl", "jit__decode_block_impl"}


@pytest.mark.parametrize("keeps_admissions", [True, False])
def test_prefill_pad_share_reads_the_admissions(keeps_admissions):
    read = load_module(BENCH / "metrics" / "prefill_pad_share.py").read
    a16 = SimpleNamespace(bucket=16, n=3, n_pad=4, tokens=26)
    a32 = SimpleNamespace(bucket=32, n=1, n_pad=1, tokens=20)
    late = SimpleNamespace(bucket=16, n=1, n_pad=1, tokens=7)
    steps = [harness.Step(0.0, 1.0, 4), harness.Step(1.0, 2.0, 4), harness.Step(2.0, 3.0, 4)]

    def rec(first, adm):
        req = SimpleNamespace(first_token_at=first, done_at=None, out_tokens=[])
        if keeps_admissions:
            req.admission = adm
        return harness.Record(0, 0, 0, np.zeros(adm.tokens, np.int32), 8, req)

    recs = [rec(0.5, a16), rec(0.5, a16), rec(0.5, a16), rec(0.5, a32),
            rec(2.5, late)]                         # admitted after the traced part
    run = harness.ServeRun(None, None, None, 4, (16, 32), (0.0, 3.0), (0.0, 2.0), steps, recs)
    got = read(run)
    if keeps_admissions:
        assert got == pytest.approx(100.0 * (1 - 46 / 96))
    else:
        assert got is None


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_a_traced_cpu_run_reads_the_padding_from_its_spans(tmp_path):
    """A traced run of a new cell on the CPU: the reader's padding over
    the traced steps is the spans' padding over the window."""
    import json

    root = new_cell_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if m["name"] in ("prefill_pad_share", "lane_occupancy")]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cells.resolve(root, "tiny-olmo.tiny-chat")
    joined = []
    reduce = trace.reduce

    def reduce_and_join(xplane, *a, **kw):
        joined.append(engine_trace.read(xplane))
        return reduce(xplane, *a, **kw)

    with mock.patch.object(trace, "reduce", reduce_and_join):
        out = harness.serve(cell, 2**31 + 7, 2.0, True, 0.0, on_chip=False)
    assert out["correct"] is True
    got = out["metrics"]["prefill_pad_share"]["value"]
    assert 0 < got < 100
    assert got == pytest.approx(joined[0].prefill_pad_share())
    assert any(s.name == "serve.step" for s in joined[0].spans)
