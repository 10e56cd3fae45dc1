"""The harness on the CPU: a new cell from new files alone, time to first
token from the due time, the refusal of a host without a TPU, and the
matching of device launches to the engine's calls."""
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench_helpers import BENCH, REPO, new_cell_root, run_on_cpu
from benchmarks.chip import cells, harness, trace


def _rec(uid, due, submitted, first, done, n_out, prompt_len=8):
    req = SimpleNamespace(first_token_at=first, done_at=done,
                          out_tokens=[1] * (n_out if done is not None else 0))
    return harness.Record(uid, due, submitted, np.zeros(prompt_len, np.int32), n_out, req)


def test_ttft_counts_from_the_due_time():
    w0, w1 = 100.0, 110.0
    recs = [
        # due at 101, submitted at 102 (the loop was inside a step), first token 103
        _rec(0, 101.0, 102.0, 103.0, 104.0, 5),
        # due at 105, never admitted by the close: counts its wait so far
        _rec(1, 105.0, 105.0, None, None, 5),
        # due before the window opened: not among the window's requests
        _rec(2, 99.0, 99.0, 100.5, 101.0, 5),
    ]
    e2e = harness._end_to_end(recs, w0, w1, setup_s=7.0, tokens=30)
    assert e2e["attempted"] == 2
    ttft = sorted([2000.0, 5000.0])
    assert e2e["ttft_p95_ms"] == pytest.approx(np.percentile(ttft, 95))
    assert e2e["ttft_p50_ms"] == pytest.approx(3500.0)
    assert e2e["lateness_p95_ms"] == pytest.approx(np.percentile([1000.0, 0.0, 0.0], 95))
    # completed in the window: requests 0 and 2; tokens produced in the
    # window over the window, and those handed back, apart
    assert e2e["completed"] == 2
    assert e2e["output_tok_s"] == pytest.approx(30 / 10.0)
    assert e2e["completed_tok_s"] == pytest.approx(10 / 10.0)
    assert e2e["tpot_p95_ms"] == pytest.approx(np.percentile([250.0, 125.0], 95))
    assert e2e["setup_s"] == 7.0


def test_launches_are_matched_to_admissions_and_decode_blocks():
    # each step's calls, in order, as the harness records them
    steps = [harness.Step(0.0, 1.0, 4, [("admit", 0.05), ("decode", 0.25)]),
             harness.Step(1.0, 2.0, 2, [("admit", 1.05), ("decode", 1.45)]),
             harness.Step(2.0, 3.0, 8, [("decode", 2.05)])]
    recs = [_rec(0, 0, 0, 0.5, None, 20, prompt_len=30),     # step 0
            _rec(1, 0, 0, 0.6, None, 20, prompt_len=31),     # step 0
            _rec(2, 0, 0, 1.5, None, 20, prompt_len=100)]    # step 1
    L = lambda n, s, e: trace.Launch(n, int(s * 1e9), int(e * 1e9))
    # the device's clock lags the host's: the first launch reads before
    # the window span opened
    launches = [L("jit_traced(1)", -0.001, 0.2), L("jit_traced(2)", 0.3, 0.6),
                L("jit__getitem", 0.6, 0.61),
                L("jit_traced(1)", 1.1, 1.4), L("jit_traced(2)", 1.5, 1.7),
                L("jit_traced(2)", 2.1, 2.9)]
    red = trace.Reduction(window_ns=(0, int(3e9)), busy_s={"d0": 2.0},
                          launches={"d0": launches}, collective_s={}, top_ops=[], idle_gaps=[])
    run = harness.ServeRun(None, None, None, 4, (32, 64, 128), (0.0, 3.0), (0.0, 3.0),
                           steps, recs, red, programs=frozenset({"jit_traced"}))
    got = run.program_seconds()
    assert got["steps"] == [0, 1, 2]
    assert got["admit"] == pytest.approx(0.201 + 0.3)
    assert got["decode"] == pytest.approx(0.3 + 0.2 + 0.8)
    # the first call reads 51 ms after its launch: the device lags
    largest, median = run.device_lag_ms()
    assert largest == pytest.approx(51.0) and median == pytest.approx(-50.0)
    # request 0 decodes 4 rounds in step 0 from position 30, then 2 more
    assert run.lane_positions(1) == [[34, 35, 100], [35, 36, 101]]
    # a launch missing: the calls can no longer be told apart
    red.launches["d0"] = launches[:-1]
    assert run.program_seconds() is None
    # only the traced steps count
    red.launches["d0"] = launches[:-1]
    run.traced = (0.0, 2.0)
    assert run.program_seconds()["steps"] == [0, 1]
    assert run.program_seconds()["decode"] == pytest.approx(0.3 + 0.2)


def test_the_engine_calls_are_recorded_in_order():
    calls = []
    engine = SimpleNamespace(_admit_block=lambda *a: ("admitted", a),
                             _decode_block=lambda *a: ("decoded", a))
    names = harness._record_calls(engine, calls)
    assert names == {"jit_<lambda>"}
    assert engine._admit_block(1, 2) == ("admitted", (1, 2))
    assert engine._decode_block(3) == ("decoded", (3,))
    assert [k for k, _ in calls] == ["admit", "decode"]
    assert calls[0][1] <= calls[1][1]


def test_a_new_cell_needs_only_new_files(tmp_path):
    root = new_cell_root(tmp_path)
    cell = cells.resolve(root, "tiny-olmo.tiny-chat")
    assert cell.traffic["engine"]["max_batch"] == 4
    assert {m["name"] for m in cell.end_to_end} == {"output_tok_s", "tpot_p95_ms", "setup_s"}
    assert set(cells.readers(cell)) == {m["name"] for m in cell.per_layer}
    out = run_on_cpu(root, "tiny-olmo.tiny-chat", 2**31 + 3, 2.0)
    assert out["correct"] is True
    assert out["attempted"] > 10
    assert set(out["metrics"]) == {"output_tok_s", "tpot_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["checks"]["retraces_max"]["value"] == 0
    with pytest.raises(KeyError):
        cells.resolve(root, "no-such.cell")


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "olmo-1b.chat-overload",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def _no_result(proc):
    lines = [x for x in proc.stdout.splitlines() if x.strip()]
    return not any(x.lstrip().startswith("{") for x in lines)


def test_a_host_without_a_tpu_is_refused():
    proc = _run_py(REPO)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "refused" in proc.stderr


def test_the_benchmark_alone_does_not_run(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(tmp_path, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / ".jax_cache")})
    assert proc.returncode != 0
    assert _no_result(proc)


def test_benchmark_json_names_files_that_exist():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (REPO / c["file"]).is_file()
    for w in bench["workloads"]:
        cell = cells.resolve(REPO, w["name"])
        assert cell.traffic["generator"]
        cell.module("generators", cell.traffic["generator"])
        assert set(cells.readers(cell)) == {m["name"] for m in cell.per_layer}
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
