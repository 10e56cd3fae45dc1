"""Entry points: the serve launcher called in-process, the benchmark
harness's exit code, the compile-cache location, and the rule that a TPU
never runs an interpreted kernel."""
import json
from pathlib import Path

import pytest

import jax

from repro.kernels import common
from repro.launch import compile_cache, serve

SMOKE = [
    "--arch", "olmo-1b", "--smoke", "--requests", "3", "--max-batch", "2",
    "--max-new", "4", "--prefill-buckets", "16",
]


@pytest.fixture
def no_cache(monkeypatch, tmp_path):
    # JAX read its cache dir at import; pointing the variable elsewhere
    # now only makes enable_compile_cache() leave the config alone
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path / "jax_cache"))


def test_serve_main_takes_argv(no_cache, capsys):
    assert serve.main(SMOKE) == 0
    stats = json.loads(capsys.readouterr().out)
    assert stats["completed"] == 3.0
    assert stats["warmup_s"] > 0
    assert stats["retraces_after_warmup"] == 0.0


def test_serve_run_returns_drained_engine():
    engine = serve.run(SMOKE + ["--decode-kernels"])
    assert len(engine.completed) == 3
    assert all(len(r.out_tokens) == 4 for r in engine.completed)
    assert engine.stats()["retraces_after_warmup"] == 0.0
    assert "HloModule" in engine.compiled_decode_block(1).as_text()


def test_bench_harness_runs_all_and_fails_on_error(no_cache, monkeypatch, capsys):
    from benchmarks import run as bench

    ran = []

    def boom(fast):
        ran.append("boom")
        raise RuntimeError("bench failed")

    monkeypatch.setattr(
        bench, "BENCHES", {"boom": boom, "fine": lambda fast: ran.append("fine")}
    )
    assert bench.main(["--fast"]) == 1
    assert ran == ["boom", "fine"]
    assert "boom,-1.0,ERROR:RuntimeError" in capsys.readouterr().out
    monkeypatch.setattr(bench, "BENCHES", {"fine": lambda fast: None})
    assert bench.main([]) == 0


def test_compile_cache_env_wins(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_and_ignored(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.delenv(compile_cache.ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    root = Path(__file__).resolve().parents[1]
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        cc.reset_cache()
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()


def test_interpreted_kernels_refused_on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("REPRO_KERNEL_INTERPRET", raising=False)
    common.require_compiled_kernels()            # compiled: fine
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "1")
    with pytest.raises(RuntimeError, match="REPRO_KERNEL_INTERPRET"):
        common.require_compiled_kernels()
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    common.require_compiled_kernels()            # the interpreter is CPU's
