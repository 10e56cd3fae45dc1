"""The serving engine's instruments on its device path: program names a
trace can tell apart, cumulative counters in ``stats()``, and the
``serve.*`` host spans with their args in a profiler trace."""
import glob

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis.sanitize import TraceCounter
from repro.configs import get_config, smoke_variant
from repro.models import api as model_api
from repro.runtime.serving import ServeConfig, ServingEngine

_PARAMS = {}


def _engine(**kw):
    cfg = smoke_variant(get_config("olmo-1b"))
    if "p" not in _PARAMS:
        _PARAMS["p"] = model_api.get_api(cfg).init_params(cfg, jax.random.PRNGKey(0))
    conf = dict(max_batch=4, max_len=64, max_new_tokens=6, prefill_buckets=(16, 32), seed=0)
    conf.update(kw)
    return cfg, ServingEngine(cfg, _PARAMS["p"], ServeConfig(**conf))


def _submit(eng, cfg, reqs, seed=0):
    rng = np.random.default_rng(seed)
    for length, n_out in reqs:
        eng.submit(rng.integers(0, cfg.vocab, length).astype(np.int32), n_out)


def test_wrapped_function_keeps_its_name_and_static_args():
    tc = TraceCounter(("k",))

    def block_impl(x, n):
        return x * n

    f = tc.jit(block_impl, kind="k", static_argnums=1)
    assert f.__name__ == "block_impl"
    assert f(jnp.ones(2), 3).tolist() == [3.0, 3.0]
    f(jnp.ones(2), 3)
    assert tc.counts["k"] == 1                  # compiled once for n=3
    f(jnp.ones(2), 4)
    assert tc.counts["k"] == 2
    assert "module @jit_block_impl" in f.lower(jnp.ones(2), 3).as_text()


def test_engine_programs_lower_under_their_own_names():
    _, eng = _engine()
    S, nb = 16, 1
    admit = eng._admit_block.lower(
        eng.params, eng._cache, eng._state, jnp.zeros((nb, S), jnp.int32),
        jnp.ones((nb,), jnp.int32), jnp.zeros((nb,), jnp.int32), jnp.ones((nb,), jnp.int32),
    )
    decode = eng._decode_block.lower(eng.params, eng._cache, eng._state, 2)
    assert "module @jit__admit_impl" in admit.as_text()
    assert "module @jit__decode_block_impl" in decode.as_text()


@pytest.mark.parametrize("reqs, counts", [
    # 5, 9, 12 share bucket 16 in one admission of 3 rows padded to 4;
    # 20 is alone in bucket 32
    (((5, 6), (9, 6), (12, 4), (20, 8)),
     {16: (3, 4, 26), 32: (1, 1, 20)}),
    # two of bucket 16 fill two rows exactly; 30 + 31 share bucket 32
    (((16, 3), (1, 3), (30, 5), (31, 5)),
     {16: (2, 2, 17), 32: (2, 2, 61)}),
])
def test_admission_counters_are_exact(reqs, counts):
    cfg, eng = _engine()
    eng.warmup()
    _submit(eng, cfg, reqs)
    eng.step()
    assert eng.prefill_tokens == sum(t for _, _, t in counts.values())
    assert eng.prefill_tokens_computed == sum(b * p for b, (_, p, _) in counts.items())
    # every request of one admission keeps the same record of it
    by_bucket = {}
    for r in list(eng._slots) + eng.completed:
        if r is not None:
            by_bucket.setdefault(r.admission.bucket, set()).add(id(r.admission))
            a = r.admission
            assert (a.n, a.n_pad, a.tokens) == counts[a.bucket]
    assert {b: len(ids) for b, ids in by_bucket.items()} == {b: 1 for b in counts}
    # one decode block ran, over every lane still active
    assert eng.decode_blocks == 1
    assert eng.busy_lane_rounds == eng.rounds * len(reqs)
    stats = eng.stats()
    for key in ("prefill_tokens", "prefill_tokens_computed", "decode_blocks",
                "busy_lane_rounds"):
        assert stats[key] == getattr(eng, key)
    assert {k for k in stats if k.startswith("prefill_s_bucket")} == {
        f"prefill_s_bucket{b}" for b in counts}
    assert all(stats[f"prefill_s_bucket{b}"] > 0 for b in counts)


def test_prefill_timing_does_not_grow_with_requests():
    cfg, eng = _engine(max_batch=2)
    eng.warmup()
    for k in range(3):
        _submit(eng, cfg, [(5, 2), (20, 2)], seed=k)
        eng.run_until_drained()
    assert eng.prefill_calls == {16: 3, 32: 3}
    assert set(eng.prefill_seconds) == {16, 32}
    means = eng.prefill_s_by_bucket()
    assert means[16] == pytest.approx(eng.prefill_seconds[16] / 3)
    assert eng.stats()["prefill_s_bucket32"] == pytest.approx(means[32])
    assert eng.prefill_tokens == 3 * 25
    assert eng.prefill_tokens_computed == 3 * (16 + 32)


def _spans(log_dir):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(sorted(glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb"))[-1])
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    s = int(ev.start_ns)
                    args = {k: v for k, v in dict(ev.stats).items() if not k.startswith("_")}
                    out.append((ev.name, s, s + int(ev.duration_ns), args))
    return sorted(out, key=lambda x: x[1])


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_cpu_trace_holds_nested_engine_spans(tmp_path):
    cfg, eng = _engine()
    eng.warmup()
    # six tokens each: a first block of 4 rounds, then one of 1 round
    # after which every request drains
    reqs = ((5, 6), (9, 6), (12, 6), (20, 6))
    np.asarray(eng._state["out_buf"][0, :6])
    _submit(eng, cfg, reqs)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.step()
        eng.step()
    finally:
        jax.profiler.stop_trace()
    spans = _spans(tmp_path)
    steps = [s for s in spans if s[0] == "serve.step"]
    assert [s[3] for s in steps] == [{"queue": 4, "lanes": 0}, {"queue": 0, "lanes": 4}]
    inner = [s for s in spans if s[0] != "serve.step"]

    def within(k):
        return [s for s in inner if steps[k][1] <= s[1] and s[2] <= steps[k][2]]

    first, second = within(0), within(1)
    assert len(first) + len(second) == len(inner)
    assert [s[0] for s in first] == ["serve.admit", "serve.admit_sync"] * 2 + [
        "serve.decode_block", "serve.block_sync"]
    assert [s[0] for s in second] == ["serve.decode_block", "serve.block_sync"] + [
        "serve.drain"] * 4
    assert [s[3] for s in first if s[0] == "serve.admit"] == [
        {"bucket": 16, "n": 3, "n_pad": 4, "tokens": 26},
        {"bucket": 32, "n": 1, "n_pad": 1, "tokens": 20}]
    assert [s[3] for s in inner if s[0] == "serve.decode_block"] == [
        {"rounds": 4, "lanes": 4}, {"rounds": 1, "lanes": 4}]
    assert [s[3] for s in second if s[0] == "serve.drain"] == [{"tokens": 6}] * 4
    assert all(s[3] == {} for s in inner if s[0].endswith("_sync"))
    # spans overlap only by nesting
    for x in spans:
        for y in spans:
            if x is not y and x[1] < y[1] < x[2]:
                assert y[2] <= x[2], (x[0], y[0])
