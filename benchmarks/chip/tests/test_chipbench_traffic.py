"""The open-loop generator: seeded, clipped, and the same work for
every seed."""
import json
from collections import Counter

import numpy as np
import pytest

from chipbench_helpers import BENCH
from benchmarks.chip.cells import load_module
from benchmarks.chip.harness import bucket_of, prompt_buckets

gen = load_module(BENCH / "generators" / "open_loop.py")


def _traffic(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["chat-overload", "code-completion"])
def test_same_seed_same_schedule(name):
    t = _traffic(name)
    a = gen.generate(t, 2**31 + 11, 10, 1000)
    b = gen.generate(t, 2**31 + 11, 10, 1000)
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, b))


@pytest.mark.parametrize("name", ["chat-overload", "code-completion"])
def test_seeds_reorder_the_same_work(name):
    t = _traffic(name)
    a = gen.generate(t, 1, 10, 1000)
    b = gen.generate(t, 2, 10, 1000)
    assert [r["due_s"] for r in a] != [r["due_s"] for r in b]
    for key in ("n_out",):
        assert Counter(r[key] for r in a) == Counter(r[key] for r in b)
    assert Counter(len(r["prompt"]) for r in a) == Counter(len(r["prompt"]) for r in b)
    assert a[-1]["due_s"] == pytest.approx(b[-1]["due_s"])


@pytest.mark.parametrize("name", ["chat-overload", "code-completion"])
def test_lengths_clipped_and_fit_the_cache(name):
    t = _traffic(name)
    reqs = gen.generate(t, 5, 30, 1000)
    p, o = t["prompt_tokens"], t["output_tokens"]
    for r in reqs:
        assert p["min"] <= len(r["prompt"]) <= p["max"]
        assert o["min"] <= r["n_out"] <= o["max"]
        assert len(r["prompt"]) + r["n_out"] <= t["engine"]["max_len"]
        assert (r["prompt"] >= 0).all() and (r["prompt"] < 1000).all()
    lens = np.array([len(r["prompt"]) for r in reqs])
    assert abs(np.median(lens) - p["median"]) / p["median"] < 0.1


def test_arrival_rate():
    t = {"arrivals": {"rate_per_s": 10.0}, "lead_s": 0,
         "prompt_tokens": {"dist": "fixed", "value": 4},
         "output_tokens": {"dist": "uniform", "min": 1, "max": 3}}
    reqs = gen.generate(t, 3, 100, 50)
    assert len(reqs) == 1000
    assert reqs[-1]["due_s"] == pytest.approx(100, rel=0.05)
    due = np.array([r["due_s"] for r in reqs])
    assert (np.diff(due) > 0).all()
    assert {r["n_out"] for r in reqs} == {1, 2, 3}


def test_prompt_buckets_cover_the_traffic():
    t = _traffic("code-completion")
    b = prompt_buckets(t)
    assert b == (256, 512, 1024, 2048, 4096)
    assert bucket_of(b, 1537) == 2048 and bucket_of(b, 256) == 256
    assert prompt_buckets(_traffic("chat-overload")) == (32, 64, 128, 256, 512, 1024)
