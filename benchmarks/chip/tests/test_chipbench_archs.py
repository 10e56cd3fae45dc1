"""Architecture modules (``archs/``): the dense module reads exactly as the
benchmark read before architectures were modules, a new architecture
runs from new files alone, and a configuration naming a module that does
not exist, or lacks part of the contract, is refused when its cell is
resolved."""
import hashlib
import json
import math
from unittest import mock

import jax
import numpy as np
import pytest

from chipbench_helpers import (BENCH, DATA, config, launch_run, new_cell_root,
                               program_shapes, run_on_cpu)
from benchmarks.chip import cells
from benchmarks.chip.cells import load_module
from benchmarks.chip.weights import check_layout, make_weights

dense = load_module(BENCH / "archs" / "dense.py")
sq_relu = load_module(DATA / "arch_sq_relu.py")

POSITIONS = ([0], [255, 1023], [1535] * 16)

# computed by the benchmark before its architectures were modules
PARENT = {
    "olmo-1b": {
        "program_changes": {},
        "param_count": 1_176_764_416,
        "prefill_flops": {1: 2353659904, 256: 554273603584, 1536: 3453460414464,
                          3968: 9553547100160},
        "decode_flops": [2353659904, 4874829824, 40877686784],
        "decode_bytes": [2353790976, 2521563136, 5576851456],
    },
    "starcoder2-15b": {
        "program_changes": {"n_layers": 4},
        "param_count": 2_139_381_760,
        "prefill_flops": {1: 3674308608, 256: 789816803328, 1536: 4832517685248,
                          3968: 12957373169664},
        "decode_flops": [3674308608, 7474249728, 61203283968],
        "decode_bytes": [3674800128, 3685285888, 3876241408],
    },
}

# small-olmo, seed 7: float64 sum and sha256 (first 16 hex digits) of each leaf
PARENT_WEIGHTS = {
    "['embed']": (20.933886321073093, "6e0c1ea82dbb3d2f"),
    "['layers']['attn']['wk']": (-11.921248581412335, "e236cf44091ad8e4"),
    "['layers']['attn']['wo']": (-4.214064930085684, "7bc201c4463cd863"),
    "['layers']['attn']['wq']": (-2.0932977428191037, "f794f77687df2a8e"),
    "['layers']['attn']['wv']": (-11.041122473120259, "23d2926af314e914"),
    "['layers']['mlp']['w_down']": (38.75749211008538, "9f4bc6885a392b94"),
    "['layers']['mlp']['w_gate']": (20.550837519478687, "233baae3fb915ed9"),
    "['layers']['mlp']['w_up']": (26.285535248439714, "6d5afa8f12a3af1a"),
}

# small-olmo, seed 7: the reference's and the control's gaps at 24 served
# tokens after a 40-token prompt, both drawn from default_rng(20260101)
PARENT_GAPS = [
    1.287265658378601, 1.217801570892334, 2.5163278579711914, 1.8729650974273682,
    2.6034717559814453, 1.4721719026565552, 1.153912901878357, 1.9236129522323608,
    1.9164910316467285, 1.4677398204803467, 1.2689685821533203, 2.1565945148468018,
    1.6693687438964844, 1.7917526960372925, 1.6669210195541382, 1.6664717197418213,
    1.0280115604400635, 1.5605316162109375, 1.9953346252441406, 2.0687575340270996,
    1.4972999095916748, 1.1992342472076416, 2.3219752311706543, 0.8682888150215149,
]
PARENT_CONTROL_GAPS = [
    0.036531805992126465, 0.015102505683898926, 0.06677031517028809, 0.014526844024658203,
    0.0, 0.07310748100280762, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.060334205627441406,
    0.0, 0.0, 0.0, 0.0, 0.0916820764541626, 0.0, 0.0, 0.0, 0.0, 0.0,
]


# ------------------------------------------------------ the dense path ----


@pytest.mark.parametrize("name", sorted(PARENT))
def test_dense_program_config_is_the_parents(name):
    import dataclasses

    from repro.configs import get_config

    conf = config(name)
    want = dataclasses.replace(get_config(conf["arch"]), **PARENT[name]["program_changes"])
    assert dense.program_config(conf) == want


@pytest.mark.parametrize("name", sorted(PARENT))
def test_dense_counts_are_the_parents(name):
    m, want = dense.dims(config(name)), PARENT[name]
    assert dense.param_count(m) == want["param_count"]
    assert {n: dense.prefill_flops(m, n) for n in want["prefill_flops"]} == want["prefill_flops"]
    assert [dense.decode_flops(m, p) for p in POSITIONS] == want["decode_flops"]
    assert [dense.decode_bytes(m, p) for p in POSITIONS] == want["decode_bytes"]


def _small_olmo_weights():
    conf = config("small-olmo")
    m = dense.dims(conf)
    return conf, m, make_weights(dense.layout(m), 7)


def test_dense_weights_are_the_parents_bits():
    _, _, w = _small_olmo_weights()
    got = {}
    for path, a in jax.tree_util.tree_leaves_with_path(w):
        a = np.asarray(a)
        got[jax.tree_util.keystr(path)] = (float(a.astype(np.float64).sum()),
                                           hashlib.sha256(a.tobytes()).hexdigest()[:16])
    assert got == PARENT_WEIGHTS


def test_dense_reference_gives_the_parents_gaps():
    conf, m, w = _small_olmo_weights()
    rng = np.random.default_rng(20260101)
    prompt = rng.integers(0, conf["vocab_size"], 40).astype(np.int32)
    served = rng.integers(0, conf["vocab_size"], 24).astype(np.int32)
    g, cg = dense.logit_gaps(m, conf, w, prompt, served, control=True)
    assert [float(x) for x in g] == PARENT_GAPS
    assert [float(x) for x in cg] == PARENT_CONTROL_GAPS


# ------------------------------------- a new architecture, new files only ----


def test_a_new_architecture_needs_only_new_files(tmp_path):
    root = new_cell_root(tmp_path, config="tiny-sqrelu", traffic="small-chat",
                         arch="arch_sq_relu")
    cell = cells.resolve(root, "tiny-sqrelu.small-chat")
    assert cell.arch.__file__ == str(root / "benchmarks/chip/archs/arch_sq_relu.py")
    out = run_on_cpu(root, "tiny-sqrelu.small-chat", 2**31 + 29, 4.0)
    assert out["correct"] is True
    assert out["checks"]["retraces_max"]["value"] == 0
    assert out["attempted"] > 10


def test_a_new_architecture_counts_the_programs_parameters():
    conf = config("tiny-sqrelu")
    m = sq_relu.dims(conf)
    shapes = program_shapes(sq_relu, conf)
    check_layout(jax.eval_shape(lambda: make_weights(sq_relu.layout(m), 7)), shapes)
    assert sq_relu.param_count(m) == sum(a.size for a in jax.tree.leaves(shapes))


def test_every_reader_reads_a_new_architecture_as_it_reads_dense():
    names = {"admit": "jit__admit_impl(1)", "decode": "jit__decode_block_impl(2)",
             "read": "jit_dynamic_slice"}
    programs = {"jit__admit_impl", "jit__decode_block_impl"}
    olmo = launch_run(programs, names)
    sq = launch_run(programs, names, arch="arch_sq_relu", config="tiny-sqrelu")
    for path in sorted((BENCH / "metrics").glob("*.py")):
        read = load_module(path).read
        a, b = read(olmo), read(sq)
        assert (a is None) == (b is None), path.name
        assert b is None or (math.isfinite(b) and b > 0), path.name


def test_the_dense_module_cannot_serve_a_new_architecture():
    conf = config("tiny-sqrelu")
    m = sq_relu.dims(conf)
    # its layout is not the program's for this configuration
    with pytest.raises(ValueError):
        check_layout(jax.eval_shape(lambda: make_weights(dense.layout(dense.dims(conf)), 7)),
                     program_shapes(sq_relu, conf))
    # and its reference, handed these weights, looks for the GELU's bias
    w = make_weights(sq_relu.layout(m), 7)
    prompt, served = np.arange(1, 17, dtype=np.int32), np.arange(20, 28, dtype=np.int32)
    with pytest.raises(KeyError):
        dense.logit_gaps(dense.dims(conf), conf, w, prompt, served)


def test_the_float8_control_of_a_new_architecture_is_not_correct(tmp_path):
    root = new_cell_root(tmp_path, config="tiny-sqrelu", traffic="small-chat",
                         arch="arch_sq_relu")
    out = run_on_cpu(root, "tiny-sqrelu.small-chat", 2**31 + 31, 4.0, control=True)
    limit = out["checks"]["logit_gap_max"]["limit"]
    assert out["program_logit_gap_max"] <= limit
    assert out["checks"]["logit_gap_max"]["value"] > limit
    assert out["failed"] >= 1
    assert out["correct"] is False


# --------------------------------------------------- an unknown module ----


@pytest.mark.parametrize("module", [None, "def dims(conf):\n    return None\n"],
                         ids=["missing", "incomplete"])
def test_an_unknown_architecture_module_is_refused(tmp_path, module):
    root = new_cell_root(tmp_path)
    path = root / "benchmarks/chip/configs/tiny-olmo.json"
    conf = json.loads(path.read_text())
    conf["bench_arch"] = "no_such_arch"
    path.write_text(json.dumps(conf))
    if module is not None:
        (root / "benchmarks/chip/archs/no_such_arch.py").write_text(module)
    made = mock.Mock(side_effect=AssertionError("weights were made"))
    with mock.patch("benchmarks.chip.harness.make_weights", made), \
            pytest.raises((FileNotFoundError, AttributeError), match="tiny-olmo.json"):
        run_on_cpu(root, "tiny-olmo.tiny-chat", 2**31 + 37, 1.0)
    assert not made.called
