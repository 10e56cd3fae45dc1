"""Work counted from configuration shapes by the dense architecture
module, against hand counts and the program's own parameter shapes."""
import jax
import pytest

from chipbench_helpers import BENCH, config, program_shapes
from benchmarks.chip.cells import load_module
from benchmarks.chip.weights import check_layout, make_weights

dense = load_module(BENCH / "archs" / "dense.py")


def test_olmo_1b_param_count_by_hand():
    m = dense.dims(config("olmo-1b"))
    emb = 50304 * 2048
    layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    assert dense.param_count(m) == emb + 16 * layer == 1_176_764_416


@pytest.mark.parametrize("name", ["olmo-1b", "starcoder2-15b", "tiny-olmo"])
def test_param_count_equals_program_init(name):
    conf = config(name)
    shapes = program_shapes(dense, conf)
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert dense.param_count(dense.dims(conf)) == n


def test_starcoder2_cut_size():
    m = dense.dims(config("starcoder2-15b"))
    assert m.layers == 4 and m.kv_heads == 4 and m.head_dim == 128
    assert dense.kv_bytes_per_token(m) == 4 * 2 * 4 * 128 * 2 == 8192
    assert 2.13e9 < dense.param_count(m) < 2.15e9


@pytest.mark.parametrize("name", ["olmo-1b", "starcoder2-15b", "tiny-olmo"])
def test_weight_layout_matches_program(name):
    conf = config(name)
    mine = jax.eval_shape(lambda: make_weights(dense.layout(dense.dims(conf)), 7))
    check_layout(mine, program_shapes(dense, conf))


def test_layout_mismatch_is_refused():
    conf = config("tiny-olmo")
    mine = jax.eval_shape(lambda: make_weights(dense.layout(dense.dims(conf)), 7))
    mine["embed"] = jax.ShapeDtypeStruct((3, 3), mine["embed"].dtype)
    with pytest.raises(ValueError):
        check_layout(mine, program_shapes(dense, conf))


def test_flops_and_bytes_by_hand():
    m = dense.Dims(vocab=10, d=4, layers=2, heads=2, kv_heads=1, head_dim=2,
                  ff=8, gated=True, bias=False, norm_affine=False, tied=True)
    mat = 4 * 2 * (2 * 2 + 2 * 1) + 3 * 4 * 8       # 48 + 96 per layer
    assert dense.layer_matrix_params(m) == mat == 144
    # prompt of 3: linear 2*L*mat*n, causal attention 4*H*hd*(1+2+3) per layer
    assert dense.prefill_flops(m, 3) == 2 * 2 * 144 * 3 + 2 * 4 * 2 * 2 * 6 + 2 * 4 * 10
    # two lanes writing at positions 0 and 4 attend to 1 and 5 keys
    per_lane = 2 * (2 * 144 + 4 * 10)
    assert dense.decode_flops(m, [0, 4]) == 2 * per_lane + 2 * 4 * 2 * 2 * (1 + 5)
    kv = 2 * 2 * 1 * 2 * 2                              # per position, bf16
    assert dense.kv_bytes_per_token(m) == kv
    assert dense.decode_bytes(m, [0, 4]) == 2 * (2 * 144 + 40) + kv * (2 + 6)


def test_seeds_make_different_weights_and_a_seed_the_same():
    m = dense.dims(config("tiny-olmo"))
    a, b = make_weights(dense.layout(m), 2**31 + 5), make_weights(dense.layout(m), 2**31 + 5)
    c = make_weights(dense.layout(m), 6)
    assert (a["embed"] == b["embed"]).all()
    assert not (a["embed"] == c["embed"]).all()
