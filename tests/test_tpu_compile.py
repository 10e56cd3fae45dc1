"""Compile the main path's kernels for a described TPU v5e, at real widths.

Mosaic (the TPU kernel compiler) refuses what the Pallas interpreter
accepts: blocks off the (8, 128) tiling, more VMEM than a kernel may use,
casts and vector ops the TPU has no lowering for.  Every case here
compiles for a ``v5e:2x2`` topology that is described, not attached, so
no chip is needed; a kernel case asserts that the program holds the
Mosaic kernel (``tpu_custom_call``) and was not interpreted.

The topology is described inside the ``topo`` fixture only: describing
it loads the TPU library, which one process at a time may hold.
"""
import dataclasses

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import (
    fused_decode_attention,
    fused_mlp,
    fused_qkv,
    im2col,
    int8_gemm,
    niu_refresh,
)
from repro.kernels.dispatch import kernel_blocks
from repro.models import api as model_api

B, MAX_LEN = 8, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                desc = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2"
                )
            except Exception as e:  # no TPU compiler in this installation
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture
def spec(topo, monkeypatch):
    """ShapeDtypeStruct factory on one described chip, with kernels forced
    to compile (the backend here is the CPU, which would interpret)."""
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "0")
    one = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    make.sharding = one
    return make


def _compile_kernel(fn, *args) -> str:
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


# head_dim 128, 256 (GQA 16/8) and 64 (two kv heads per 128-lane slab)
ARCHS = ("olmo-1b", "gemma3-12b", "whisper-medium")


@pytest.mark.parametrize("arch", ARCHS)
def test_fused_qkv_compiles(spec, arch):
    cfg = get_config(arch)
    d, hd = cfg.d_model, cfg.head_dim
    dq, dkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    blocks = kernel_blocks(cfg, sk=MAX_LEN)

    def fn(x, wq, wk, wv, pos):
        return fused_qkv(
            x, wq, wk, wv, None, None, None, pos, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, head_dim=hd, block_m=blocks["block_m"],
        )

    f32 = jnp.float32
    _compile_kernel(
        fn, spec((B, d)), spec((d, dq), f32), spec((d, dkv), f32),
        spec((d, dkv), f32), spec((B,), jnp.int32),
    )


@pytest.mark.parametrize("ring", [False, True], ids=["valid_len", "ring"])
@pytest.mark.parametrize("arch", ARCHS)
def test_fused_decode_attention_compiles(spec, arch, ring):
    cfg = get_config(arch)
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    blocks = kernel_blocks(cfg, sk=MAX_LEN)

    def fn(q, k, v, wo, qpos, lim, win):
        kw = dict(kv_positions=lim) if ring else dict(kv_valid_len=lim)
        return fused_decode_attention(
            q, k, v, wo, None, q_positions=qpos, window_arr=win,
            block_s=blocks["block_s"], **kw,
        )

    kv = spec((B, MAX_LEN, hkv, hd))
    lim = spec((B, MAX_LEN) if ring else (B,), jnp.int32)
    _compile_kernel(
        fn, spec((B, hq, hd)), kv, kv, spec((hq * hd, cfg.d_model), jnp.float32),
        spec((B,), jnp.int32), lim, spec((), jnp.int32),
    )


@pytest.mark.parametrize("arch", ARCHS)
def test_fused_mlp_compiles(spec, arch):
    cfg = get_config(arch)
    d, f = cfg.d_model, cfg.d_ff
    gated = cfg.mlp == "swiglu"
    blocks = kernel_blocks(cfg)

    def fn(x, wu, wg, wd):
        return fused_mlp(
            x, wu, wg if gated else None, None, wd, None, act=cfg.mlp,
            block_f=blocks["block_f"],
        )

    f32 = jnp.float32
    _compile_kernel(
        fn, spec((B, d)), spec((d, f), f32), spec((d, f), f32),
        spec((f, d), f32),
    )


def test_olmo_decode_step_with_kernels_compiles(spec):
    """The full-width olmo-1b decode step on the kernel path: the program
    ``serve --decode-kernels`` runs per token."""
    cfg = dataclasses.replace(get_config("olmo-1b"), decode_kernels=True)
    api = model_api.get_api(cfg)

    def place(tree):
        return jax.tree.map(lambda s: spec(s.shape, s.dtype), tree)

    params = place(jax.eval_shape(
        lambda: api.init_params(cfg, jax.random.PRNGKey(0))
    ))
    cache = place(jax.eval_shape(lambda: api.init_cache(cfg, B, MAX_LEN)))

    def step(p, c, tok, pos):
        return api.decode_step(cfg, p, c, tok, pos)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, spec((B, 1), jnp.int32), spec((B,), jnp.int32)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    )
    assert used < 16 * 2 ** 30          # one v5e chip's HBM


def test_niu_refresh_compiles(spec):
    _compile_kernel(
        lambda q, e, s: niu_refresh(q, e, s),
        spec((2048, 8192), jnp.int8), spec((), jnp.int32), spec((), jnp.int32),
    )


# ResNet-50 layers as GEMMs (N=Cout, M=k*k*Cin, P=OH*OW): stage 1's 3x3
# conv, and its 1x1 expansion, which carries the bottleneck's residual
@pytest.mark.parametrize(
    "n,m,p,residual",
    [(64, 576, 3136, False), (256, 64, 3136, True)],
    ids=["stage1_3x3", "stage1_1x1_residual"],
)
def test_int8_gemm_compiles(spec, n, m, p, residual):
    i8 = jnp.int8

    def fn(w, x, bias, res):
        return int8_gemm(w, x, bias, 7, res, relu=True)

    _compile_kernel(
        fn, spec((n, m), i8), spec((m, p), i8), spec((n,), jnp.int32),
        spec((n, p), i8) if residual else None,
    )


# (H, W, C, k, stride, pad) of ResNet-50 convolutions
@pytest.mark.parametrize(
    "h,c,k,stride,pad",
    [(224, 3, 7, 2, 3), (56, 64, 3, 1, 1), (56, 128, 3, 2, 1), (14, 256, 3, 1, 1)],
    ids=["stem", "stage1_3x3", "stage2_3x3_s2", "stage4_3x3"],
)
def test_im2col_compiles(spec, h, c, k, stride, pad):
    _compile_kernel(
        lambda x: im2col(x, k, stride, pad), spec((h, h, c), jnp.int8)
    )
