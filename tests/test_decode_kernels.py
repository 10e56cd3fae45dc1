"""Fused Pallas decode kernels (kernels/decode.py, DESIGN.md SS10).

Per-op numeric-tolerance tests against the pure-jnp oracles, ring-buffer
decode-attention edge cases parametrized across the XLA reference AND the
kernel (both paths pinned by one suite), blocking invariance, the
interpret-dispatch rule, and engine-level greedy-stream argmax-identity
(fused and staged/coalesced paths) with zero retraces after warmup.
"""
import dataclasses
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis.sanitize import retrace_guard
from repro.configs import get_config, smoke_variant
from repro.core.pu import host_offload_config, tpu_v5e_config
from repro.kernels import (
    decode_attention_ref,
    default_interpret,
    fused_decode_attention,
    fused_mlp,
    fused_mlp_ref,
    fused_qkv,
    fused_qkv_ref,
)
from repro.kernels.decode import heads_per_slab
from repro.models import api as model_api
from repro.models import attention as attn
from repro.runtime.serving import ServeConfig, ServingEngine

B, D, HQ, HKV, HD, SK, FF = 3, 96, 4, 2, 32, 40, 112
_DT = jnp.bfloat16


def _f32(a):
    return np.asarray(a, np.float32)


def _close(a, b, atol=5e-2):
    np.testing.assert_allclose(_f32(a), _f32(b), atol=atol)


@pytest.fixture(scope="module")
def tensors(rng):
    n = lambda *s: rng.normal(size=s)
    return {
        "x": jnp.asarray(n(B, D), _DT),
        "wq": jnp.asarray(n(D, HQ * HD) * 0.05, jnp.float32),
        "wk": jnp.asarray(n(D, HKV * HD) * 0.05, jnp.float32),
        "wv": jnp.asarray(n(D, HKV * HD) * 0.05, jnp.float32),
        "bq": jnp.asarray(n(HQ * HD) * 0.05, jnp.float32),
        "bk": jnp.asarray(n(HKV * HD) * 0.05, jnp.float32),
        "bv": jnp.asarray(n(HKV * HD) * 0.05, jnp.float32),
        "q": jnp.asarray(n(B, HQ, HD), _DT),
        "k": jnp.asarray(n(B, SK, HKV, HD), _DT),
        "v": jnp.asarray(n(B, SK, HKV, HD), _DT),
        "wo": jnp.asarray(n(HQ * HD, D) * 0.05, jnp.float32),
        "bo": jnp.asarray(n(D) * 0.05, jnp.float32),
        "w_up": jnp.asarray(n(D, FF) * 0.05, jnp.float32),
        "w_gate": jnp.asarray(n(D, FF) * 0.05, jnp.float32),
        "b_up": jnp.asarray(n(FF) * 0.05, jnp.float32),
        "w_down": jnp.asarray(n(FF, D) * 0.05, jnp.float32),
        "b_down": jnp.asarray(n(D) * 0.05, jnp.float32),
        "pos": jnp.asarray([3, 17, 999], jnp.int32),
        "qpos": jnp.asarray([5, 20, 39], jnp.int32),
    }


# ---------------------------------------------------------------------------
# per-op tolerance vs oracles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bias", [True, False])
@pytest.mark.parametrize("rope", [True, False])
def test_fused_qkv_matches_ref(tensors, bias, rope):
    t = tensors
    args = (
        t["x"], t["wq"], t["wk"], t["wv"],
        t["bq"] if bias else None,
        t["bk"] if bias else None,
        t["bv"] if bias else None,
        t["pos"],
    )
    kw = dict(n_heads=HQ, n_kv_heads=HKV, head_dim=HD, rope=rope, theta=1e4)
    got = fused_qkv(*args, block_m=64, **kw)
    want = fused_qkv_ref(*args, **kw)
    for g, w in zip(got, want):
        _close(g, w, atol=2e-2)


_ATTN_CASES = {
    "full": dict(),
    "valid_len": dict(kv_valid_len="stagger"),
    "window_static": dict(kv_valid_len="stagger", window=7),
    "window_dynamic": dict(kv_valid_len="stagger", window_arr=9),
    "ring": dict(kv_positions="ring"),
    "ring_window": dict(kv_positions="ring", window_arr=9),
    "noncausal": dict(causal=False),
}


def _attn_kwargs(case, rng):
    kw = dict(_ATTN_CASES[case])
    if kw.get("kv_valid_len") == "stagger":
        kw["kv_valid_len"] = jnp.asarray([6, 21, 40], jnp.int32)
    if kw.get("kv_positions") == "ring":
        kw["kv_positions"] = jnp.asarray(
            rng.integers(-1, 45, (B, SK)), jnp.int32
        )
    if "window_arr" in kw:
        kw["window_arr"] = jnp.asarray(kw["window_arr"], jnp.int32)
    return kw


@pytest.mark.parametrize("case", sorted(_ATTN_CASES))
def test_fused_attention_matches_ref(tensors, rng, case):
    t = tensors
    kw = _attn_kwargs(case, rng)
    got = fused_decode_attention(
        t["q"], t["k"], t["v"], t["wo"], t["bo"],
        q_positions=t["qpos"], block_s=16, **kw,
    )
    want = decode_attention_ref(
        t["q"], t["k"], t["v"], t["wo"], t["bo"], q_positions=t["qpos"], **kw
    )
    _close(got, want)


@pytest.mark.parametrize(
    "hkv,hd,pack", [(4, 64, 2), (2, 128, 1), (3, 96, 3), (2, 32, 2)]
)
def test_fused_attention_head_packing(rng, hkv, hd, pack):
    """Heads narrower than a 128-lane tile are read ``pack`` to a slab,
    each head's queries masked to its own lanes: the context stays per
    head, whatever the packing."""
    assert heads_per_slab(hkv, hd) == pack
    hq = 2 * hkv
    n = lambda *s: rng.normal(size=s)
    q = jnp.asarray(n(B, hq, hd), _DT)
    k = jnp.asarray(n(B, SK, hkv, hd), _DT)
    v = jnp.asarray(n(B, SK, hkv, hd), _DT)
    wo = jnp.asarray(n(hq * hd, D) * 0.05, jnp.float32)
    kw = dict(
        q_positions=jnp.asarray([5, 20, 39], jnp.int32),
        kv_valid_len=jnp.asarray([6, 21, 40], jnp.int32),
    )
    got = fused_decode_attention(q, k, v, wo, None, block_s=16, **kw)
    _close(got, decode_attention_ref(q, k, v, wo, None, **kw))


@pytest.mark.parametrize(
    "act,gated,bias",
    [("swiglu", True, True), ("swiglu", True, False),
     ("gelu", False, True), ("sq_relu", False, False)],
)
def test_fused_mlp_matches_ref(tensors, act, gated, bias):
    t = tensors
    args = (
        t["x"], t["w_up"],
        t["w_gate"] if gated else None,
        t["b_up"] if bias else None,
        t["w_down"],
        t["b_down"] if bias else None,
    )
    got = fused_mlp(*args, act=act, block_f=48)
    _close(got, fused_mlp_ref(*args, act=act), atol=2e-2)


def test_blocking_invariance(tensors, rng):
    """Streaming in slabs must match the single-block pass: the kernel
    block size is a VMEM refinement of the plan tile, never a semantic."""
    t = tensors
    kw = _attn_kwargs("ring_window", rng)
    whole = fused_decode_attention(
        t["q"], t["k"], t["v"], t["wo"], t["bo"],
        q_positions=t["qpos"], block_s=SK, **kw,
    )
    split = fused_decode_attention(
        t["q"], t["k"], t["v"], t["wo"], t["bo"],
        q_positions=t["qpos"], block_s=8, **kw,
    )
    _close(split, whole, atol=2e-2)
    margs = (t["x"], t["w_up"], t["w_gate"], t["b_up"], t["w_down"], t["b_down"])
    _close(
        fused_mlp(*margs, act="swiglu", block_f=FF),
        fused_mlp(*margs, act="swiglu", block_f=16),
        atol=2e-2,
    )


# ---------------------------------------------------------------------------
# satellite: ring-buffer edge cases, pinned on the XLA reference AND the
# kernel by the same suite
# ---------------------------------------------------------------------------


def _attn_out(impl, t, k=None, v=None, **kw):
    """One decode-attention + out-projection through either path."""
    k = t["k"] if k is None else k
    v = t["v"] if v is None else v
    if impl == "kernel":
        return fused_decode_attention(
            t["q"], k, v, t["wo"], t["bo"],
            q_positions=t["qpos"], block_s=16, **kw,
        )
    ctx = attn.gqa_attention(
        t["q"][:, None], k, v,
        q_positions=t["qpos"][:, None], causal=kw.pop("causal", True),
        chunk=16, **kw,
    )
    y = ctx.reshape(B, HQ * HD) @ t["wo"].astype(_DT)
    return y + t["bo"].astype(_DT)


@pytest.mark.parametrize("impl", ["xla", "kernel"])
def test_ring_wrap_negative_positions_never_attend(tensors, impl):
    """Full ring wrap with young lanes: never-written slots carry negative
    positions and must not contribute -- poisoning their K/V entries with
    huge values cannot change the output (bitwise)."""
    t = tensors
    cache_len = SK
    decode_pos = jnp.asarray([5, 20, 39], jnp.int32)      # lane 0 wrote 6 slots
    slots = jnp.arange(cache_len, dtype=jnp.int32)
    kvp = decode_pos[:, None] - ((decode_pos[:, None] - slots[None]) % cache_len)
    assert bool(jnp.any(kvp < 0))

    clean = _attn_out(impl, t, kv_positions=kvp)
    poison = jnp.where((kvp < 0)[..., None, None], jnp.asarray(1e4, _DT), t["k"])
    vpois = jnp.where((kvp < 0)[..., None, None], jnp.asarray(1e4, _DT), t["v"])
    dirty = _attn_out(impl, t, k=poison, v=vpois, kv_positions=kvp)
    np.testing.assert_array_equal(_f32(clean), _f32(dirty))


@pytest.mark.parametrize("impl", ["xla", "kernel"])
def test_window_arr_matches_static_window(tensors, impl):
    """A dynamic () window_arr is exactly the static window of the same
    value, on both implementations."""
    t = tensors
    for w in (1, 7, 64):
        stat = _attn_out(impl, t, window=w)
        dyn = _attn_out(impl, t, window_arr=jnp.asarray(w, jnp.int32))
        np.testing.assert_array_equal(_f32(stat), _f32(dyn))


@pytest.mark.parametrize("impl", ["xla", "kernel"])
def test_staggered_valid_len_masks_tail(tensors, impl):
    """Per-lane kv_valid_len at staggered positions: slots past a lane's
    limit must not contribute (poison invariance), including a lane whose
    whole history is a single slot."""
    t = tensors
    vlen = jnp.asarray([1, 21, 40], jnp.int32)
    clean = _attn_out(impl, t, kv_valid_len=vlen)
    tail = jnp.arange(SK)[None] >= vlen[:, None]          # (B, Sk)
    kpois = jnp.where(tail[..., None, None], jnp.asarray(1e4, _DT), t["k"])
    vpois = jnp.where(tail[..., None, None], jnp.asarray(1e4, _DT), t["v"])
    dirty = _attn_out(impl, t, k=kpois, v=vpois, kv_valid_len=vlen)
    np.testing.assert_array_equal(_f32(clean), _f32(dirty))


def test_xla_and_kernel_agree(tensors, rng):
    """The two implementations agree within bf16 reassociation noise on
    every masking mode."""
    for case in sorted(_ATTN_CASES):
        kw = _attn_kwargs(case, rng)
        _close(
            _attn_out("kernel", tensors, **dict(kw)),
            _attn_out("xla", tensors, **dict(kw)),
        )


# ---------------------------------------------------------------------------
# satellite: interpret-dispatch rule
# ---------------------------------------------------------------------------


def test_default_interpret_rule(monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_INTERPRET", raising=False)
    assert default_interpret() == (jax.default_backend() != "tpu")
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "1")
    assert default_interpret() is True
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "0")
    assert default_interpret() is False


# ---------------------------------------------------------------------------
# engine-level: greedy streams argmax-identical to the XLA path
# ---------------------------------------------------------------------------

_PARAMS = {}


def _setup(arch, **overrides):
    cfg = smoke_variant(get_config(arch))
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    key = (arch, tuple(sorted(overrides)))
    if key not in _PARAMS:
        api = model_api.get_api(cfg)
        _PARAMS[key] = api.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, _PARAMS[key]


def _stream(cfg, params, prompts, stagger=False, **kw):
    eng = ServingEngine(
        cfg, params,
        ServeConfig(max_batch=2, max_len=64, max_new_tokens=5, seed=0, **kw),
    )
    it = iter(prompts)
    eng.submit(next(it).copy())
    if stagger:
        eng.step()                    # first request decodes alone first
    for p in it:
        eng.submit(p.copy())
    return {r.uid: r.out_tokens for r in eng.run_until_drained()}, eng


_ENGINE_VARIANTS = {
    "olmo-1b": {},
    "gemma3-12b": {},                                   # local:global windows
    "olmo-ring": {},                                    # ring KV + window
    "whisper-medium": {},                               # encdec + bias + gelu
    "zamba2-1.2b": {},                                  # hybrid shared block
}


def _arch_setup(name):
    if name == "olmo-ring":
        return _setup("olmo-1b", window=16, kv_ring=True)
    return _setup(name)


@pytest.mark.parametrize("arch", sorted(_ENGINE_VARIANTS))
def test_serve_kernels_argmax_identical(arch):
    """Acceptance: --decode-kernels greedy streams match the XLA path
    exactly under staggered admissions on every smoke family."""
    cfg, params = _arch_setup(arch)
    rng = np.random.default_rng(3)
    prompts = [
        rng.integers(0, cfg.vocab, int(l)).astype(np.int32)
        for l in (9, 14, 6)
    ]
    ref, _ = _stream(cfg, params, prompts, stagger=True)
    got, _ = _stream(cfg, params, prompts, stagger=True, decode_kernels=True)
    assert got == ref


def test_serve_kernels_staged_paths_match():
    """The staged (serial) and coalesced (overlapped lane-group) multi-PU
    decode paths pick the kernels up through the same dispatch layer and
    stay argmax-identical to the XLA single-PU stream."""
    cfg, params = _setup("olmo-1b", n_layers=4)
    rng = np.random.default_rng(4)
    prompts = [
        rng.integers(0, cfg.vocab, int(l)).astype(np.int32)
        for l in (8, 13, 5)
    ]
    pus = [host_offload_config(), tpu_v5e_config()]
    ref, _ = _stream(cfg, params, prompts)
    serial, _ = _stream(
        cfg, params, prompts, decode_kernels=True, stream_pus=pus,
        decode_microbatches=1,
    )
    overlap, _ = _stream(
        cfg, params, prompts, decode_kernels=True, stream_pus=pus,
        decode_microbatches=2,
    )
    assert serial == ref
    assert overlap == ref


def test_serve_kernels_warmup_zero_retraces():
    cfg, params = _setup("olmo-1b")
    eng = ServingEngine(
        cfg, params,
        ServeConfig(
            max_batch=2, max_len=64, max_new_tokens=5, seed=0,
            decode_kernels=True,
        ),
    )
    eng.warmup()
    with retrace_guard(eng.tracing):
        rng = np.random.default_rng(5)
        for l in (6, 11, 3):
            eng.submit(rng.integers(0, cfg.vocab, l).astype(np.int32))
        eng.run_until_drained()
