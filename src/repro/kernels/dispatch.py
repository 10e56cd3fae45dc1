"""Model -> kernel dispatch for the fused decode path.

The models layer (``transformer._layer_fn``, ``encdec._dec_layer_fn``,
``hybrid._shared_block``) calls these wrappers instead of touching
``decode.py`` directly, so every decode entry point -- the fused
``_decode_block`` scan, the per-stage loops, and the coalesced staged path
-- picks the kernels up from one place.  Activation is gated on
``cfg.decode_kernels`` (threaded from ``ServeConfig.decode_kernels`` by
the serving engine) plus the single-token shape test, with a
``REPRO_DECODE_KERNELS=0`` env kill switch for A/B triage without
replumbing configs.

This module deliberately imports nothing from ``repro.models`` (the models
import *it*); ``cfg`` is duck-typed on the ``ModelConfig`` fields it reads.

Block sizing (``kernel_blocks``): the streaming plan's schedulable tile is
one whole weight matrix (``runtime.serving.model_gemms`` /
``plan_model_streaming``), so the kernel's block size is the *VMEM
refinement* of a plan tile -- the tile is consumed whole when it fits the
per-operand VMEM budget and split into equal HBM->VMEM slabs along its
streaming axis otherwise.  The planner's tile sequence and the kernel's
block sequence therefore describe the same HBM traffic.

Exclusions (kept on XLA; DESIGN.md SS10): the KV-cache scatter between
QKV and attention, norms/residuals, MoE MLPs (token routing is not a
weight-streaming GEMM), and ``logical_constraint`` sharding annotations
(the decode kernels assume per-device replicated weights).
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import decode

_ENV_KILL = "REPRO_DECODE_KERNELS"
# Per-operand VMEM budget for one streamed slab.  ~4 MiB leaves room for
# double-buffering plus scratch inside a ~16 MiB VMEM.
_VMEM_BUDGET = 4 * 2 ** 20


def enabled(cfg) -> bool:
    """True when the fused decode kernels are switched on for this model."""
    if os.environ.get(_ENV_KILL, "1") in ("0", "false", "False", "no"):
        return False
    return bool(getattr(cfg, "decode_kernels", False))


def _single_token(x: jax.Array) -> bool:
    return x.ndim == 3 and x.shape[1] == 1


def attention_active(cfg, x: jax.Array) -> bool:
    """Fused QKV/attention applies: flag on + single-token decode step."""
    return enabled(cfg) and _single_token(x)


def mlp_active(cfg, x: jax.Array) -> bool:
    """Fused MLP applies: flag on + single token + dense (non-MoE) MLP."""
    return enabled(cfg) and _single_token(x) and not getattr(cfg, "is_moe", False)


def _slab(dim: int, bytes_per_unit: int) -> int:
    """VMEM refinement of a plan tile: whole when it fits, equal slabs
    (rounded up to the 128-lane tile) otherwise."""
    total = dim * bytes_per_unit
    if total <= _VMEM_BUDGET:
        return dim
    n = -(-total // _VMEM_BUDGET)
    blk = -(-dim // n)
    blk = ((blk + 127) // 128) * 128
    return min(blk, dim)


def kernel_blocks(cfg, *, sk: Optional[int] = None, dtype=jnp.bfloat16) -> dict:
    """Derive each kernel's block size from the model's plan-tile shapes."""
    it = jnp.dtype(dtype).itemsize
    d, hd = cfg.d_model, cfg.head_dim
    dq = cfg.n_heads * hd
    dkv = cfg.n_kv_heads * hd
    out = {
        # qkv streams d_model rows of the three projection tiles together
        "block_m": _slab(d, (dq + 2 * dkv) * it),
        # mlp streams d_ff columns of gate+up plus the matching down rows
        "block_f": _slab(cfg.d_ff, 3 * d * it),
    }
    if sk is not None:
        # attention streams one lane slab of kv heads' k and v per grid step
        lanes = decode.heads_per_slab(cfg.n_kv_heads, hd) * hd
        out["block_s"] = _slab(sk, 2 * lanes * it)
    return out


def decode_qkv(cfg, p: dict, x: jax.Array, positions: jax.Array, *, rope: bool):
    """(B, 1, d) -> q (B, 1, Hq, hd), k/v (B, 1, Hkv, hd) via fused_qkv."""
    b = x.shape[0]
    blocks = kernel_blocks(cfg, dtype=x.dtype)
    q, k, v = decode.fused_qkv(
        x[:, 0],
        p["wq"], p["wk"], p["wv"],
        p.get("bq"), p.get("bk"), p.get("bv"),
        positions.reshape(b) if positions is not None else None,
        n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim,
        rope=rope,
        theta=float(cfg.rope_theta),
        block_m=blocks["block_m"],
    )
    return q[:, None], k[:, None], v[:, None]


def decode_attention(
    cfg,
    p: dict,
    q: jax.Array,                       # (B, 1, Hq, hd)
    k: jax.Array,                       # (B, Sk, Hkv, hd)
    v: jax.Array,
    *,
    q_positions: jax.Array,             # (B,) or (B, 1)
    kv_valid_len: Optional[jax.Array] = None,
    window: Optional[int] = None,
    window_arr: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
    causal: bool = True,
) -> jax.Array:
    """Fused attention + output projection -> (B, 1, d)."""
    b, sk = q.shape[0], k.shape[1]
    blocks = kernel_blocks(cfg, sk=sk, dtype=q.dtype)
    y = decode.fused_decode_attention(
        q[:, 0],
        k, v,
        p["wo"], p.get("bo"),
        q_positions=q_positions.reshape(b),
        kv_valid_len=kv_valid_len,
        window=window,
        window_arr=window_arr,
        kv_positions=kv_positions,
        causal=causal,
        block_s=blocks["block_s"],
    )
    return y[:, None]


def decode_mlp(cfg, p: dict, x: jax.Array) -> jax.Array:
    """(B, 1, d) -> (B, 1, d) via fused_mlp (dense MLPs only)."""
    blocks = kernel_blocks(cfg, dtype=x.dtype)
    y = decode.fused_mlp(
        x[:, 0],
        p["w_up"], p.get("w_gate"), p.get("b_up"),
        p["w_down"], p.get("b_down"),
        act=cfg.mlp,
        block_f=blocks["block_f"],
    )
    return y[:, None]
