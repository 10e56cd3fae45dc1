"""Fused Pallas decode-stage kernels: the paper's PU datapath on the
per-token serving hot path.

Three kernels cover the two per-token hot ops of one decode layer
(DESIGN.md SS10); all reuse ``int8_gemm.py``'s structure -- a reduction
grid streaming weight/state tiles HBM->VMEM, accumulation into VMEM
scratch across grid steps, and the post-processing fused into the last
step's epilogue:

- :func:`fused_qkv` -- the Q/K/V projections of a single decode token as
  one weight-streaming pass over ``d_model`` (shared activation tile, all
  three heads' accumulators live in scratch), with bias add and RoPE
  rotation fused into the epilogue.
- :func:`fused_decode_attention` -- single-token GQA attention over the
  whole KV cache: per-lane ring/valid/window masking and a
  streaming-softmax (running max / denom / accumulator) reduction over
  KV blocks, one grid step per (lane, kv head, KV slab).  The output
  projection ``ctx @ wo + bo`` follows as one batched XLA GEMM.
- :func:`fused_mlp` -- the (gated-)MLP as one pass over ``d_ff`` blocks:
  up/gate GEMMs, bias and activation per block, immediately contracted
  through the matching ``w_down`` rows into a (B, d_model) scratch
  accumulator -- the (B, d_ff) intermediate never materializes in HBM.

Blocking matches the plan's weight-streaming granularity: a schedulable
plan tile is one weight matrix (``runtime.serving.model_gemms``), and the
kernel splits a tile into VMEM-budgeted sub-blocks only when it exceeds
the budget (``dispatch.kernel_blocks``), so the planner's tile sequence
and the kernel's block sequence describe the same traffic.

Numerics mirror the XLA reference ops (f32 accumulation, one rounding to
the compute dtype per GEMM, masking with the same -1e30 sentinel), so
greedy decode streams stay argmax-identical to the composed-XLA path;
exact bit-identity is NOT guaranteed (streaming softmax reassociates).

``interpret=None`` resolves through :func:`common.default_interpret`:
interpreted on CPU, compiled on TPU, ``REPRO_KERNEL_INTERPRET`` override.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret

_NEG = -1e30          # masking sentinel; matches models.attention._NEG
_BIG = jnp.iinfo(jnp.int32).max
# padded ring-slot / arange sentinels (chosen so every mask comparison on
# a padded column is False without int32 overflow)
_PAD_NEG = -(1 << 30)
_PAD_POS = 1 << 30


def _pad_axis(a: jax.Array, axis: int, mult: int, value=0) -> jax.Array:
    rem = (-a.shape[axis]) % mult
    if not rem:
        return a
    pads = [(0, 0)] * a.ndim
    pads[axis] = (0, rem)
    return jnp.pad(a, pads, constant_values=value)


# ------------------------------------------------------------- fused QKV --


def _qkv_kernel(
    x_ref,          # (B, bm)
    wq_ref,         # (bm, Dq)
    wk_ref,         # (bm, Dkv)
    wv_ref,         # (bm, Dkv)
    bq_ref,         # (1, Dq)
    bk_ref,         # (1, Dkv)
    bv_ref,         # (1, Dkv)
    cosq_ref,       # (B, Dq) f32 -- per-lane cos of each head's angle
    sinq_ref,       # (B, Dq) f32 -- per-lane sin, negated on t1's lanes
    cosk_ref,       # (B, Dkv) f32
    sink_ref,       # (B, Dkv) f32
    q_ref,          # out (B, Dq)
    k_ref,          # out (B, Dkv)
    v_ref,          # out (B, Dkv)
    accq_ref,       # scratch (B, Dq) f32
    acck_ref,       # scratch (B, Dkv) f32
    accv_ref,       # scratch (B, Dkv) f32
    *,
    n_m: int,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope: bool,
    has_bias: bool,
):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        accq_ref[...] = jnp.zeros_like(accq_ref)
        acck_ref[...] = jnp.zeros_like(acck_ref)
        accv_ref[...] = jnp.zeros_like(accv_ref)

    xb = x_ref[...]
    dims = (((1,), (0,)), ((), ()))
    accq_ref[...] += jax.lax.dot_general(
        xb, wq_ref[...], dims, preferred_element_type=jnp.float32
    )
    acck_ref[...] += jax.lax.dot_general(
        xb, wk_ref[...], dims, preferred_element_type=jnp.float32
    )
    accv_ref[...] += jax.lax.dot_general(
        xb, wv_ref[...], dims, preferred_element_type=jnp.float32
    )

    @pl.when(j == n_m - 1)
    def _epilogue():
        dt = q_ref.dtype
        # one rounding to the compute dtype per GEMM (mirrors the XLA dot),
        # THEN bias, THEN rope -- project_qkv/apply_rope op order.
        q = accq_ref[...].astype(dt)
        k = acck_ref[...].astype(dt)
        v = accv_ref[...].astype(dt)
        if has_bias:
            q = q + bq_ref[...].astype(dt)
            k = k + bk_ref[...].astype(dt)
            v = v + bv_ref[...].astype(dt)

        if rope:
            half = head_dim // 2

            def rot(t, cos, sin):
                # per head [t1, t2] -> [t1 cos - t2 sin, t2 cos + t1 sin],
                # on the flat (B, H*hd) lanes: the partner of a lane is
                # half a head away, fetched by a lane rotation
                tf = t.astype(jnp.float32)
                w = tf.shape[1]
                lane = jax.lax.broadcasted_iota(jnp.int32, tf.shape, 1)
                partner = jnp.where(
                    lane % head_dim < half,
                    pltpu.roll(tf, w - half, 1),       # t2 under t1
                    pltpu.roll(tf, half, 1),           # t1 under t2
                )
                return (tf * cos + partner * sin).astype(dt)

            q = rot(q, cosq_ref[...], sinq_ref[...])
            k = rot(k, cosk_ref[...], sink_ref[...])
        q_ref[...] = q
        k_ref[...] = k
        v_ref[...] = v


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_heads", "n_kv_heads", "head_dim", "rope", "theta",
        "block_m", "interpret",
    ),
)
def fused_qkv(
    x: jax.Array,                       # (B, d) compute dtype
    wq: jax.Array,                      # (d, Hq*hd)
    wk: jax.Array,                      # (d, Hkv*hd)
    wv: jax.Array,                      # (d, Hkv*hd)
    bq: Optional[jax.Array] = None,     # (Hq*hd,)
    bk: Optional[jax.Array] = None,
    bv: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,   # (B,) int32 (rope only)
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope: bool = True,
    theta: float = 1e4,
    block_m: int = 512,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode token's QKV projections + bias + RoPE in one pass.

    Returns ``(q (B, Hq, hd), k (B, Hkv, hd), v (B, Hkv, hd))`` in
    ``x.dtype`` -- the post-rope tensors the cache write and attention
    consume.
    """
    interpret = resolve_interpret(interpret)
    b, d = x.shape
    dq, dkv = n_heads * head_dim, n_kv_heads * head_dim
    dt = x.dtype
    has_bias = bq is not None

    if positions is None:
        positions = jnp.zeros((b,), jnp.int32)
    if rope:
        freqs = 1.0 / (
            theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
        )
        angles = positions[:, None].astype(jnp.float32) * freqs   # (B, hd/2)
        sin, cos = jnp.sin(angles), jnp.cos(angles)
    else:
        sin = cos = jnp.zeros((b, head_dim // 2), jnp.float32)
    # one head's lanes are [cos, cos] and [-sin, sin], tiled over heads
    cos_h = jnp.concatenate([cos, cos], axis=-1)
    sin_h = jnp.concatenate([-sin, sin], axis=-1)
    tables = [
        jnp.tile(t, (1, heads))
        for heads in (n_heads, n_kv_heads) for t in (cos_h, sin_h)
    ]

    zq = jnp.zeros((1, dq), dt)
    zkv = jnp.zeros((1, dkv), dt)
    bq2 = bq.reshape(1, dq).astype(dt) if has_bias else zq
    bk2 = bk.reshape(1, dkv).astype(dt) if has_bias else zkv
    bv2 = bv.reshape(1, dkv).astype(dt) if has_bias else zkv

    block_m = min(block_m, d)
    xp = _pad_axis(x, 1, block_m)
    wqp = _pad_axis(wq.astype(dt), 0, block_m)
    wkp = _pad_axis(wk.astype(dt), 0, block_m)
    wvp = _pad_axis(wv.astype(dt), 0, block_m)
    n_m = xp.shape[1] // block_m

    q, k, v = pl.pallas_call(
        functools.partial(
            _qkv_kernel, n_m=n_m, n_heads=n_heads, n_kv_heads=n_kv_heads,
            head_dim=head_dim, rope=rope, has_bias=has_bias,
        ),
        grid=(n_m,),
        in_specs=[
            pl.BlockSpec((b, block_m), lambda j: (0, j)),
            pl.BlockSpec((block_m, dq), lambda j: (j, 0)),
            pl.BlockSpec((block_m, dkv), lambda j: (j, 0)),
            pl.BlockSpec((block_m, dkv), lambda j: (j, 0)),
            pl.BlockSpec((1, dq), lambda j: (0, 0)),
            pl.BlockSpec((1, dkv), lambda j: (0, 0)),
            pl.BlockSpec((1, dkv), lambda j: (0, 0)),
            pl.BlockSpec((b, dq), lambda j: (0, 0)),
            pl.BlockSpec((b, dq), lambda j: (0, 0)),
            pl.BlockSpec((b, dkv), lambda j: (0, 0)),
            pl.BlockSpec((b, dkv), lambda j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((b, dq), lambda j: (0, 0)),
            pl.BlockSpec((b, dkv), lambda j: (0, 0)),
            pl.BlockSpec((b, dkv), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, dq), dt),
            jax.ShapeDtypeStruct((b, dkv), dt),
            jax.ShapeDtypeStruct((b, dkv), dt),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, dq), jnp.float32),
            pltpu.VMEM((b, dkv), jnp.float32),
            pltpu.VMEM((b, dkv), jnp.float32),
        ],
        interpret=interpret,
    )(xp, wqp, wkp, wvp, bq2, bk2, bv2, *tables)
    return (
        q.reshape(b, n_heads, head_dim),
        k.reshape(b, n_kv_heads, head_dim),
        v.reshape(b, n_kv_heads, head_dim),
    )


# ------------------------------------------------- fused decode attention --


def heads_per_slab(n_kv_heads: int, head_dim: int) -> int:
    """KV heads one attention grid step reads: the fewest whose hd-wide
    lane slabs fill whole 128-lane tiles, or all of them (a full-width
    block is always tile-legal)."""
    for p in range(1, n_kv_heads):
        if n_kv_heads % p == 0 and p * head_dim % 128 == 0:
            return p
    return n_kv_heads


def _decode_attn_kernel(
    limit_ref,      # SMEM (B,) int32 -- per-lane valid length
    row_ref,        # SMEM (B,) int32 -- query position
    win_ref,        # SMEM (1,) int32 -- attention window
    q_ref,          # (P*G, P*hd) pre-scaled, block-diagonal queries of P heads
    k_ref,          # (bs, P*hd) key slab of P kv heads
    v_ref,          # (bs, P*hd)
    col_ref,        # (1, bs) int32 -- absolute position per cache slot
    o_ref,          # out (P*G, P*hd) -- context in the diagonal blocks
    m_ref,          # scratch (P*G, 1) f32 -- running max
    l_ref,          # scratch (P*G, 1) f32 -- running denom
    acc_ref,        # scratch (P*G, P*hd) f32 -- running PV accumulator
    *,
    n_s: int,
    causal: bool,
    use_kvp: bool,
):
    i = pl.program_id(0)
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    col = col_ref[...]                             # (1, bs)
    if use_kvp:
        # ring buffer: each slot carries its absolute position; negative
        # positions mark never-written slots (padded slots carry _PAD_NEG)
        valid = col >= 0
    else:
        valid = col < limit_ref[i]
    if causal:
        row = row_ref[i]
        valid = valid & (col <= row) & (col > row - win_ref[0])

    s = jax.lax.dot_general(                       # (PG, Phd) x (bs, Phd)^T
        q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                              # (PG, bs)
    s = jnp.where(valid, s, _NEG)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                         # (PG, bs)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(                      # (PG, bs) x (bs, Phd)
        p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = m_new

    @pl.when(s_idx == n_s - 1)
    def _epilogue():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype
        )


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_s", "interpret"),
)
def fused_decode_attention(
    q: jax.Array,                       # (B, Hq, hd) post-rope, unscaled
    k: jax.Array,                       # (B, Sk, Hkv, hd)
    v: jax.Array,                       # (B, Sk, Hkv, hd)
    wo: jax.Array,                      # (Hq*hd, d)
    bo: Optional[jax.Array] = None,     # (d,)
    *,
    q_positions: jax.Array,             # (B,) int32 absolute query position
    kv_valid_len: Optional[jax.Array] = None,   # () or (B,) int32
    window: Optional[int] = None,               # static sliding window
    window_arr: Optional[jax.Array] = None,     # dynamic () int32 window
    kv_positions: Optional[jax.Array] = None,   # (Sk,) or (B, Sk) ring slots
    causal: bool = True,
    block_s: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Single-token GQA attention + output projection -> (B, d).

    Mask semantics mirror ``models.attention._decode_attention`` exactly:
    ``kv_positions`` (ring caches; negative = never written) else
    ``arange < kv_valid_len``; causal row/window bounds on top.  The
    kernel's grid is (lane, kv head pack, KV slab): each step streams the
    ``block_s`` slots of K and V of ``P = heads_per_slab(Hkv, hd)`` kv
    heads and updates a running (max, denom, accumulator) softmax for
    their query groups.  ``ctx @ wo (+ bo)`` is one (B, Hq*hd) GEMM in
    XLA after the kernel (DESIGN.md SS10).
    """
    interpret = resolve_interpret(interpret)
    b, hq, hd = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    groups = hq // hkv
    pack = heads_per_slab(hkv, hd)
    n_pack = hkv // pack
    rows, lanes = pack * groups, pack * hd
    dt = q.dtype
    use_kvp = kv_positions is not None

    if block_s is None:
        block_s = min(sk, 512)
    block_s = min(block_s, sk)

    if use_kvp:
        col = jnp.broadcast_to(
            kv_positions.astype(jnp.int32).reshape(-1, sk), (b, sk)
        )
        pad_val = _PAD_NEG
    else:
        col = jnp.broadcast_to(jnp.arange(sk, dtype=jnp.int32)[None], (b, sk))
        pad_val = _PAD_POS
    col = _pad_axis(col, 1, block_s, value=pad_val)[:, None, :]
    # (B, Sk, Hkv, hd) -> (B, Sk, Hkv*hd): P kv heads make one lane slab
    kp = _pad_axis(k, 1, block_s).reshape(b, -1, hkv * hd)
    vp = _pad_axis(v, 1, block_s).reshape(b, -1, hkv * hd)
    n_s = kp.shape[1] // block_s

    limit = jnp.broadcast_to(
        jnp.asarray(sk if kv_valid_len is None else kv_valid_len, jnp.int32),
        (b,),
    )
    row = q_positions.astype(jnp.int32).reshape(b)
    if window_arr is not None:
        win = jnp.asarray(window_arr, jnp.int32)
    elif window is not None:
        win = jnp.asarray(window, jnp.int32)
    else:
        win = jnp.asarray(_BIG, jnp.int32)
    win = win.reshape(1)
    # the query scale rounds in the compute dtype, as in the XLA path
    qs = (q * (1.0 / (hd ** 0.5))).reshape(b, n_pack, pack, groups, hd)
    if pack > 1:
        # block-diagonal: head p's queries see only head p's hd lanes
        eye = jnp.eye(pack, dtype=bool)[:, None, :, None]
        qs = jnp.where(eye, qs[:, :, :, :, None, :], 0)
    qs = qs.reshape(b, n_pack, rows, lanes)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    group_spec = pl.BlockSpec((None, None, rows, lanes), lambda i, h, s: (i, h, 0, 0))
    kv_spec = pl.BlockSpec((None, block_s, lanes), lambda i, h, s: (i, s, h))
    ctx = pl.pallas_call(
        functools.partial(
            _decode_attn_kernel, n_s=n_s, causal=causal, use_kvp=use_kvp
        ),
        grid=(b, n_pack, n_s),
        in_specs=[
            smem, smem, smem,
            group_spec,
            kv_spec,
            kv_spec,
            pl.BlockSpec((None, 1, block_s), lambda i, h, s: (i, 0, s)),
        ],
        out_specs=group_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_pack, rows, lanes), dt),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, lanes), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(limit, row, win, qs, kp, vp, col)
    if pack > 1:
        ctx = ctx.reshape(b, n_pack, pack, groups, pack, hd)
        ctx = jnp.moveaxis(jnp.diagonal(ctx, axis1=2, axis2=4), -1, 2)
    y = ctx.reshape(b, hq * hd) @ wo.astype(dt)
    if bo is not None:
        y = y + bo.astype(dt)
    return y


# -------------------------------------------------------------- fused MLP --


def _mlp_kernel(
    x_ref,          # (B, d)
    wg_ref,         # (d, bf) -- gate weights (== up weights when ungated)
    wu_ref,         # (d, bf)
    bu_ref,         # (1, bf)
    wd_ref,         # (bf, d)
    bd_ref,         # (1, d)
    out_ref,        # (B, d)
    acc_ref,        # scratch (B, d) f32
    *,
    n_f: int,
    act: str,
    gated: bool,
    has_bias: bool,
):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dt = out_ref.dtype
    f32 = jnp.float32
    xb = x_ref[...]
    dims = (((1,), (0,)), ((), ()))

    def rnd(t):
        # one rounding to the compute dtype, kept in f32: the TPU vector
        # unit has no bf16 arithmetic, so the activation runs in f32 and
        # rounds after every op the XLA path rounds after
        return t.astype(dt).astype(f32)

    # d_model is unblocked, so each f-slab's up/gate columns complete in one
    # dot -- rounding to the compute dtype here is exactly the XLA dot's
    g = rnd(jax.lax.dot_general(xb, wg_ref[...], dims, preferred_element_type=f32))
    if has_bias:
        g = rnd(g + bu_ref[...].astype(f32))
    if gated:
        up = rnd(
            jax.lax.dot_general(xb, wu_ref[...], dims, preferred_element_type=f32)
        )
    if act == "swiglu":
        h = rnd(g / (1.0 + jnp.exp(-g))) * up
    elif act == "gelu":
        h = jax.nn.gelu(g)
    elif act == "sq_relu":
        r = jnp.maximum(g, 0.0)
        h = r * r
    else:
        raise ValueError(act)
    acc_ref[...] += jax.lax.dot_general(
        h.astype(dt), wd_ref[...], dims, preferred_element_type=f32
    )

    @pl.when(j == n_f - 1)
    def _epilogue():
        y = acc_ref[...].astype(dt)
        if has_bias:
            y = y + bd_ref[...].astype(dt)
        out_ref[...] = y


@functools.partial(
    jax.jit, static_argnames=("act", "block_f", "interpret")
)
def fused_mlp(
    x: jax.Array,                       # (B, d) compute dtype
    w_up: jax.Array,                    # (d, f)
    w_gate: Optional[jax.Array] = None, # (d, f) -- presence selects gating
    b_up: Optional[jax.Array] = None,   # (f,)
    w_down: Optional[jax.Array] = None, # (f, d)
    b_down: Optional[jax.Array] = None, # (d,)
    *,
    act: str = "swiglu",
    block_f: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """up-proj -> activation -> down-proj without the HBM intermediate.

    Matches ``models.mlp.mlp_apply``: ``g = x @ (w_gate or w_up) (+ b_up)``,
    ``up = x @ w_up`` when gated, ``act(g, up) @ w_down (+ b_down)``.
    """
    interpret = resolve_interpret(interpret)
    b, d = x.shape
    f = w_up.shape[1]
    dt = x.dtype
    gated = w_gate is not None
    has_bias = b_up is not None
    if act == "swiglu" and not gated:
        raise ValueError("swiglu requires w_gate")

    block_f = min(block_f, f)
    wg = (w_gate if gated else w_up).astype(dt)
    wu = w_up.astype(dt)
    wgp = _pad_axis(wg, 1, block_f)
    wup = _pad_axis(wu, 1, block_f)
    wdp = _pad_axis(w_down.astype(dt), 0, block_f)
    fp = wgp.shape[1]
    n_f = fp // block_f
    bu2 = (
        _pad_axis(b_up.reshape(1, f).astype(dt), 1, block_f)
        if has_bias else jnp.zeros((1, fp), dt)
    )
    bd2 = (
        b_down.reshape(1, d).astype(dt) if has_bias else jnp.zeros((1, d), dt)
    )

    out = pl.pallas_call(
        functools.partial(
            _mlp_kernel, n_f=n_f, act=act, gated=gated, has_bias=has_bias
        ),
        grid=(n_f,),
        in_specs=[
            pl.BlockSpec((b, d), lambda j: (0, 0)),
            pl.BlockSpec((d, block_f), lambda j: (0, j)),
            pl.BlockSpec((d, block_f), lambda j: (0, j)),
            pl.BlockSpec((1, block_f), lambda j: (0, j)),
            pl.BlockSpec((block_f, d), lambda j: (j, 0)),
            pl.BlockSpec((1, d), lambda j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((b, d), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, d), dt),
        scratch_shapes=[pltpu.VMEM((b, d), jnp.float32)],
        interpret=interpret,
    )(x, wgp, wup, bu2, wdp, bd2)
    return out
