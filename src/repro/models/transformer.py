"""Decoder-only transformer LM: dense, MoE, and VLM-stub variants.

Layers are scanned (stacked params) with per-layer remat, so the lowered
HLO stays compact for 48-layer production configs and activation memory is
bounded by one layer boundary per layer (sequence-parallel sharded).

Supports: GQA + RoPE, sliding-window and local:global attention schedules,
MoE blocks, learned positions, tied embeddings, a stubbed vision front-end
(precomputed patch embeddings overwrite the first ``vision_patches`` token
slots -- the assignment treats modality front-ends as stubs).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import dispatch as kdispatch
from repro.models import attention as attn
from repro.models import mlp as mlp_mod
from repro.models.common import (
    apply_norm,
    apply_rope,
    chunked_softmax_xent,
    norm_axes,
    norm_params,
)
from repro.parallel.sharding import logical_constraint

_BIG_WINDOW = jnp.iinfo(jnp.int32).max


# ------------------------------------------------ int8 KV cache (pow2) ----
# The paper's INT8 + power-of-two-scale arithmetic applied to the decode
# state: K/V are stored as int8 payloads with one int8 exponent per
# (token, kv-head); dequantization on read is a shift-scale, exactly the
# PU's scale/shift module.  Halves decode HBM traffic (SSPerf).


def kv_quantize(x: jax.Array):
    """(..., hd) float -> (int8 payload, int8 exponent over last dim)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    e = jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-30) / 127.0))
    e = jnp.clip(e, -126, 126)
    q = jnp.clip(
        jnp.round(xf / jnp.exp2(e)[..., None]), -128, 127
    ).astype(jnp.int8)
    return q, e.astype(jnp.int8)


def kv_dequantize(q: jax.Array, e: jax.Array, dt) -> jax.Array:
    return (q.astype(jnp.float32) * jnp.exp2(e.astype(jnp.float32))[..., None]).astype(dt)


# ------------------------------------------------------------- params -----


def _layer_params(cfg: ModelConfig, key) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "attn_norm": norm_params(cfg, cfg.d_model, k1),
        "attn": attn.attn_params(cfg, k2),
        "mlp_norm": norm_params(cfg, cfg.d_model, k3),
    }
    if cfg.is_moe:
        p["moe"] = mlp_mod.moe_params(cfg, k4)
    else:
        p["mlp"] = mlp_mod.mlp_params(cfg, k4)
    # None (non-parametric norms) are invalid scan xs; drop them.
    return {k: v for k, v in p.items() if v is not None}


def init_params(cfg: ModelConfig, key) -> dict:
    keys = jax.random.split(key, 4 + cfg.n_layers)
    init = jax.nn.initializers.normal(0.02)
    params: Dict[str, Any] = {
        "embed": init(keys[0], (cfg.vocab, cfg.d_model), jnp.float32),
        "final_norm": norm_params(cfg, cfg.d_model, keys[1]),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = init(keys[2], (cfg.d_model, cfg.vocab), jnp.float32)
    if cfg.pos_embed == "learned":
        params["pos_embed"] = init(keys[3], (cfg.max_position, cfg.d_model), jnp.float32)
    layer_keys = jnp.stack(keys[4:])
    params["layers"] = jax.vmap(lambda k: _layer_params(cfg, k))(layer_keys)
    return {k: v for k, v in params.items() if v is not None}


def param_axes(cfg: ModelConfig) -> dict:
    layer_ax = {
        "attn_norm": norm_axes(cfg),
        "attn": attn.attn_axes(cfg),
        "mlp_norm": norm_axes(cfg),
    }
    if cfg.is_moe:
        layer_ax["moe"] = mlp_mod.moe_axes(cfg)
    else:
        layer_ax["mlp"] = mlp_mod.mlp_axes(cfg)
    layer_ax = {k: v for k, v in layer_ax.items() if v is not None}
    # prepend the stacked 'layers' axis
    layer_ax = jax.tree.map(
        lambda ax: ("layers",) + ax,
        layer_ax,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x
        ),
    )
    axes: Dict[str, Any] = {
        "embed": ("vocab", "embed_d"),
        "final_norm": norm_axes(cfg),
        "layers": layer_ax,
    }
    if not cfg.tie_embeddings:
        axes["unembed"] = ("embed_d", "vocab")
    if cfg.pos_embed == "learned":
        axes["pos_embed"] = (None, "embed_d")
    return {k: v for k, v in axes.items() if v is not None}


def layer_windows(cfg: ModelConfig) -> jax.Array:
    """Per-layer effective attention window (int32, stacked for scan)."""
    idx = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    if cfg.global_every:
        is_global = (idx + 1) % cfg.global_every == 0
        return jnp.where(is_global, _BIG_WINDOW, cfg.window or _BIG_WINDOW)
    if cfg.window:
        return jnp.full((cfg.n_layers,), cfg.window, jnp.int32)
    return jnp.full((cfg.n_layers,), _BIG_WINDOW, jnp.int32)


# ------------------------------------------------------------- forward ----


def _qkv(
    cfg: ModelConfig, x: jax.Array, lp: dict, positions: jax.Array,
    use_kernels: bool,
):
    """Attention-norm and QKV projection (RoPE applied) -> q, k, v."""
    h = apply_norm(cfg, x, lp.get("attn_norm"))
    if use_kernels:
        return kdispatch.decode_qkv(
            cfg, lp["attn"], h, positions, rope=cfg.pos_embed == "rope"
        )
    q, k, v = attn.project_qkv(cfg, lp["attn"], h)
    if cfg.pos_embed == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _attend(
    cfg: ModelConfig,
    x: jax.Array,
    lp: dict,
    q: jax.Array,
    k_att: jax.Array,             # (B, Sk, KV, hd)
    v_att: jax.Array,
    window: jax.Array,
    positions: jax.Array,
    valid: Optional[jax.Array],
    kv_positions: Optional[jax.Array],
    use_kernels: bool,
) -> jax.Array:
    """Residual attention block over k_att / v_att -> x."""
    dt = x.dtype
    if use_kernels:
        x = x + kdispatch.decode_attention(
            cfg, lp["attn"], q, k_att.astype(dt), v_att.astype(dt),
            q_positions=positions,
            kv_valid_len=valid,
            window_arr=window,
            kv_positions=kv_positions,
        )
    else:
        ctx = attn.gqa_attention(
            q, k_att.astype(dt), v_att.astype(dt),
            q_positions=positions,
            kv_valid_len=valid,
            causal=True,
            window_arr=window,
            kv_positions=kv_positions,
            chunk=cfg.attn_chunk,
        )
        x = x + attn.project_out(cfg, lp["attn"], ctx)
    return logical_constraint(x, "batch", "seq", "d_model")


def _mlp_block(cfg: ModelConfig, x: jax.Array, lp: dict) -> Tuple[jax.Array, jax.Array]:
    """Residual MLP / MoE block -> (x, moe aux loss)."""
    h2 = apply_norm(cfg, x, lp.get("mlp_norm"))
    aux = jnp.zeros((), jnp.float32)
    if cfg.is_moe:
        y, aux = mlp_mod.moe_apply(cfg, lp["moe"], h2)
    elif kdispatch.mlp_active(cfg, h2):
        y = kdispatch.decode_mlp(cfg, lp["mlp"], h2)
    else:
        y = mlp_mod.mlp_apply(cfg, lp["mlp"], h2)
    x = x + y
    return logical_constraint(x, "batch", "seq", "d_model"), aux


def _layer_fn(
    cfg: ModelConfig,
    x: jax.Array,                 # (B, S, D)
    lp: dict,
    window: jax.Array,            # () int32
    positions: jax.Array,         # (B, S)
    return_kv: bool,
):
    """One full-sequence layer -> (x, aux, this layer's k/v or None).

    The single-token decode runs the same three blocks in
    :func:`decode_stage`, with the cache write between them."""
    q, k, v = _qkv(cfg, x, lp, positions, use_kernels=False)
    x = _attend(
        cfg, x, lp, q, k, v, window, positions, None, None, use_kernels=False
    )

    if return_kv and cfg.kv_quant:
        kq, ke_out = kv_quantize(k)
        vq, ve_out = kv_quantize(v)
        kv_quant_out = (kq, vq, ke_out, ve_out)

    x, aux = _mlp_block(cfg, x, lp)
    if not return_kv:
        kv_out = None
    elif cfg.kv_quant:
        kv_out = kv_quant_out
    else:
        kv_out = (k, v)
    return x, aux, kv_out


def _embed(cfg, params, tokens, patch_embeds, positions):
    x = params["embed"].astype(_dtype(cfg))[tokens]
    if cfg.family == "vlm" and patch_embeds is not None:
        pe = patch_embeds.astype(x.dtype)
        x = jax.lax.dynamic_update_slice(x, pe, (0, 0, 0))
    if cfg.pos_embed == "learned":
        x = x + params["pos_embed"].astype(x.dtype)[positions]
    return x


def _dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def forward_hidden(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,                 # (B, S)
    patch_embeds: Optional[jax.Array] = None,
    return_cache: bool = False,
) -> Tuple[jax.Array, jax.Array, Optional[Tuple[jax.Array, jax.Array]]]:
    """Full-sequence pass -> (hidden (B,S,D), moe aux loss, optional kv cache)."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = _embed(cfg, params, tokens, patch_embeds, positions)
    x = logical_constraint(x, "batch", "seq", "d_model")
    windows = layer_windows(cfg)

    def body(carry, xs):
        x, aux_sum = carry
        lp, win = xs
        x, aux, kv = _layer_fn(cfg, x, lp, win, positions, return_kv=return_cache)
        return (x, aux_sum + aux), kv

    if cfg.remat == "layer":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable
        )
    (x, aux), kvs = jax.lax.scan(body, (x, 0.0), (params["layers"], windows))
    x = apply_norm(cfg, x, params.get("final_norm"))
    cache = None
    if return_cache:
        cache = tuple(kvs)   # (L, B, S, KV, hd) payloads (+ exps if quant)
    return x, aux / cfg.n_layers, cache


def _unembed_matrix(cfg, params):
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["unembed"]


def train_loss(cfg: ModelConfig, params: dict, batch: dict) -> jax.Array:
    hidden, aux, _ = forward_hidden(
        cfg, params, batch["tokens"], batch.get("patch_embeds")
    )
    loss = chunked_softmax_xent(
        hidden, _unembed_matrix(cfg, params), batch["labels"], batch.get("mask")
    )
    return loss + 0.01 * aux


def logits_last(cfg: ModelConfig, params: dict, hidden: jax.Array) -> jax.Array:
    """(B, S, D) -> logits of the final position (B, V)."""
    h_last = hidden[:, -1]
    return (h_last @ _unembed_matrix(cfg, params).astype(hidden.dtype)).astype(
        jnp.float32
    )


def prefill(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,
    patch_embeds: Optional[jax.Array] = None,
    lengths: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Full-context pass -> (last-token logits (B,V), kv cache (L,B,S,KV,hd) x2).

    ``lengths`` (B,) enables bucketed batched prefill: rows are true
    prompts right-padded to a shared bucket length; logits are gathered
    at each row's last *real* token (``lengths - 1``).  The cache keeps
    the padded tail -- causal masking hides those slots from every query
    at position < length, and batched decode overwrites slot ``length``
    (then length+1, ...) before it ever becomes visible, so the tail is
    never attended to.

    Ring configs (kv_ring + pure SWA) return the ring layout: the last
    ``window`` tokens placed at slots ``position % window``; the ring
    re-layout is whole-sequence, so it composes with ``lengths=None``
    only (the serving engine admits ring configs lane-isolated).
    """
    hidden, _, cache = forward_hidden(
        cfg, params, tokens, patch_embeds, return_cache=True
    )
    if cfg.kv_ring and cfg.window and not cfg.global_every:
        if lengths is not None:
            raise ValueError(
                "bucketed prefill (lengths) is unsupported for kv_ring "
                "configs: the ring re-layout is a whole-sequence shift"
            )
        s = tokens.shape[1]
        w = min(s, cfg.window)
        ring_len = cfg.window if s >= cfg.window else s

        def conv(kv_full):
            # seq axis is 2: (L, B, S, KV[, hd])
            if s <= ring_len:
                return kv_full
            last = jax.lax.slice_in_dim(kv_full, s - ring_len, s, axis=2)
            slots = (jnp.arange(s - ring_len, s) % ring_len)
            out = jnp.zeros(
                kv_full.shape[:2] + (ring_len,) + kv_full.shape[3:],
                kv_full.dtype,
            )
            return out.at[:, :, slots].set(last)

        cache = tuple(conv(c) for c in cache)
    if lengths is not None:
        b = tokens.shape[0]
        h_last = hidden[jnp.arange(b), lengths - 1]
        logits = (
            h_last @ _unembed_matrix(cfg, params).astype(hidden.dtype)
        ).astype(jnp.float32)
        return logits, cache
    return logits_last(cfg, params, hidden), cache


def _ring_len(cfg: ModelConfig, max_len: int) -> int:
    """Effective cache length: the attention window for pure-SWA models."""
    if cfg.kv_ring and cfg.window and not cfg.global_every:
        return min(max_len, cfg.window)
    return max_len


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    max_len = _ring_len(cfg, max_len)
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    if cfg.kv_quant:
        # int8 payloads + per-(token, kv-head) power-of-two exponents:
        # the paper's PU arithmetic applied to the decode state.
        eshape = shape[:-1]
        return (
            jnp.zeros(shape, jnp.int8),
            jnp.zeros(shape, jnp.int8),
            jnp.full(eshape, -126, jnp.int8),
            jnp.full(eshape, -126, jnp.int8),
        )
    return (jnp.zeros(shape, _dtype(cfg)), jnp.zeros(shape, _dtype(cfg)))


def cache_axes(cfg: ModelConfig):
    ax = ("layers", "batch", "kv_seq", "kv_heads", None)
    if cfg.kv_quant:
        ex = ("layers", "batch", "kv_seq", "kv_heads")
        return (ax, ax, ex, ex)
    return (ax, ax)


# -------------------------------------------------- layer-sliced decode ---
# The stage pipeline (runtime.stage_decode) runs each pipeline stage's
# contiguous layer range on its own submesh: decode_step decomposes into
# decode_embed (first stage) -> decode_stage per layer slice -> decode_unembed
# (last stage), and the fused single-PU loop is exactly the one-stage
# composition, so staged and fused serving share every per-layer op.


def _decode_positions(pos: jax.Array, b: int) -> Tuple[jax.Array, jax.Array]:
    """Normalize pos to (int32 pos, (B, 1) positions) for one-token decode."""
    pos = jnp.asarray(pos, jnp.int32)
    positions = (
        jnp.broadcast_to(pos, (b, 1)) if pos.ndim == 0 else pos[:, None]
    ).astype(jnp.int32)
    return pos, positions


def decode_slice_points(cfg: ModelConfig) -> Tuple[int, ...]:
    """Layer indices where a stage boundary may fall (every layer)."""
    return tuple(range(cfg.n_layers + 1))


def slice_params(cfg: ModelConfig, params: dict, layer_range) -> dict:
    """Stage-local decode params for layers [start, stop)."""
    start, stop = layer_range
    return {
        "layers": jax.tree.map(lambda a: a[start:stop], params["layers"]),
        "windows": layer_windows(cfg)[start:stop],
    }


def slice_cache(cfg: ModelConfig, cache, layer_range):
    """Stage-local KV cache lanes for layers [start, stop)."""
    start, stop = layer_range
    return jax.tree.map(lambda a: a[start:stop], cache)


def decode_embed(cfg: ModelConfig, params: dict, tokens: jax.Array, pos: jax.Array) -> jax.Array:
    """First-stage half of the embed/unembed split: token -> hidden (B, 1, D)."""
    _, positions = _decode_positions(pos, tokens.shape[0])
    return _embed(cfg, params, tokens, None, positions)


def decode_stage(
    cfg: ModelConfig,
    stage_params: dict,
    hidden: jax.Array,               # (B, 1, D)
    stage_cache,
    pos: jax.Array,                  # () or (B,) int32 -- write position
):
    """One token step through a contiguous layer slice -> (hidden, cache).

    ``stage_params``/``stage_cache`` come from :func:`slice_params` /
    :func:`slice_cache`; an empty slice is the identity (the hidden state
    passes through untouched).

    The stacked cache is the layer loop's carry: layer ``l`` writes its new
    K/V row per lane at ``[l, lane, write_pos]`` and attends over layer
    ``l`` of the updated carry, so no layer slice is re-stacked and the
    round loop around this step updates the cache buffers in place."""
    if stage_params["layers"] and jax.tree.leaves(stage_params["layers"])[0].shape[0] == 0:
        return hidden, stage_cache
    b = hidden.shape[0]
    pos, positions = _decode_positions(pos, b)
    # fused decode kernels (kernels/decode.py) take over QKV and attention
    # when cfg.decode_kernels is set; the cache write stays XLA
    use_kernels = kdispatch.attention_active(cfg, hidden)
    dt = hidden.dtype
    cache_len = stage_cache[0].shape[2]
    # decode_pos may be () (all lanes aligned) or (B,) (staggered batched
    # decode: each lane writes its own cache position)
    per_lane = pos.ndim > 0
    # ring buffer (pure-SWA): write round-robin; slot s holds absolute
    # position pos - ((pos - s) mod L); never-written slots come out
    # negative and are masked in attention.
    ring = bool(cfg.kv_ring and cfg.window and not cfg.global_every)
    write_pos = pos % cache_len if ring else pos
    kv_positions = None
    if ring:
        slots = jnp.arange(cache_len, dtype=jnp.int32)
        if per_lane:
            kv_positions = pos[:, None] - (
                (pos[:, None] - slots[None, :]) % cache_len
            )
        else:
            kv_positions = pos - ((pos - slots) % cache_len)
    valid = pos + 1
    lanes = jnp.arange(b)

    def cwrite(buf, layer, new):
        # buf (L, B, S, ...), new (B, 1, ...): one row per lane of one layer
        new = new.astype(buf.dtype)
        if per_lane:
            return buf.at[layer, lanes, write_pos].set(new[:, 0])
        start = (layer, 0, write_pos) + (0,) * (buf.ndim - 3)
        return jax.lax.dynamic_update_slice(buf, new[None], start)

    def body(carry, xs):
        x, cache, layer = carry
        lp, win = xs
        q, k, v = _qkv(cfg, x, lp, positions, use_kernels)
        if cfg.kv_quant:
            kq, ke_new = kv_quantize(k)
            vq, ve_new = kv_quantize(v)
            new_rows = (kq, vq, ke_new, ve_new)
        else:
            new_rows = (k, v)
        cache = tuple(cwrite(c, layer, n) for c, n in zip(cache, new_rows))
        view = tuple(
            jax.lax.dynamic_index_in_dim(c, layer, keepdims=False) for c in cache
        )
        if cfg.kv_quant:
            ck, cv, ke, ve = view
            k_att, v_att = kv_dequantize(ck, ke, dt), kv_dequantize(cv, ve, dt)
        else:
            k_att, v_att = view
        x = _attend(
            cfg, x, lp, q, k_att, v_att, win, positions, valid, kv_positions,
            use_kernels,
        )
        x, _ = _mlp_block(cfg, x, lp)
        return (x, cache, layer + 1), None

    (x, cache, _), _ = jax.lax.scan(
        body,
        (hidden, tuple(stage_cache), jnp.zeros((), jnp.int32)),
        (stage_params["layers"], stage_params["windows"]),
    )
    return x, cache


def decode_unembed(cfg: ModelConfig, params: dict, hidden: jax.Array) -> jax.Array:
    """Last-stage half of the split: hidden (B, 1, D) -> logits (B, V)."""
    x = apply_norm(cfg, hidden, params.get("final_norm"))
    return logits_last(cfg, params, x)


def decode_step(
    cfg: ModelConfig,
    params: dict,
    cache: Tuple[jax.Array, jax.Array],
    tokens: jax.Array,               # (B, 1)
    pos: jax.Array,                  # () or (B,) int32 -- write position
                                     # (per-lane when slots are staggered)
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One token step against a KV cache -> (logits (B,V), new cache).

    Exactly the one-stage composition of the sliced entry points, so the
    fused loop and the stage pipeline run identical per-layer math."""
    x = decode_embed(cfg, params, tokens, pos)
    x, new_cache = decode_stage(
        cfg, slice_params(cfg, params, (0, cfg.n_layers)), x, cache, pos
    )
    return decode_unembed(cfg, params, x), new_cache
