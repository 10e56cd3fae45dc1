"""Plain float32 reference of the served decoder-only models.

Written from the published architectures (OLMo, arXiv:2402.00838;
StarCoder2, arXiv:2402.19173) in straightforward ``jax.numpy``: no
kernels, no cache, no batching, every matrix product at
``Precision.HIGHEST``.  It imports nothing of the program; its one
contact with the program is the weight tree's layout, which
``weights.check_layout`` ties to the program's ``init_params``.

The same forward with every matrix product in float8 (e4m3, scaled per
row and per column) is the control: the step below the bfloat16 that the
configurations state, which the comparison must be able to tell apart.

Sequences are padded at the end to a multiple of ``BLOCK``; the padding
is causal-masked from every real position, and it keeps the number of
compiled shapes small.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.chip.work import Dims

BLOCK = 512
HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _mm_f8(a, b):
    """``a @ b`` with both operands rounded to float8 e4m3, ``a`` scaled
    per row and ``b`` per column; the products of the float8 values are
    exact in bfloat16 and accumulate in float32."""
    sa = jnp.maximum(jnp.max(jnp.abs(a), axis=-1, keepdims=True), 1e-30) / F8_MAX
    sb = jnp.maximum(jnp.max(jnp.abs(b), axis=-2, keepdims=True), 1e-30) / F8_MAX
    qa = (a / sa).astype(F8).astype(jnp.bfloat16)
    qb = (b / sb).astype(F8).astype(jnp.bfloat16)
    return jnp.matmul(qa, qb, preferred_element_type=jnp.float32) * sa * sb


@functools.lru_cache(maxsize=None)
def _arch(conf_items: Tuple) -> Dict:
    conf = dict(conf_items)
    return {
        "olmo": conf["model_type"] == "olmo",
        "eps": conf.get("norm_epsilon", 1e-5),
        "theta": float(conf["rope_theta"]),
        "window": conf.get("sliding_window"),
    }


def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + eps)
    if scale is not None:
        y = y * scale + bias
    return y


def _rope(x, theta):
    """Rotary embedding, rotate-half form, at positions 0..n-1; x (n, h, hd)."""
    n, _, hd = x.shape
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(n, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, window, mm):
    """Causal attention of q (n, H, hd) over k, v (n, KV, hd), a block of
    queries at a time; head h reads kv head h // (H / KV)."""
    n, h, hd = q.shape
    groups = h // k.shape[1]
    k = jnp.repeat(k, groups, axis=1).transpose(1, 2, 0)      # (H, hd, n)
    v = jnp.repeat(v, groups, axis=1).transpose(1, 0, 2)      # (H, n, hd)
    keys = jnp.arange(n)
    out = []
    for s in range(0, n, BLOCK):
        qb = q[s:s + BLOCK].transpose(1, 0, 2) / math.sqrt(hd)  # (H, b, hd)
        scores = mm(qb, k)                                       # (H, b, n)
        rows = s + jnp.arange(qb.shape[1])[:, None]
        ok = keys[None, :] <= rows
        if window:
            ok &= keys[None, :] > rows - window
        scores = jnp.where(ok[None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        out.append(mm(p, v).transpose(1, 0, 2))                  # (b, H, hd)
    return jnp.concatenate(out, axis=0)


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _layer(m: Dims, conf_items, x, lp, control: bool):
    a = _arch(conf_items)
    mm = _mm_f8 if control else _mm
    n = x.shape[0]
    at, ml = lp["attn"], lp["mlp"]
    norm1, norm2 = lp.get("attn_norm", {}), lp.get("mlp_norm", {})
    h = _layer_norm(x, norm1.get("scale"), norm1.get("bias"), a["eps"])
    q, k, v = mm(h, at["wq"]), mm(h, at["wk"]), mm(h, at["wv"])
    if m.bias:
        q, k, v = q + at["bq"], k + at["bk"], v + at["bv"]
    q = _rope(q.reshape(n, m.heads, m.head_dim), a["theta"])
    k = _rope(k.reshape(n, m.kv_heads, m.head_dim), a["theta"])
    v = v.reshape(n, m.kv_heads, m.head_dim)
    ctx = _attention(q, k, v, a["window"], mm).reshape(n, m.heads * m.head_dim)
    o = mm(ctx, at["wo"])
    if m.bias:
        o = o + at["bo"]
    x = x + o
    h = _layer_norm(x, norm2.get("scale"), norm2.get("bias"), a["eps"])
    if m.gated:
        y = jax.nn.silu(mm(h, ml["w_gate"])) * mm(h, ml["w_up"])
    else:
        y = jax.nn.gelu(mm(h, ml["w_up"]) + ml["b_up"], approximate=True)
    y = mm(y, ml["w_down"])
    if m.bias:
        y = y + ml["b_down"]
    return x + y


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(m: Dims, conf_items, h, h_ctrl, final_norm, unembed, served):
    """Per position: the reference's best logit less its logit of the
    served token, and less its logit of the control's first token."""
    a = _arch(conf_items)
    fn = final_norm or {}
    gaps, ctrl_gaps = [], []
    for s in range(0, h.shape[0], BLOCK):
        hb = _layer_norm(h[s:s + BLOCK], fn.get("scale"), fn.get("bias"), a["eps"])
        z = _mm(hb, unembed)
        best = jnp.max(z, axis=-1)
        tok = served[s:s + BLOCK]
        gaps.append(best - jnp.take_along_axis(z, tok[:, None], axis=-1)[:, 0])
        if h_ctrl is not None:
            hc = _layer_norm(h_ctrl[s:s + BLOCK], fn.get("scale"), fn.get("bias"), a["eps"])
            c = jnp.argmax(_mm_f8(hc, unembed), axis=-1)
            ctrl_gaps.append(best - jnp.take_along_axis(z, c[:, None], axis=-1)[:, 0])
    g = jnp.concatenate(gaps)
    return g, (jnp.concatenate(ctrl_gaps) if h_ctrl is not None else None)


def _hidden(m: Dims, conf_items, weights, tokens, control: bool):
    x = weights["embed"][tokens]
    for i in range(m.layers):
        lp = jax.tree.map(lambda w: w[i], weights["layers"])
        x = _layer(m, conf_items, x, lp, control)
    return x


def logit_gaps(
    m: Dims, conf: dict, weights: dict, prompt: np.ndarray,
    served: np.ndarray, control: bool = False,
) -> Tuple[np.ndarray, np.ndarray | None]:
    """Gaps at each served token of one request: ``prompt`` then the
    ``served`` tokens, the first of which the prompt's last position
    produced.  With ``control`` also the gaps of the float8 control's
    first token at the same positions and context."""
    conf_items = tuple(sorted(
        (k, v) for k, v in conf.items()
        if k in ("model_type", "norm_epsilon", "rope_theta", "sliding_window")
    ))
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    n = len(seq)
    padded = -(-n // BLOCK) * BLOCK
    tokens = jnp.asarray(np.pad(seq, (0, padded - n)))
    first = len(prompt) - 1
    target = np.zeros(padded, np.int32)
    target[first:n] = served
    h = _hidden(m, conf_items, weights, tokens, False)
    h_ctrl = _hidden(m, conf_items, weights, tokens, True) if control else None
    unembed = weights["embed"].T if m.tied else weights["unembed"]
    g, cg = _head(m, conf_items, h, h_ctrl, weights.get("final_norm"),
                  unembed, jnp.asarray(target))
    g = np.asarray(g)[first:n]
    cg = np.asarray(cg)[first:n] if cg is not None else None
    return g, cg
