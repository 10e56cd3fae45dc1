"""Squared-ReLU decoder-only transformers: Nemotron-4's layer (arXiv:2402.16819)
as the program registers it (``mlp="sq_relu"``, ``norm="layernorm"``).

An architecture module that a test adds as a new file, beside a
configuration file that names it (``"bench_arch": "arch_sq_relu"``), to
show that a configuration can bring its own shapes, weight layout, work
counts and reference without an edit to the benchmark.  Its equations
and counts live here and nowhere else.

Each layer: an affine LayerNorm, grouped-query attention with rotary
embeddings over the whole head and no biases, a residual add; an affine
LayerNorm, ``relu(x @ w_up)**2 @ w_down``, a residual add.  Then a final
affine LayerNorm and an untied output head.

The reference is plain float32 ``jax.numpy`` with every matrix product
at ``Precision.HIGHEST``; its control rounds both operands of every
matrix product to float8 e4m3 (scaled per row and per column).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Iterable, Tuple

import numpy as np

import jax
import jax.numpy as jnp

BF16 = 2
BLOCK = 128
HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


@dataclasses.dataclass(frozen=True)
class Dims:
    vocab: int
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    eps: float
    theta: float


def dims(conf: dict) -> Dims:
    """Shapes of a configuration file (Hugging Face ``config.json`` keys)."""
    return Dims(
        vocab=conf["vocab_size"],
        d=conf["hidden_size"],
        layers=conf["num_hidden_layers"],
        heads=conf["num_attention_heads"],
        kv_heads=conf["num_key_value_heads"],
        head_dim=conf["hidden_size"] // conf["num_attention_heads"],
        ff=conf["intermediate_size"],
        eps=float(conf["norm_eps"]),
        theta=float(conf["rope_theta"]),
    )


def program_config(conf: Dict):
    """The registered architecture at the file's sizes; refused where the
    registration computes another layer than this module's."""
    from repro.configs import get_config

    base = get_config(conf["arch"])
    if (base.mlp, base.norm, base.attn_bias, base.mlp_bias) != ("sq_relu", "layernorm", False, False):
        raise ValueError(f"{conf['arch']} is not a squared-ReLU, LayerNorm, bias-free model")
    m = dims(conf)
    return dataclasses.replace(
        base, n_layers=m.layers, d_model=m.d, n_heads=m.heads, n_kv_heads=m.kv_heads,
        head_dim=m.head_dim, d_ff=m.ff, vocab=m.vocab, rope_theta=m.theta,
        tie_embeddings=False,
    )


def layout(m: Dims) -> Dict[Tuple[str, ...], Tuple[Tuple[int, ...], str]]:
    """Leaf path -> (shape, kind)."""
    L, d = m.layers, m.d
    q, kv = m.heads * m.head_dim, m.kv_heads * m.head_dim
    out = {
        ("embed",): ((m.vocab, d), "normal"),
        ("unembed",): ((d, m.vocab), "normal"),
        ("layers", "attn", "wq"): ((L, d, q), "normal"),
        ("layers", "attn", "wk"): ((L, d, kv), "normal"),
        ("layers", "attn", "wv"): ((L, d, kv), "normal"),
        ("layers", "attn", "wo"): ((L, q, d), "normal"),
        ("layers", "mlp", "w_up"): ((L, d, m.ff), "normal"),
        ("layers", "mlp", "w_down"): ((L, m.ff, d), "normal"),
        ("final_norm", "scale"): ((d,), "scale"),
        ("final_norm", "bias"): ((d,), "bias"),
    }
    for norm in ("attn_norm", "mlp_norm"):
        out[("layers", norm, "scale")] = ((L, d), "scale")
        out[("layers", norm, "bias")] = ((L, d), "bias")
    return out


# ------------------------------------------------------------- counts ----


def _layer_matrix_params(m: Dims) -> int:
    return m.d * m.head_dim * (2 * m.heads + 2 * m.kv_heads) + 2 * m.d * m.ff


def _weights_read(m: Dims) -> int:
    """Every weight but the embedding, which a round reads a row of."""
    return m.layers * (_layer_matrix_params(m) + 4 * m.d) + 2 * m.d + m.d * m.vocab


def param_count(m: Dims) -> int:
    return _weights_read(m) + m.vocab * m.d


def prefill_flops(m: Dims, n: int) -> int:
    """A prompt of ``n`` tokens, causal, with logits at its last position."""
    attn = m.layers * 4 * m.heads * m.head_dim * (n * (n + 1) // 2)
    return 2 * m.layers * _layer_matrix_params(m) * n + attn + 2 * m.d * m.vocab


def decode_flops(m: Dims, positions: Iterable[int]) -> int:
    """One decode round: a lane writing at ``p`` attends to ``p + 1`` keys."""
    per_lane = 2 * (m.layers * _layer_matrix_params(m) + m.d * m.vocab)
    return sum(per_lane + m.layers * 4 * m.heads * m.head_dim * (p + 1) for p in positions)


def decode_bytes(m: Dims, positions: Iterable[int]) -> int:
    """The weights once in bfloat16, and per lane ``p + 1`` positions of
    keys and values read and one written."""
    kv = m.layers * 2 * m.kv_heads * m.head_dim * BF16
    return BF16 * _weights_read(m) + sum(kv * (p + 2) for p in positions)


# ---------------------------------------------------------- reference ----


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _mm_f8(a, b):
    sa = jnp.maximum(jnp.max(jnp.abs(a), axis=-1, keepdims=True), 1e-30) / F8_MAX
    sb = jnp.maximum(jnp.max(jnp.abs(b), axis=-2, keepdims=True), 1e-30) / F8_MAX
    qa = (a / sa).astype(F8).astype(jnp.bfloat16)
    qb = (b / sb).astype(F8).astype(jnp.bfloat16)
    return jnp.matmul(qa, qb, preferred_element_type=jnp.float32) * sa * sb


def _norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x, theta):
    """Rotate-half rotary embedding at positions 0..n-1; x (n, h, hd)."""
    n, _, hd = x.shape
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(n, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _hidden(m: Dims, weights, tokens, control: bool):
    mm = _mm_f8 if control else _mm
    n, groups = tokens.shape[0], m.heads // m.kv_heads
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    x = weights["embed"][tokens]
    for i in range(m.layers):
        lp = jax.tree.map(lambda w: w[i], weights["layers"])
        h = _norm(x, lp["attn_norm"], m.eps)
        q = _rope(mm(h, lp["attn"]["wq"]).reshape(n, m.heads, m.head_dim), m.theta)
        k = _rope(mm(h, lp["attn"]["wk"]).reshape(n, m.kv_heads, m.head_dim), m.theta)
        v = mm(h, lp["attn"]["wv"]).reshape(n, m.kv_heads, m.head_dim)
        k = jnp.repeat(k, groups, axis=1).transpose(1, 2, 0)          # (H, hd, n)
        v = jnp.repeat(v, groups, axis=1).transpose(1, 0, 2)          # (H, n, hd)
        scores = mm(q.transpose(1, 0, 2) / np.sqrt(m.head_dim), k)   # (H, n, n)
        p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        ctx = mm(p, v).transpose(1, 0, 2).reshape(n, m.heads * m.head_dim)
        x = x + mm(ctx, lp["attn"]["wo"])
        h = _norm(x, lp["mlp_norm"], m.eps)
        x = x + mm(jnp.square(jax.nn.relu(mm(h, lp["mlp"]["w_up"]))), lp["mlp"]["w_down"])
    return _norm(x, weights["final_norm"], m.eps)


@jax.jit
def _gaps(h, h_ctrl, unembed, served):
    """The reference's best logit less its logit of the served token, and
    less its logit of the control's first choice."""
    z = _mm(h, unembed)
    best = jnp.max(z, axis=-1)
    gap = lambda tok: best - jnp.take_along_axis(z, tok[:, None], axis=-1)[:, 0]
    ctrl = gap(jnp.argmax(_mm_f8(h_ctrl, unembed), axis=-1)) if h_ctrl is not None else None
    return gap(served), ctrl


def logit_gaps(
    m: Dims, conf: dict, weights: dict, prompt: np.ndarray,
    served: np.ndarray, control: bool = False,
) -> Tuple[np.ndarray, np.ndarray | None]:
    """Gaps at each served token of one request: ``prompt`` then the
    ``served`` tokens, the first of which the prompt's last position
    produced.  With ``control`` also the float8 control's gaps."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    n = len(seq)
    padded = -(-n // BLOCK) * BLOCK
    tokens = jnp.asarray(np.pad(seq, (0, padded - n)))
    first = len(prompt) - 1
    target = np.zeros(padded, np.int32)
    target[first:n] = served
    h = _hidden(m, weights, tokens, False)
    h_ctrl = _hidden(m, weights, tokens, True) if control else None
    g, cg = _gaps(h, h_ctrl, weights["unembed"], jnp.asarray(target))
    return np.asarray(g)[first:n], (np.asarray(cg)[first:n] if control else None)
