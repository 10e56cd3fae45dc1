"""Dense decoder-only transformers (OLMo, arXiv:2402.00838; StarCoder2,
arXiv:2402.19173): the architecture module of every configuration file
that names no ``bench_arch``.

It gives the shapes of a configuration file (the published
``config.json`` keys), the program's ``ModelConfig`` for it, the layout
of the weight tree the program's transformer takes, the work counts the
roofline and MFU readers divide, and the plain float32 reference with
its float8 control.

A roofline share has to count the same work whatever implements it, so
nothing here asks the program what it did: the counts follow from the
configuration file and from the lengths the traffic served.  Matrix
products count two operations per multiply-add.  Bytes count what a
decode round has to read at the configuration's serving precision
(bfloat16): the weights once per round, and the keys and values of the
live positions of each active lane, read and written.

The reference is written from the published architectures in
straightforward ``jax.numpy``: no kernels, no cache, no batching, every
matrix product at ``Precision.HIGHEST``.  It uses nothing of the
program; its one contact with the program is the weight tree's layout,
which ``weights.check_layout`` ties to the program's ``init_params``.
The same forward with every matrix product in float8 (e4m3, scaled per
row and per column) is the control: the step below the bfloat16 that the
configurations state, which the comparison must be able to tell apart.
Sequences are padded at the end to a multiple of ``BLOCK``; the padding
is causal-masked from every real position, and it keeps the number of
compiled shapes small.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Iterable, Tuple

import numpy as np

import jax
import jax.numpy as jnp

BF16 = 2
BLOCK = 512
HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0

# published config.json keys -> the program's ModelConfig fields
HF_TO_PROGRAM = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}


@dataclasses.dataclass(frozen=True)
class Dims:
    vocab: int
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    gated: bool
    bias: bool
    norm_affine: bool
    tied: bool


def dims(conf: dict) -> Dims:
    """Shapes of a configuration file (Hugging Face ``config.json`` keys)."""
    d = conf["hidden_size"]
    heads = conf["num_attention_heads"]
    return Dims(
        vocab=conf["vocab_size"],
        d=d,
        layers=conf["num_hidden_layers"],
        heads=heads,
        kv_heads=conf.get("num_key_value_heads", heads),
        head_dim=conf.get("head_dim") or d // heads,
        ff=conf["intermediate_size"],
        gated=conf["hidden_act"] == "silu",
        bias=bool(conf.get("use_bias", conf.get("attention_bias", False))),
        norm_affine=conf.get("norm_type") == "layer_norm",
        tied=bool(conf.get("tie_word_embeddings", False)),
    )


def program_config(conf: Dict):
    """The program's ModelConfig for a configuration file: the registered
    architecture, with the file's published sizes (and its cuts) set."""
    from repro.configs import get_config

    base = get_config(conf["arch"])
    changes = {
        field: type(getattr(base, field))(conf[key])
        for key, field in HF_TO_PROGRAM.items()
        if key in conf and getattr(base, field) != conf[key]
    }
    if "head_dim" not in conf and {"d_model", "n_heads"} & changes.keys():
        changes["head_dim"] = conf["hidden_size"] // conf["num_attention_heads"]
    return dataclasses.replace(base, **changes)


def layout(m: Dims) -> Dict[Tuple[str, ...], Tuple[Tuple[int, ...], str]]:
    """Leaf path -> (shape, kind); kind is 'normal', 'scale' or 'bias'."""
    L, d, hd = m.layers, m.d, m.head_dim
    q, kv = m.heads * hd, m.kv_heads * hd
    out: Dict[Tuple[str, ...], Tuple[Tuple[int, ...], str]] = {
        ("embed",): ((m.vocab, d), "normal"),
        ("layers", "attn", "wq"): ((L, d, q), "normal"),
        ("layers", "attn", "wk"): ((L, d, kv), "normal"),
        ("layers", "attn", "wv"): ((L, d, kv), "normal"),
        ("layers", "attn", "wo"): ((L, q, d), "normal"),
        ("layers", "mlp", "w_up"): ((L, d, m.ff), "normal"),
        ("layers", "mlp", "w_down"): ((L, m.ff, d), "normal"),
    }
    if not m.tied:
        out[("unembed",)] = ((d, m.vocab), "normal")
    if m.gated:
        out[("layers", "mlp", "w_gate")] = ((L, d, m.ff), "normal")
    if m.bias:
        for name, n in (("bq", q), ("bk", kv), ("bv", kv), ("bo", d)):
            out[("layers", "attn", name)] = ((L, n), "bias")
        out[("layers", "mlp", "b_up")] = ((L, m.ff), "bias")
        out[("layers", "mlp", "b_down")] = ((L, d), "bias")
    if m.norm_affine:
        for norm in ("attn_norm", "mlp_norm"):
            out[("layers", norm, "scale")] = ((L, d), "scale")
            out[("layers", norm, "bias")] = ((L, d), "bias")
        out[("final_norm", "scale")] = ((d,), "scale")
        out[("final_norm", "bias")] = ((d,), "bias")
    return out


def layer_matrix_params(m: Dims) -> int:
    """Weights of one layer's matrix products (attention and MLP)."""
    attn = m.d * m.head_dim * (2 * m.heads + 2 * m.kv_heads)
    mlp = (3 if m.gated else 2) * m.d * m.ff
    return attn + mlp


def layer_vector_params(m: Dims) -> int:
    """Biases and norm scales of one layer."""
    n = 0
    if m.bias:
        n += m.head_dim * (m.heads + 2 * m.kv_heads) + m.d + m.ff + m.d
    if m.norm_affine:
        n += 4 * m.d
    return n


def param_count(m: Dims) -> int:
    emb = m.vocab * m.d * (1 if m.tied else 2)
    final = 2 * m.d if m.norm_affine else 0
    return emb + m.layers * (layer_matrix_params(m) + layer_vector_params(m)) + final


def kv_bytes_per_token(m: Dims) -> int:
    """Keys and values of one position, over all layers, in bfloat16."""
    return m.layers * 2 * m.kv_heads * m.head_dim * BF16


def attention_flops(m: Dims, keys: int) -> int:
    """Scores and weighted sum of one query against ``keys`` positions."""
    return m.layers * 4 * m.heads * m.head_dim * keys


def prefill_flops(m: Dims, n: int) -> int:
    """A prompt of ``n`` tokens, causal, with logits at its last position."""
    linear = 2 * m.layers * layer_matrix_params(m) * n
    attn = m.layers * 4 * m.heads * m.head_dim * (n * (n + 1) // 2)
    return linear + attn + 2 * m.d * m.vocab


def decode_flops(m: Dims, positions: Iterable[int]) -> int:
    """One decode round: each active lane's token at its ``position``
    (the index its key and value are written at) attends to
    ``position + 1`` keys and produces a full row of logits."""
    per_lane = 2 * (m.layers * layer_matrix_params(m) + m.d * m.vocab)
    total = 0
    for p in positions:
        total += per_lane + attention_flops(m, p + 1)
    return total


def decode_weight_bytes(m: Dims) -> int:
    """Weights one decode round reads, in bfloat16: every layer and the
    output head (the embedding rows a round reads are negligible)."""
    return BF16 * (
        m.layers * (layer_matrix_params(m) + layer_vector_params(m))
        + m.d * m.vocab
        + (2 * m.d if m.norm_affine else 0)
    )


def decode_bytes(m: Dims, positions: Iterable[int]) -> int:
    """Bytes one decode round needs: the weights once, and per active
    lane the keys and values of ``position + 1`` positions read and one
    written."""
    kv = kv_bytes_per_token(m)
    total = decode_weight_bytes(m)
    for p in positions:
        total += kv * (p + 2)
    return total


# ---------------------------------------------------------- reference ----


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
