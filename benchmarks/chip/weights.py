"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights, not the program: the engine is handed
this tree, and the plain reference makes the same tree again from the
same seed once the program's state is freed.  The tree has the layout
the program's transformer takes (stacked layers, ``(in, out)``
matrices); :func:`check_layout` holds it to the program's own
``init_params`` shapes, so a layout change shows as an error and not as
a wrong answer.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from benchmarks.chip.work import Dims

STD = 0.02


def seed_key(seed: int) -> jax.Array:
    """A key for any whole seed, 64-bit ones included."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _layout(m: Dims) -> Dict[Tuple[str, ...], Tuple[Tuple[int, ...], str]]:
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


def _make(m: Dims, key: jax.Array) -> dict:
    tree: dict = {}
    for i, (path, (shape, kind)) in enumerate(sorted(_layout(m).items())):
        x = STD * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if kind == "scale":
            x = 1.0 + x
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = x
    return tree


def make_weights(m: Dims, seed: int) -> dict:
    """The whole float32 weight tree of ``m`` for ``seed``, on the device."""
    return jax.jit(_make, static_argnums=0)(m, seed_key(seed))


def check_layout(weights_shape: dict, program_shape: dict) -> None:
    """Raise unless the two trees have the same paths, shapes and dtypes."""
    mine = {jax.tree_util.keystr(p): (a.shape, a.dtype)
            for p, a in jax.tree_util.tree_leaves_with_path(weights_shape)}
    theirs = {jax.tree_util.keystr(p): (a.shape, a.dtype)
              for p, a in jax.tree_util.tree_leaves_with_path(program_shape)}
    if mine != theirs:
        diff = sorted(set(mine.items()) ^ set(theirs.items()))
        raise ValueError(f"weight layout differs from the program's: {diff}")
