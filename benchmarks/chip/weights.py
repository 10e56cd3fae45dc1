"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights, not the program: the engine is handed
this tree, and the plain reference makes the same tree again from the
same seed once the program's state is freed.  The tree has the layout
that the cell's architecture module gives (``archs/<name>.py``,
``layout(m)``); :func:`check_layout` holds it to the program's own
``init_params`` shapes, so a layout change shows as an error and not as
a wrong answer.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

STD = 0.02


def seed_key(seed: int) -> jax.Array:
    """A key for any whole seed, 64-bit ones included."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _make(leaves: Tuple, key: jax.Array) -> dict:
    tree: dict = {}
    for i, (path, (shape, kind)) in enumerate(leaves):
        x = STD * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if kind == "scale":
            x = 1.0 + x
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = x
    return tree


def make_weights(layout: Dict[Tuple[str, ...], Tuple[Tuple[int, ...], str]],
                 seed: int) -> dict:
    """The whole float32 weight tree of ``layout`` (an architecture's
    ``layout(m)``: leaf path -> (shape, kind), kind 'normal', 'scale' or
    'bias') for ``seed``, on the device.  Leaf ``i`` in the sorted order
    of paths is drawn from the seed's key folded with ``i``: normal with
    deviation ``STD``, and scales at 1 plus that draw."""
    return jax.jit(_make, static_argnums=0)(tuple(sorted(layout.items())), seed_key(seed))


def check_layout(weights_shape: dict, program_shape: dict) -> None:
    """Raise unless the two trees have the same paths, shapes and dtypes."""
    mine = {jax.tree_util.keystr(p): (a.shape, a.dtype)
            for p, a in jax.tree_util.tree_leaves_with_path(weights_shape)}
    theirs = {jax.tree_util.keystr(p): (a.shape, a.dtype)
              for p, a in jax.tree_util.tree_leaves_with_path(program_shape)}
    if mine != theirs:
        diff = sorted(set(mine.items()) ^ set(theirs.items()))
        raise ValueError(f"weight layout differs from the program's: {diff}")
