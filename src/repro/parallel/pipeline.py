"""Pipeline parallelism via shard_map + ppermute (GPipe schedule).

The paper scales out by instantiating PUs that process *independent* frames
(pure data parallelism).  At LM scale a 1000+-node fleet also needs layer
pipelining; this module adds it as a composable runner over the same
stacked-layer parameter layout the models already use for scan.

Design (classic shift-register formulation, cf. the shard_map pipelining
pattern):

- The mesh gains a ``stage`` axis of size S; the stacked layer params
  (L, ...) are sharded S-ways along the layer axis, so each device group
  holds L/S contiguous layers.
- The global batch is split into M microbatches.  At step t, stage s runs
  its local layers over microbatch (t - s); between steps, activations
  shift one stage forward via ``ppermute``.  The pipe drains after
  M + S - 1 steps.  Bubble fraction = (S-1)/(M+S-1) -- reported by
  :func:`bubble_fraction` so configs can be sanity-checked.
- Backward happens through autodiff: ppermute's transpose is the reverse
  permute, so one jax.grad over the runner yields the correct interleaved
  backward schedule for free.

The runner is deliberately *model-agnostic*: it takes any
``layer_fn(params_slice, x) -> x`` and works for every architecture family
whose blocks are a scanned stack (all 10 assigned archs).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    """GPipe bubble overhead: (S-1) / (M+S-1)."""
    return (n_stages - 1) / (n_microbatches + n_stages - 1)


def _stage_index(mesh: Mesh, axis: str) -> jax.Array:
    return jax.lax.axis_index(axis)


def pipeline_apply(
    layer_fn: Callable[[Any, jax.Array], jax.Array],
    stacked_params: Any,          # pytree, leaves (L, ...) stacked over layers
    x: jax.Array,                 # (B, ...) global batch on entry
    mesh: Mesh,
    n_microbatches: int,
    stage_axis: str = "stage",
    layers_per_stage: Optional[int] = None,
) -> jax.Array:
    """Run L stacked layers over x with GPipe pipelining along ``stage_axis``.

    Semantically identical to

        for i in range(L): x = layer_fn(tree_slice(params, i), x)

    but executed with the layer stack split across ``stage_axis`` and
    microbatched activations flowing through ppermute.
    """
    n_stages = mesh.shape[stage_axis]
    n_layers = jax.tree.leaves(stacked_params)[0].shape[0]
    assert n_layers % n_stages == 0, (n_layers, n_stages)
    assert x.shape[0] % n_microbatches == 0, (x.shape, n_microbatches)
    lps = layers_per_stage or n_layers // n_stages

    mb = x.reshape((n_microbatches, x.shape[0] // n_microbatches) + x.shape[1:])

    # shard specs: layers dim over stages; microbatch dim replicated inside
    # (the batch may additionally be sharded over 'data' by the caller's jit).
    param_spec = jax.tree.map(lambda _: P(stage_axis), stacked_params)
    in_spec = (param_spec, P())        # microbatches enter replicated
    out_spec = P()

    def stage_prog(params_local, mb_local):
        """Runs on every stage group; params_local leaves are (L/S, ...)."""
        stage = jax.lax.axis_index(stage_axis)
        n_mb = mb_local.shape[0]
        mb_shape = mb_local.shape[1:]

        def run_local_layers(carry_x):
            def body(h, layer_params):
                return layer_fn(layer_params, h), None
            h, _ = jax.lax.scan(body, carry_x, params_local)
            return h

        steps = n_mb + n_stages - 1
        state = jnp.zeros(mb_shape, mb_local.dtype)   # activation register
        outputs = jnp.zeros_like(mb_local)

        def step_fn(t, carry):
            state, outputs = carry
            # stage 0 ingests microbatch t (when valid)
            take = jnp.clip(t, 0, n_mb - 1)
            injected = jnp.where(
                (stage == 0) & (t < n_mb),
                mb_local[take],
                state,
            )
            h = run_local_layers(injected)
            # last stage writes its finished microbatch (t - (S-1))
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_mb - 1)
            valid_out = (stage == n_stages - 1) & (t >= n_stages - 1)
            outputs = jax.lax.cond(
                valid_out,
                lambda o: jax.lax.dynamic_update_slice(
                    o, h[None].astype(o.dtype), (out_idx,) + (0,) * len(mb_shape)
                ),
                lambda o: o,
                outputs,
            )
            # shift activations forward one stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            state = jax.lax.ppermute(h, stage_axis, perm)
            return (state, outputs)

        state, outputs = jax.lax.fori_loop(0, steps, step_fn, (state, outputs))
        # only the last stage holds real outputs; broadcast them to all
        # stages so the result is replicated (psum over one-hot mask).
        mask = (stage == n_stages - 1).astype(outputs.dtype)
        outputs = jax.lax.psum(outputs * mask, stage_axis)
        return outputs

    runner = jax.shard_map(
        stage_prog,
        mesh=mesh,
        in_specs=in_spec,
        out_specs=out_spec,
        check_vma=False,
    )
    out_mb = runner(stacked_params, mb)
    return out_mb.reshape(x.shape)


def tree_layer_slice(stacked_params: Any, i) -> Any:
    """Dynamic slice of layer i from stacked (L, ...) params."""
    return jax.tree.map(
        lambda p: jax.lax.dynamic_index_in_dim(p, i, 0, keepdims=False),
        stacked_params,
    )


def sequential_apply(
    layer_fn: Callable[[Any, jax.Array], jax.Array],
    stacked_params: Any,
    x: jax.Array,
) -> jax.Array:
    """Reference: the plain scan the pipeline must match bit-for-bit."""
    def body(h, layer_params):
        return layer_fn(layer_params, h), None
    h, _ = jax.lax.scan(body, x, stacked_params)
    return h
