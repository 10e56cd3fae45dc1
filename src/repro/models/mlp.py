"""Dense MLPs and top-k routed mixture-of-experts.

MoE uses GShard-style capacity dispatch but with index scatters instead of
(T, E, C) one-hot einsums, so dispatch memory is O(T*K + E*C*D) and the
whole block stays pjit-shardable: capacity shards over "data" (expert_cap
rule) and expert hidden dims over "model" (ff rule); an EP rule-set can
move experts onto their own axis without touching this code.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.common import mlp_act, mlp_is_gated
from repro.parallel import sharding as _sh
from repro.parallel.sharding import logical_constraint


# --------------------------------------------------------------- dense ----


def mlp_params(cfg, key, d_model=None, d_ff=None) -> dict:
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    keys = jax.random.split(key, 3)
    init = jax.nn.initializers.normal(0.02)
    p = {"w_up": init(keys[0], (d, f), jnp.float32),
         "w_down": init(keys[1], (f, d), jnp.float32)}
    if mlp_is_gated(cfg.mlp):
        p["w_gate"] = init(keys[2], (d, f), jnp.float32)
    if cfg.mlp_bias:
        p["b_up"] = jnp.zeros((f,), jnp.float32)
        p["b_down"] = jnp.zeros((d,), jnp.float32)
    return p


def mlp_axes(cfg) -> dict:
    ax = {"w_up": ("embed_d", "ff"), "w_down": ("ff", "embed_d")}
    if mlp_is_gated(cfg.mlp):
        ax["w_gate"] = ("embed_d", "ff")
    if cfg.mlp_bias:
        ax["b_up"] = ("ff",)
        ax["b_down"] = ("d_model",)
    return ax


def mlp_apply(cfg, p: dict, x: jax.Array) -> jax.Array:
    dt = x.dtype
    g = x @ p["w_up"].astype(dt) if "w_gate" not in p else x @ p["w_gate"].astype(dt)
    up = x @ p["w_up"].astype(dt) if "w_gate" in p else None
    if cfg.mlp_bias:
        g = g + p["b_up"].astype(dt)
    h = mlp_act(cfg.mlp, g, up)
    h = logical_constraint(h, "batch", None, "ff")
    y = h @ p["w_down"].astype(dt)
    if cfg.mlp_bias:
        y = y + p["b_down"].astype(dt)
    return y


# ----------------------------------------------------------------- MoE ----


def moe_params(cfg, key) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    keys = jax.random.split(key, 4)
    init = jax.nn.initializers.normal(0.02)
    p = {
        "router": init(keys[0], (d, e), jnp.float32),
        "w_up": init(keys[1], (e, d, f), jnp.float32),
        "w_down": init(keys[2], (e, f, d), jnp.float32),
    }
    if mlp_is_gated(cfg.mlp):
        p["w_gate"] = init(keys[3], (e, d, f), jnp.float32)
    return p


def moe_axes(cfg) -> dict:
    ax = {
        "router": ("embed_d", "experts"),
        "w_up": ("experts", "embed_d", "ff"),
        "w_down": ("experts", "ff", "embed_d"),
    }
    if mlp_is_gated(cfg.mlp):
        ax["w_gate"] = ("experts", "embed_d", "ff")
    return ax


def moe_capacity(cfg, tokens: int) -> int:
    cap = int(tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(cap, cfg.top_k)


def moe_apply(cfg, p: dict, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Routed MoE with locality-aware dispatch.

    When a sharding context is active and the batch is sharded, dispatch
    runs *locally* per device group via shard_map: each group routes its
    own tokens into its own (E, C_local, D) buffer against the (gathered)
    expert weights -- zero dispatch collectives.  Measured on
    granite/train_4k (SSPerf): global-buffer dispatch under GSPMD costs
    41-199 s/step of collectives; local dispatch removes all of it.

    Capacity semantics become per-group (C_local = local_tokens * k * cf /
    E), which is what EP systems deploy in practice -- documented in
    DESIGN.md SSArch-applicability.
    """
    mesh, rules = _sh._CTX.mesh, _sh._CTX.rules
    if mesh is not None and rules is not None:
        # Dispatch shards tokens over EVERY mesh axis -- batch rows over
        # the rule-set's batch axes, sequence over the remaining axes --
        # WITHOUT reshaping (merging two sharded dims forces a full
        # re-layout gather per layer: measured 3.9x collective regression
        # multi-pod).  Restricting to batch axes alone would replicate the
        # dispatch compute across the remaining axes (measured 16x
        # redundant MoE flops on mixtral/train_4k under fsdp_tp).
        batch_rule = tuple(
            a for a in _sh._axes_for("batch", rules, mesh)
            if mesh.shape[a] > 1
        )
        rest = tuple(
            a for a in mesh.axis_names
            if mesh.shape[a] > 1 and a not in batch_rule
        )
        b_div = 1
        for a in batch_rule:
            b_div *= mesh.shape[a]
        s_div = 1
        for a in rest:
            s_div *= mesh.shape[a]
        axes0 = batch_rule if (b_div and x.shape[0] % b_div == 0) else ()
        axes1 = rest if (s_div and x.shape[1] % s_div == 0) else ()
        shard_axes = (axes0, axes1)
        n_shards = 1
        for a in axes0 + axes1:
            n_shards *= mesh.shape[a]
        # Dispatch cost model (EXPERIMENTS.md SSPerf, cell A): local
        # dispatch replicates the expert bank (E*3*D*F bytes/layer) per
        # device group but moves no tokens; global dispatch keeps weights
        # sharded but its dynamic-index scatters generate heavy GSPMD
        # traffic proportional to the capacity buffer.  Local pays iff the
        # token traffic exceeds the expert bank: T > E*F.
        # Measured (collective term, 256 chips):
        #   granite train  T=1M >> 20k  local 4.0s  vs global 41.0s (10.2x)
        #   mixtral train  T=1M >> 115k local 17.2s vs global 32.8s  (1.9x)
        #   mixtral decode T=128 < 115k local 3.8s  vs global 13ms   (294x)
        # cfg.moe_dispatch ('local'/'global') overrides the rule.
        tokens_global = x.shape[0] * x.shape[1]
        if cfg.moe_dispatch == "local":
            local_pays = True
        elif cfg.moe_dispatch == "global":
            local_pays = False
        else:
            local_pays = tokens_global > cfg.n_experts * cfg.d_ff
        if (axes0 or axes1) and n_shards > 1 and local_pays:
            return _moe_apply_local(cfg, p, x, mesh, shard_axes)
    return _moe_apply_global(cfg, p, x)


def _moe_apply_local(cfg, p, x, mesh, shard_axes):
    """shard_map dispatch with (batch-axes, seq-axes) token sharding:
    tokens stay on their devices; expert weights enter replicated (GSPMD
    gathers the ZeRO shards at the boundary)."""
    from jax.sharding import PartitionSpec as P

    axes0, axes1 = shard_axes

    def _part(axes):
        if not axes:
            return None
        return axes if len(axes) > 1 else axes[0]

    x_spec = P(_part(axes0), _part(axes1))
    aux_spec = P(_part(axes0 + axes1) if (axes0 or axes1) else None)
    w_spec = jax.tree.map(lambda _: P(), p)

    def local_fn(p_local, x_local):
        # inside shard_map every mesh axis is manual: with_sharding_constraint
        # on them is illegal AND meaningless -- suspend the logical-axis ctx.
        with _sh.activation_sharding_ctx(None, None):
            y, aux = _moe_apply_global(cfg, p_local, x_local)
        return y, aux.reshape(1)

    y, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(w_spec, x_spec),
        out_specs=(x_spec, aux_spec),
        check_vma=False,
    )(p, x)
    return y, jnp.mean(aux)


def _moe_apply_global(cfg, p: dict, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Routed MoE.  x: (B, S, D) -> (y, aux_loss).

    Dispatch: top-k router; per-(token, k) target slot (e, pos) computed via
    a (T, E) assignment cumsum; token features scattered into an (E, C, D)
    buffer with mode="drop" enforcing capacity; combined back with a gather.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    cap = moe_capacity(cfg, t)
    dt = x.dtype

    xt = x.reshape(t, d)
    logits = (xt @ p["router"].astype(dt)).astype(jnp.float32)    # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, experts = jax.lax.top_k(probs, k)                  # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )

    # Load-balancing auxiliary loss (Switch-style): E * sum(f_e * p_e).
    onehot = jax.nn.one_hot(experts, e, dtype=jnp.float32)        # (T, K, E)
    assign = jnp.sum(onehot, axis=1)                              # (T, E)
    frac_tokens = jnp.mean(assign, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs) / k

    # Position of token t in expert e's buffer (GShard cumsum).
    positions_te = jnp.cumsum(assign, axis=0) - 1.0               # (T, E)
    pos = jnp.take_along_axis(
        positions_te, experts.astype(jnp.int32), axis=1
    ).astype(jnp.int32)                                           # (T, K)
    keep = pos < cap
    pos_safe = jnp.where(keep, pos, cap)                          # OOB -> drop

    e_flat = experts.reshape(t * k)
    pos_flat = pos_safe.reshape(t * k)
    x_rep = jnp.repeat(xt[:, None, :], k, axis=1).reshape(t * k, d)

    buf = jnp.zeros((e, cap, d), dt)
    buf = buf.at[e_flat, pos_flat].add(x_rep, mode="drop")
    buf = logical_constraint(buf, "experts", "expert_cap", None)

    g = jnp.einsum("ecd,edf->ecf", buf,
                   (p["w_gate"] if "w_gate" in p else p["w_up"]).astype(dt))
    up = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(dt)) if "w_gate" in p else None
    h = mlp_act(cfg.mlp, g, up)
    h = logical_constraint(h, "experts", "expert_cap", "ff")
    y_e = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dt))   # (E, C, D)

    gathered = y_e.at[e_flat, pos_flat].get(
        mode="fill", fill_value=0
    )                                                             # (T*K, D)
    w = jnp.where(keep, gate_vals, 0.0).reshape(t * k, 1).astype(dt)
    y = jnp.sum((gathered * w).reshape(t, k, d), axis=1)
    return y.reshape(b, s, d), aux
