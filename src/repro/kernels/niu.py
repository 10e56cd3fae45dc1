"""Pallas TPU kernel: the Noise Injection Unit (paper SS VI).

The paper's NIU is a *hardware block* that replaces a PU: each inference
round it reads the noiseless int8 weights of AIMC-emulated tiles from a
pristine HBM region, injects fresh device-noise instances, and overwrites
the weight regions the PU consumes.  This kernel is its TPU-native
realization: a tiled read-modify-write over the quantized weight buffer,
streaming (block_r x block_c) tiles HBM->VMEM, perturbing them with a
counter-based in-kernel RNG, and emitting the updated int8 payloads.

RNG: a stateless integer-mix hash of (seed, element index) -- the
counter-based construction hardware NIUs use, portable across interpret
mode (CPU validation) and TPU lowering (no backend PRNG primitives
needed).  Gaussian samples come from a Box-Muller transform of two
uniform draws.

Noise model (matches core/aimc.py's float path on the dequantized scale):
    sigma = prog_noise_scale * (0.25*|w| + 0.05*w_max)
    w'    = clip(round((drift*(w + sigma*N) + read*w_max*N') / 2^e), -128, 127)
with w = q * 2^e and w_max the tile's programmed range.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import resolve_interpret


def _mix(x: jax.Array) -> jax.Array:
    """xorshift-multiply integer mixer (lowbias32), uint32 -> uint32."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def _uniform(counter: jax.Array, salt: int) -> jax.Array:
    """(0,1) floats from the counter hash: the top 24 bits, centred in
    their bucket.  24 bits are exact in float32 and go through int32,
    which Mosaic can convert (it has no uint32 -> float32 cast)."""
    bits = _mix(counter ^ jnp.uint32(salt))
    hi = jax.lax.bitcast_convert_type(bits >> 8, jnp.int32)
    return (hi.astype(jnp.float32) + 0.5) * (1.0 / 2**24)


def _gaussian(counter: jax.Array, salt: int) -> jax.Array:
    u1 = _uniform(counter, salt)
    u2 = _uniform(counter, salt + 0x9E3779B9)
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(2.0 * jnp.pi * u2)


def _niu_kernel(
    seed_ref,        # (1, 1) int32
    q_ref,           # (br, bc) int8 pristine payload
    exp_ref,         # (1, 1) int32 power-of-two exponent
    wmax_ref,        # (1, 1) f32 programmed range
    out_ref,         # (br, bc) int8 noisy payload
    *,
    prog_noise_scale: float,
    read_noise_scale: float,
    drift: float,
    n_cols: int,
):
    i = pl.program_id(0)
    j = pl.program_id(1)
    br, bc = q_ref.shape

    scale = jnp.exp2(exp_ref[0, 0].astype(jnp.float32))
    w = q_ref[...].astype(jnp.float32) * scale
    w_max = wmax_ref[0, 0]

    # Per-element global counter: unique across the grid and the tile.
    row = jax.lax.broadcasted_iota(jnp.uint32, (br, bc), 0) + jnp.uint32(i * br)
    col = jax.lax.broadcasted_iota(jnp.uint32, (br, bc), 1) + jnp.uint32(j * bc)
    counter = (
        row * jnp.uint32(n_cols) + col
    ) ^ _mix(seed_ref[0, 0].astype(jnp.uint32))

    g = _gaussian(counter, 0x1234567)
    sigma_prog = prog_noise_scale * (0.25 * jnp.abs(w) + 0.05 * w_max)
    w_noisy = w + sigma_prog * g
    if drift != 1.0:
        w_noisy = w_noisy * drift
    if read_noise_scale > 0.0:
        g2 = _gaussian(counter, 0x7654321)
        w_noisy = w_noisy + read_noise_scale * w_max * g2

    q = jnp.clip(jnp.round(w_noisy / scale), -128, 127)
    out_ref[...] = q.astype(jnp.int8)


@functools.partial(
    jax.jit,
    static_argnames=(
        "prog_noise_scale", "read_noise_scale", "drift",
        "block_r", "block_c", "interpret",
    ),
)
def niu_refresh(
    q: jax.Array,                 # (R, C) int8 pristine payload
    exp: jax.Array,               # () int32/int8 pow2 exponent
    seed: jax.Array | int,        # () int32
    *,
    prog_noise_scale: float = 0.1,
    read_noise_scale: float = 0.02,
    drift: float = 1.0,
    block_r: int = 256,
    block_c: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """One NIU round: fresh noise instance on an int8 weight tile -> int8.

    ``interpret=None`` resolves via :func:`common.default_interpret`
    (interpreted off-TPU, compiled on TPU, env override).
    """
    interpret = resolve_interpret(interpret)
    r, c = q.shape
    pad_r, pad_c = (-r) % block_r, (-c) % block_c
    qp = jnp.pad(q, ((0, pad_r), (0, pad_c))) if (pad_r or pad_c) else q
    rp, cp = qp.shape

    exp_arr = jnp.asarray(exp, jnp.int32).reshape(1, 1)
    scale = jnp.exp2(exp_arr[0, 0].astype(jnp.float32))
    wmax = (jnp.max(jnp.abs(q.astype(jnp.float32))) * scale).reshape(1, 1)
    seed_arr = jnp.asarray(seed, jnp.int32).reshape(1, 1)

    out = pl.pallas_call(
        functools.partial(
            _niu_kernel,
            prog_noise_scale=prog_noise_scale,
            read_noise_scale=read_noise_scale,
            drift=drift,
            n_cols=c,   # unpadded: counters must match the oracle's grid
        ),
        grid=(rp // block_r, cp // block_c),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((block_r, block_c), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, block_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rp, cp), jnp.int8),
        interpret=interpret,
    )(seed_arr, qp, exp_arr, wmax)
    return out[:r, :c]


def niu_refresh_ref(
    q: jax.Array,
    exp: jax.Array,
    seed: jax.Array | int,
    *,
    prog_noise_scale: float = 0.1,
    read_noise_scale: float = 0.02,
    drift: float = 1.0,
) -> jax.Array:
    """Pure-jnp oracle: same counter-based RNG, no tiling."""
    r, c = q.shape
    scale = jnp.exp2(jnp.asarray(exp, jnp.int32).astype(jnp.float32))
    w = q.astype(jnp.float32) * scale
    w_max = jnp.max(jnp.abs(w))
    row = jax.lax.broadcasted_iota(jnp.uint32, (r, c), 0)
    col = jax.lax.broadcasted_iota(jnp.uint32, (r, c), 1)
    counter = (row * jnp.uint32(c) + col) ^ _mix(
        jnp.asarray(seed, jnp.int32).astype(jnp.uint32)
    )
    g = _gaussian(counter, 0x1234567)
    w_noisy = w + prog_noise_scale * (0.25 * jnp.abs(w) + 0.05 * w_max) * g
    if drift != 1.0:
        w_noisy = w_noisy * drift
    if read_noise_scale > 0.0:
        g2 = _gaussian(counter, 0x7654321)
        w_noisy = w_noisy + read_noise_scale * w_max * g2
    return jnp.clip(jnp.round(w_noisy / scale), -128, 127).astype(jnp.int8)
