"""Pallas kernel: IM2COL patch extraction (paper SS II-B, Fig. 3).

The paper realizes IM2COL as *address/length command bundles* to the AXI
DataMover: feature maps stay in HWC order in HBM and the DMA engine gathers
strided segments into the activation buffer, forming the patch matrix
on-the-fly.  The TPU-native analogue: the kernel's index arithmetic plays
the command generator, and the Pallas block pipeline plays the DMA -- each
grid step gathers the strided rows of one output-row block from the (padded)
feature map in VMEM and emits the corresponding patch-matrix rows.

Grid: one program per output row (OH); each program emits the (OW, k*k*C)
patch block for that row as k*k contiguous (OW, C) segments, each stored
at its own lane offset -- a direct transcription of the "address and
length bundles ... according to the IFM dimensions and Conv
characteristics".  A stride above 1 is taken out of the gather before the
kernel: the padded map is split into ``stride`` column phases, so column
``kj + stride*o`` is column ``kj // stride + o`` of phase ``kj % stride``
and every segment the kernel reads is contiguous (Mosaic has no strided
sublane slice).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import resolve_interpret


def _im2col_kernel(img_ref, out_ref, *, k: int, stride: int, ow: int, c: int):
    base = pl.program_id(0) * stride
    for ki in range(k):
        for kj in range(k):
            idx = ki * k + kj
            # Patch layout: [(ki, kj) outer, C inner] -- matches weight
            # reshape w4d.transpose(3,0,1,2).reshape(Cout, k*k*Cin).
            out_ref[0, :, idx * c:(idx + 1) * c] = img_ref[
                kj % stride, base + ki, pl.ds(kj // stride, ow), :
            ]


@functools.partial(jax.jit, static_argnames=("k", "stride", "pad", "interpret"))
def im2col(
    img: jax.Array,        # (H, W, C), int8 (or any dtype)
    k: int,
    stride: int = 1,
    pad: int = 0,
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Patch matrix (OH*OW, k*k*C) from an HWC feature map.

    ``interpret=None`` resolves via :func:`common.default_interpret`.
    """
    interpret = resolve_interpret(interpret)
    h, w, c = img.shape
    oh = (h + 2 * pad - k) // stride + 1
    ow = (w + 2 * pad - k) // stride + 1
    # pad the width to a whole number of phases, then split the columns
    # into their stride phases: (stride, Hp, Wp/stride, C)
    wp = -(-(w + 2 * pad) // stride) * stride
    imgp = jnp.pad(img, ((pad, pad), (pad, wp - w - pad), (0, 0)))
    phases = imgp.reshape(h + 2 * pad, wp // stride, stride, c).transpose(2, 0, 1, 3)

    out = pl.pallas_call(
        functools.partial(_im2col_kernel, k=k, stride=stride, ow=ow, c=c),
        grid=(oh,),
        in_specs=[pl.no_block_spec],
        out_specs=pl.BlockSpec((1, ow, k * k * c), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((oh, ow, k * k * c), img.dtype),
        interpret=interpret,
    )(phases)
    return out.reshape(oh * ow, k * k * c)
