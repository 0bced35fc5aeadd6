"""p-core analogue: depthwise conv Pallas kernel with VMEM sliding-window
reuse (the TPU port of the paper's line buffer, DESIGN.md §2).

The dual-OPU p-core keeps a T_w*(T_kh-1)+T_kw line buffer in BRAM so each ifm
pixel is read from DRAM once and reused across the K_h x K_w window.  On TPU
the analogue is: bring the halo rows of an output-row tile into VMEM once and
compute every window tap from them — HBM traffic is 1x the ifm instead of
K_h*K_w x.  Channel parallelism maps to the VPU lanes (channels-last, so the
per-tap multiply is a (bh, Wo, 128) vector op), mirroring the p-core's
per-PE-per-channel layout.

Grid: (N, C / block_c, H_out tiles).  The kernel takes the map unpadded:
each step DMAs only the map rows its output-row block reads (an
element-indexed block from ``util.halo_start``, the tile's first halo row
clipped to the map; tiles overlap by K_h - stride rows), and
``util.dw_halo_tile`` builds the zero border in a VMEM halo tile and reads
stride-2 columns from it with a strided read.  So the conv padding, the
stride phases and the last tile's extra rows never exist in HBM, and VMEM
use is bounded whatever the map size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.util import (LANES, apply_act, dw_halo_tile, halo_block,
                                halo_start, halo_tile_width, lane_tile,
                                pad_axis, resolve_interpret, row_tiling,
                                vmem_row_bytes)


def _dw_kernel(x_ref, w_ref, *rest, h: int, pad: int, kh: int, kw: int,
               stride: int, fuse_bias: bool, act: str | None):
    """x_ref: (1, rows, W, bc) halo rows of the unpadded map; w_ref:
    (kh, kw, bc); o_ref: (1, bh, Wo, bc); xs_ref: the VMEM halo tile,
    one lane tile of channels at a time.  The bias operand only exists
    when ``fuse_bias`` — no zeros block is streamed for bias-less convs."""
    if fuse_bias:
        b_ref, o_ref, xs_ref = rest
    else:
        (o_ref, xs_ref), b_ref = rest, None
    _, bh, wo, bc = o_ref.shape
    for c0 in range(0, bc, LANES):
        cw = min(LANES, bc - c0)
        acc = dw_halo_tile(x_ref, xs_ref, w_ref, c0, cw,
                           tile=pl.program_id(2), h=h, pad=pad, kh=kh, kw=kw,
                           stride=stride, bh=bh, wo=wo)
        if fuse_bias:
            acc = acc + b_ref[0, pl.ds(c0, cw)].astype(jnp.float32)
        o_ref[0, :, :, pl.ds(c0, cw)] = apply_act(acc, act).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("stride", "pad", "act",
                                             "block_c", "interpret"))
def depthwise_conv2d(x: jax.Array, w: jax.Array,
                     bias: jax.Array | None = None, *, stride: int = 1,
                     pad: int = 1, act: str | None = None,
                     block_c: int = 128,
                     interpret: bool | None = None) -> jax.Array:
    """NHWC depthwise conv.  x: (N,H,W,C); w: (K_h,K_w,C); bias: (C,).
    ``block_c`` is lane-aligned by ``lane_tile``; the output-row tile is
    sized to the VMEM budget."""
    interpret = resolve_interpret(interpret)
    n, h, wd, c = x.shape
    kh, kw, cw = w.shape
    assert cw == c, (w.shape, c)
    bc = lane_tile(block_c, c)
    ho = (h + 2 * pad - kh) // stride + 1
    wo = (wd + 2 * pad - kw) // stride + 1
    tile = (halo_tile_width(wd, pad), min(bc, LANES))  # VMEM halo tile row
    row = (2 * stride * vmem_row_bytes(wd, bc)       # halo, double-buffered
           + stride * vmem_row_bytes(*tile)          # halo tile
           + 3 * vmem_row_bytes(wo, bc))             # acc + output x2
    bh, n_ht, span, _ = row_tiling(ho, stride, kh, h + 2 * pad, row)
    rows = min(span, h)
    # channels padded to a block multiple only where the block does not
    # divide them; the spatial border is built in VMEM
    xp = pad_axis(x, 3, bc)
    wp = pad_axis(w, 2, bc)
    fuse_bias = bias is not None
    n_c = xp.shape[3] // bc
    grid = (n, n_c, n_ht)
    in_specs = [
        # one channel tile: a literal 0 offset, which the compiler can
        # prove lane-aligned even when bc < 128
        pl.BlockSpec(halo_block(rows, wd, bc),
                     lambda i, j, t: (i, halo_start(t, bh, stride, pad, h,
                                                    rows), 0,
                                      j * bc if n_c > 1 else 0)),
        pl.BlockSpec((kh, kw, bc), lambda i, j, t: (0, 0, j)),
    ]
    operands = [xp, wp]
    if fuse_bias:
        # a (1, C) row: a 1-D block narrower than the array is refused
        # by the TPU compiler (its XLA layout tiles the whole vector)
        in_specs.append(pl.BlockSpec((1, bc), lambda i, j, t: (0, j)))
        operands.append(pad_axis(bias, 0, bc).reshape(1, -1))
    return pl.pallas_call(
        functools.partial(_dw_kernel, h=h, pad=pad, kh=kh, kw=kw,
                          stride=stride, fuse_bias=fuse_bias, act=act),
        grid=grid,
        in_specs=in_specs,
        # the last row tile (and channel tile) may overhang: Pallas drops
        # the rows past ``ho``, so no slice of the output follows
        out_specs=pl.BlockSpec((1, bh, wo, bc),
                               lambda i, j, t: (i, t, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, ho, wo, c), x.dtype),
        scratch_shapes=[pltpu.VMEM((span, *tile), jnp.float32)],
        interpret=interpret,
    )(*operands)
