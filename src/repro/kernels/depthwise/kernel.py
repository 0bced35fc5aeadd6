"""p-core analogue: depthwise conv Pallas kernel with VMEM sliding-window
reuse (the TPU port of the paper's line buffer, DESIGN.md §2).

The dual-OPU p-core keeps a T_w*(T_kh-1)+T_kw line buffer in BRAM so each ifm
pixel is read from DRAM once and reused across the K_h x K_w window.  On TPU
the analogue is: bring a (H+K-1, W+K-1, block_c) halo tile into VMEM once and
compute every window tap from it — HBM traffic is 1x the ifm instead of
K_h*K_w x.  Channel parallelism maps to the VPU lanes (channels-last, so the
per-tap multiply is a (Ho, Wo, block_c) vector op), mirroring the p-core's
per-PE-per-channel layout.

Grid: (N, C / block_c, H_out tiles).  Each step DMAs only the halo rows
its output-row block reads (an element-indexed block; tiles overlap by
K_h - 1 rows), so VMEM use is bounded whatever the map size, and reads
every tap from that tile with ``util.window_tap`` (stride-2 columns are
phase-split in the wrapper; the TPU compiler refuses strided slices).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.util import (apply_act, halo_block, lane_tile,
                                pad_axis, resolve_interpret, row_tiling,
                                split_w_phases, vmem_row_bytes, window_tap)


def _dw_kernel(x_ref, w_ref, *rest, kh: int, kw: int, stride: int, wh: int,
               fuse_bias: bool, act: str | None):
    """x_ref: (1, span, Wp, bc) padded halo rows, columns stride-phase
    split (phase width ``wh``); w_ref: (kh, kw, bc);
    o_ref: (1, bh, Wo, bc).  The bias operand only exists when
    ``fuse_bias`` — no zeros block is streamed for bias-less convs."""
    if fuse_bias:
        b_ref, o_ref = rest
    else:
        (o_ref,), b_ref = rest, None
    _, bh, wo, bc = o_ref.shape
    acc = jnp.zeros((bh, wo, bc), jnp.float32)
    for i in range(kh):          # unrolled window taps — every tap reads the
        for j in range(kw):      # same VMEM tile (line-buffer reuse)
            tap = window_tap(x_ref, (0,), i, j, bh, wo, stride, wh)
            acc = acc + tap.astype(jnp.float32) * w_ref[i, j, :].astype(
                jnp.float32)
    if fuse_bias:
        acc = acc + b_ref[...].astype(jnp.float32)
    o_ref[0] = apply_act(acc, act).astype(o_ref.dtype)


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
    wp_ = wd + 2 * pad
    row = (2 * stride * vmem_row_bytes(wp_, bc)      # halo, double-buffered
           + 3 * vmem_row_bytes(wo, bc))             # acc + output x2
    bh, n_ht, span, extra_h = row_tiling(ho, stride, kh, h + 2 * pad, row)
    # pad channels to a block multiple, spatial by the conv padding (plus
    # the rows the last tile's halo reads)
    xp, wh = split_w_phases(
        pad_axis(jnp.pad(x, ((0, 0), (pad, pad + extra_h), (pad, pad),
                             (0, 0))), 3, bc), stride)
    wp = pad_axis(w, 2, bc)
    fuse_bias = bias is not None
    cp = xp.shape[3]
    n_c = cp // bc
    grid = (n, n_c, n_ht)
    in_specs = [
        # one channel tile: a literal 0 offset, which the compiler can
        # prove lane-aligned even when bc < 128
        pl.BlockSpec(halo_block(span, xp.shape[2], bc),
                     lambda i, j, t: (i, t * bh * stride, 0,
                                      j * bc if n_c > 1 else 0)),
        pl.BlockSpec((kh, kw, bc), lambda i, j, t: (0, 0, j)),
    ]
    operands = [xp, wp]
    if fuse_bias:
        in_specs.append(pl.BlockSpec((bc,), lambda i, j, t: (j,)))
        operands.append(pad_axis(bias, 0, bc))
    out = pl.pallas_call(
        functools.partial(_dw_kernel, kh=kh, kw=kw, stride=stride, wh=wh,
                          fuse_bias=fuse_bias, act=act),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bh, wo, bc),
                               lambda i, j, t: (i, t, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, n_ht * bh, wo, cp), x.dtype),
        interpret=interpret,
    )(*operands)
    return out[:, :ho, :, :c]
