"""c-core analogue: implicit-GEMM conv + tiled GEMM Pallas kernels.

The dual-OPU c-core broadcasts one ifm pixel across the PE array and exploits
input/output channel parallelism — on TPU that is a GEMM over conv patches.
The seed materialized the full im2col patch matrix in HBM (a K_h*K_w x
activation blow-up) before the GEMM ever ran; ``conv2d_implicit_gemm`` instead
keeps the NHWC feature map as-is and assembles each (block_m x block_k) patch
tile *inside the kernel* from a halo tile resident in VMEM, so HBM traffic is
~1x the ifm (DESIGN.md §1).  ``im2col`` survives only in ref.py as the test
oracle.

Grid: (N, H_out tiles, C_o tiles).  Each step DMAs only the halo rows its
output-row block reads (an element-indexed block, so VMEM use is bounded
whatever the image size) and keeps them resident across the inner C_o
tiles.  Each step runs K_h*K_w MXU dots of (block_h*W_out, C_i) @
(C_i, block_n), each tap read from the halo ref (``util.window_tap``:
contiguous reads only — the TPU compiler refuses strided slices of loaded
values and strided ref reads wider than one lane tile), accumulated in a
float32 VMEM scratch (the overlay's output-buffer partial sums, §III-A),
then a fused bias + ReLU/ReLU6 epilogue (the overlay's post-processing
unit).

``matmul_bias_act`` is the plain tiled GEMM used by the 1x1 (pointwise / fc)
fast path, where im2col is the identity.  Block shapes default to
(128, 128, 128): MXU-native, 3 * 128*128*4B = 192 KiB of VMEM per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.util import (apply_act, cdiv, halo_block, lane_tile,
                                mxu_dot, pad_axis, pad_to,
                                resolve_interpret, row_tiling,
                                split_w_phases, vmem_row_bytes, window_tap)


DEFAULT_BLOCK = (128, 128, 128)  # (block_m, block_n, block_k)


def _apply_epilogue(out, b_ref, act: str | None):
    if b_ref is not None:
        out = out + b_ref[...].astype(jnp.float32)
    return apply_act(out, act)


# --------------------------------------------------------------------------
# tiled GEMM (the 1x1 / fc fast path, and the building block of the tests)
# --------------------------------------------------------------------------
def _matmul_kernel(x_ref, w_ref, *rest, nk: int, fuse_bias: bool,
                   act: str | None):
    """One (i, j, k) grid step: acc[i,j] += x[i,k] @ w[k,j].

    The bias operand only exists when ``fuse_bias`` — no zeros block is
    allocated or streamed for bias-less GEMMs.
    """
    if fuse_bias:
        b_ref, o_ref, acc_ref = rest
    else:
        (o_ref, acc_ref), b_ref = rest, None

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += mxu_dot(x_ref[...], w_ref[...])

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        o_ref[...] = _apply_epilogue(acc_ref[...], b_ref,
                                     act).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "act", "interpret"))
def matmul_bias_act(x: jax.Array, w: jax.Array, bias: jax.Array | None = None,
                    *, block: tuple[int, int, int] = DEFAULT_BLOCK,
                    act: str | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """(M, K) @ (K, N) + bias with fused activation, Pallas-tiled.

    Shapes are padded up to the block grid; the result is sliced back.
    ``interpret=None`` auto-detects: interpret on CPU, compiled on TPU.
    """
    interpret = resolve_interpret(interpret)
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    bm = min(block[0], max(M, 8))
    bn = lane_tile(block[1], N)
    bk = lane_tile(block[2], K)
    xp = pad_to(x, (bm, bk))
    wp = pad_to(w, (bk, bn))
    fuse_bias = bias is not None
    Mp, Kp = xp.shape
    _, Np = wp.shape
    nk = Kp // bk
    grid = (Mp // bm, Np // bn, nk)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
    ]
    operands = [xp, wp]
    if fuse_bias:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        operands.append(pad_to(bias.reshape(1, N), (1, bn)))
    out = pl.pallas_call(
        functools.partial(_matmul_kernel, nk=nk, fuse_bias=fuse_bias,
                          act=act),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(*operands)
    return out[:M, :N]


# --------------------------------------------------------------------------
# implicit-GEMM conv (K > 1): no HBM patch matrix, ever
# --------------------------------------------------------------------------
def _implicit_gemm_kernel(x_ref, w_ref, *rest, kh: int, kw: int, stride: int,
                          wh: int, fuse_bias: bool, act: str | None):
    """One (n, ht, co) grid step of the implicit GEMM.

    x_ref:   (1, span_h, Wp, C) — the halo rows of the padded image that
             this output-row block reads (an element-indexed block: tiles
             overlap by kh - 1 rows, HBM traffic ~1x the ifm), columns
             stride-phase split (phase width ``wh``).
    w_ref:   (kh, kw, C, bn)
    b_ref:   (1, bn) — only present when ``fuse_bias``
    o_ref:   (1, bh, wo, bn)
    acc_ref: (bh*wo, bn) float32 VMEM scratch accumulator.

    The (bh*wo, C) patch tile of each window tap is read from the halo
    ref (``window_tap``) — the in-kernel im2col — and fed straight to the
    MXU.
    """
    if fuse_bias:
        b_ref, o_ref, acc_ref = rest
    else:
        (o_ref, acc_ref), b_ref = rest, None
    _, bh, wo, bn = o_ref.shape
    c = x_ref.shape[3]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    for i in range(kh):                # unrolled window taps: each gathers a
        for j in range(kw):            # patch tile from the same VMEM halo
            tap = window_tap(x_ref, (0,), i, j, bh, wo, stride, wh)
            acc_ref[...] += mxu_dot(tap.reshape(bh * wo, c), w_ref[i, j])
    out = _apply_epilogue(acc_ref[...], b_ref, act)
    o_ref[0] = out.reshape(bh, wo, bn).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("stride", "pad", "act",
                                             "block_h", "block_n",
                                             "interpret"))
def conv2d_implicit_gemm(x: jax.Array, w: jax.Array,
                         bias: jax.Array | None = None, *, stride: int = 1,
                         pad: int = 0, act: str | None = None,
                         block_h: int = 0, block_n: int = 128,
                         interpret: bool | None = None) -> jax.Array:
    """NHWC conv as implicit GEMM: patch tiles assembled in VMEM, no
    (N*Ho*Wo, Kh*Kw*C) intermediate in HBM.

    x: (N, H, W, C_i); w: (K_h, K_w, C_i, C_o); bias: (C_o,) or None.
    ``block_h`` output rows per grid step (0 = auto: aim for a ~256-row
    GEMM M-tile), capped so the halo tile fits the VMEM budget;
    ``block_n`` output-channel tile (lane-aligned by ``lane_tile``).
    """
    interpret = resolve_interpret(interpret)
    n, h, wd, ci = x.shape
    kh, kw, ci2, co = w.shape
    assert ci == ci2, (x.shape, w.shape)
    ho = (h + 2 * pad - kh) // stride + 1
    wo = (wd + 2 * pad - kw) // stride + 1
    wp_ = wd + 2 * pad
    bn = lane_tile(block_n, co)
    row = (2 * stride * vmem_row_bytes(wp_, ci)       # halo, double-buffered
           + 3 * vmem_row_bytes(wo, bn))              # acc + output x2
    bh, n_ht, span_h, extra_h = row_tiling(
        ho, stride, kh, h + 2 * pad, row,
        limit=block_h if block_h > 0 else cdiv(256, wo))
    xp, wh = split_w_phases(
        jnp.pad(x, ((0, 0), (pad, pad + extra_h), (pad, pad), (0, 0))),
        stride)
    wx = xp.shape[2]
    wp = pad_axis(w, 3, bn)
    cop = wp.shape[3]
    fuse_bias = bias is not None
    grid = (n, n_ht, cop // bn)
    in_specs = [
        pl.BlockSpec(halo_block(span_h, wx, ci),
                     lambda i, t, j: (i, t * bh * stride, 0, 0)),
        pl.BlockSpec((kh, kw, ci, bn), lambda i, t, j: (0, 0, 0, j)),
    ]
    operands = [xp, wp]
    if fuse_bias:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, t, j: (0, j)))
        operands.append(pad_to(bias.reshape(1, co), (1, bn)))
    out = pl.pallas_call(
        functools.partial(_implicit_gemm_kernel, kh=kh, kw=kw, stride=stride,
                          wh=wh, fuse_bias=fuse_bias, act=act),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bh, wo, bn),
                               lambda i, t, j: (i, t, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, n_ht * bh, wo, cop), x.dtype),
        scratch_shapes=[pltpu.VMEM((bh * wo, bn), jnp.float32)],
        interpret=interpret,
    )(*operands)
    return out[:, :ho, :, :co]
