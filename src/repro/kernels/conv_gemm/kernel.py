"""c-core analogue: the tiled GEMM Pallas kernel.

The dual-OPU c-core broadcasts one ifm pixel across the PE array and exploits
input/output channel parallelism — on TPU that is a GEMM.  ``matmul_bias_act``
is the tiled GEMM of the 1x1 (pointwise / fc) path, where im2col is the
identity: grid (M tiles, N tiles, K tiles), a float32 VMEM scratch
accumulator (the overlay's output-buffer partial sums, §III-A) and a fused
bias + ReLU/ReLU6 epilogue (the overlay's post-processing unit).  Every other
conv runs on XLA's convolution (``ops.py``, DESIGN.md §1).  Block shapes
default to (128, 128, 128): MXU-native, 3 * 128*128*4B = 192 KiB of VMEM per
step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.util import (apply_act, lane_tile, mxu_dot, pad_to,
                                resolve_interpret)


DEFAULT_BLOCK = (128, 128, 128)  # (block_m, block_n, block_k)


def _apply_epilogue(out, b_ref, act: str | None):
    if b_ref is not None:
        out = out + b_ref[...].astype(jnp.float32)
    return apply_act(out, act)


# --------------------------------------------------------------------------
# tiled GEMM (the 1x1 / fc path)
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
