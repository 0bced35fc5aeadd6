"""Dispatch wrapper: 2D convolution (c-core analogue).

No im2col materialization anywhere on this path: 1x1 convs flatten pixels
(im2col is the identity) and run the tiled GEMM, with block shapes from
the autotune cache when a tuned entry exists for the layer signature,
else the default.

Every other conv (the RGB stems, squeezenet's 3x3 fire expands) runs on
XLA's convolution, not on the implicit-GEMM kernel (``kernel.py``,
DESIGN.md §1).  The kernel's per-tap dot contracts over ``C_i`` alone and
its operands carry ``C_i`` padded to 128 lanes through every halo DMA and
the wrapper's pad and phase-split copies.  On one TPU v5e (device time
from a profiler trace, ``benchmarks/conv_route_bench.py``) XLA's
convolution was faster on every shape measured: the 3-channel stem 1.75x
at batch 1 and 6.3x at batch 32, the four distinct fire expands (16-64
channels) 1.18-1.28x and 1.98-2.60x, two lane-full 3x3 convs (128 and
256 channels) 1.06-1.12x and 1.38-1.48x, two strided 1x1 convs (64 and
128 channels) 1.08-1.09x and 5.4-6.6x.  :func:`implicit_gemm_conv` keeps
the kernel callable with its tuned or heuristic blocks, for that
benchmark and the tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels.conv_gemm.kernel import (DEFAULT_BLOCK,
                                            conv2d_implicit_gemm,
                                            matmul_bias_act)
from repro.kernels.util import apply_act


def _sig(kind: str, x: jax.Array, kh: int, kw: int, ci: int, co: int,
         stride: int, pad: int) -> autotune.LayerSig:
    return autotune.LayerSig(kind=kind, H=x.shape[1], W=x.shape[2],
                             C_i=ci, C_o=co, K_h=kh, K_w=kw, stride=stride,
                             pad=pad, dtype=str(x.dtype))


def conv2d_gemm(x: jax.Array, w: jax.Array, bias: jax.Array | None = None,
                *, stride: int = 1, pad: int = 0, act: str | None = None,
                interpret: bool | None = None) -> jax.Array:
    """NHWC conv with fused bias/activation epilogue.

    x: (N, H, W, C_i); w: (K_h, K_w, C_i, C_o); bias: (C_o,) or None.
    A plain 1x1 conv runs the tiled GEMM; every other conv
    (:func:`xla_routed`) runs XLA's convolution, which beat the
    implicit-GEMM kernel on every shape measured (module docstring).
    """
    kh, kw, ci, co = w.shape
    if xla_routed(kh, kw, stride, pad):
        return xla_conv(x, w, bias, stride=stride, pad=pad, act=act)
    return pointwise_conv(x, w.reshape(ci, co), bias, act=act,
                          interpret=interpret)


def xla_routed(kh: int, kw: int, stride: int, pad: int) -> bool:
    """Whether :func:`conv2d_gemm` runs this conv on XLA's convolution:
    every conv but a plain 1x1 (stride 1, no padding)."""
    return not (kh == 1 and kw == 1 and stride == 1 and pad == 0)


def implicit_gemm_conv(x: jax.Array, w: jax.Array,
                       bias: jax.Array | None = None, *, stride: int = 1,
                       pad: int = 0, act: str | None = None, block=None,
                       interpret: bool | None = None) -> jax.Array:
    """The implicit-GEMM kernel with the layer's autotuned blocks (the
    per-kind heuristic where none is cached) unless ``block`` is given.
    No route of :func:`conv2d_gemm` runs it."""
    kh, kw, ci, co = w.shape
    if block is not None:
        bh, bn = block
    else:
        sig = _sig("conv", x, kh, kw, ci, co, stride, pad)
        cfg = autotune.get_config(sig) or autotune.heuristic_config(sig)
        bh, bn = cfg["block_h"], cfg["block_n"]
    return conv2d_implicit_gemm(x, w, bias, stride=stride, pad=pad, act=act,
                                block_h=bh, block_n=bn, interpret=interpret)


def xla_conv(x: jax.Array, w: jax.Array, bias: jax.Array | None = None, *,
             stride: int = 1, pad: int = 0,
             act: str | None = None) -> jax.Array:
    """XLA's convolution with the kernels' precision rule (``mxu_dot``):
    f32 operands at HIGHEST, accumulated in f32, then the bias and
    activation in f32, in the caller's jitted program (XLA fuses them)."""
    precision = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                 else None)
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding=((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision,
        preferred_element_type=jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return apply_act(out, act).astype(x.dtype)


def pointwise_conv(x: jax.Array, w: jax.Array,
                   bias: jax.Array | None = None, *, act: str | None = None,
                   block=None, interpret: bool | None = None) -> jax.Array:
    """1x1 conv fast path: pure GEMM over flattened pixels.

    Accepts w as (C_i, C_o) or (1, 1, C_i, C_o).
    """
    n, h, wd, ci = x.shape
    if w.ndim == 4:
        w = w.reshape(w.shape[2], w.shape[3])
    co = w.shape[-1]
    if block is None:
        sig = _sig("pointwise", x, 1, 1, ci, co, 1, 0)
        cfg = autotune.get_config(sig)
        block = tuple(cfg["block"]) if cfg else DEFAULT_BLOCK
    out = matmul_bias_act(x.reshape(n * h * wd, ci), w, bias, block=block,
                          act=act, interpret=interpret)
    return out.reshape(n, h, wd, co)
