"""Dispatch wrapper for the depthwise kernel (channel block autotuned per
layer signature when a cache entry exists, else the heuristic)."""
from __future__ import annotations

import jax

from repro.kernels import autotune
from repro.kernels.depthwise.kernel import depthwise_conv2d


def depthwise(x: jax.Array, w: jax.Array, bias: jax.Array | None = None,
              *, stride: int = 1, pad: int = 1, act: str | None = None,
              block_c: int | None = None,
              interpret: bool | None = None) -> jax.Array:
    n, h, wd, c = x.shape
    kh, kw, _ = w.shape
    if block_c is None:
        sig = autotune.LayerSig(kind="depthwise", H=h, W=wd, C_i=c, C_o=c,
                                K_h=kh, K_w=kw, stride=stride, pad=pad,
                                dtype=str(x.dtype))
        cfg = autotune.get_config(sig) or autotune.heuristic_config(sig)
        block_c = cfg["block_c"]
    return depthwise_conv2d(x, w, bias, stride=stride, pad=pad, act=act,
                            block_c=min(block_c, c), interpret=interpret)
