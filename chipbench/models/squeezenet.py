"""SqueezeNet v1.1, the plain reference (Iandola et al., arXiv:1602.07360;
v1.1 as published in github.com/forresti/SqueezeNet, ``SqueezeNet_v1.1``).

A 3x3 stride-2 stem conv to ``stem.channels``, eight fire modules read
from the configuration's ``fires`` as ``(squeeze, expand)`` rows (a 1x1
squeeze, then a 1x1 and a 3x3 expand side by side, concatenated 1x1
first), a 3x3 stride-2 max pool after each layer named in ``pool_after``,
a 1x1 conv to ``classes`` and global average pooling.  ReLU follows every
conv, conv10 included.  Every channel width, kernel size and stride is
v1.1's.

Departures from the published v1.1:

* the stem conv pads by ``stem.pad`` (1) where v1.1 pads none, and each
  max pool pads its bottom and right edge by one (with -inf) where v1.1's
  Caffe pooling rounds its output size up.  So the maps are 112, 56, 28
  and 14 pixels wide at 224 px, where v1.1's are 111, 55, 27 and 13: the
  program keeps the graph of ``repro.models.zoo``, on which its
  reproduction of the paper's Table IV cycle count is built (DESIGN.md
  §7), and this reference follows it;
* no dropout (inference), and no batch normalisation (v1.1 has none).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from chipbench.refops import Layer, conv, conv_out, global_avgpool

POOL_K, POOL_S = 3, 2        # v1.1's max pools: 3x3, stride 2


def pool_out(h: int) -> int:
    """Output rows of a max pool padded by one at the bottom and right."""
    return (h + 1 - POOL_K) // POOL_S + 1


def maxpool(x):
    """3x3 stride-2 max pool over NHWC ``x``, padded bottom and right by
    one row and column of -inf."""
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, POOL_K, POOL_K, 1),
                             (1, POOL_S, POOL_S, 1),
                             ((0, 0), (0, 1), (0, 1), (0, 0)))


def layers(arch: dict) -> list[Layer]:
    """The weighted layers in order, with their map sizes (each conv's
    output before any pool behind it)."""
    h = arch["image_size"]
    stem = arch["stem"]
    k, s, pad = stem["kernel"], stem["stride"], stem["pad"]
    ho = conv_out(h, k, s, pad)
    out = [Layer("conv1", "conv", k, s, pad, 3, stem["channels"], h, ho)]
    h, c = ho, stem["channels"]
    pools = set(arch["pool_after"])
    if "conv1" in pools:
        h = pool_out(h)
    for i, (sq, ex) in enumerate(arch["fires"], start=2):
        f = f"fire{i}"
        out += [Layer(f"{f}_squeeze", "conv", 1, 1, 0, c, sq, h, h),
                Layer(f"{f}_e1x1", "conv", 1, 1, 0, sq, ex, h, h),
                Layer(f"{f}_e3x3", "conv", 3, 1, 1, sq, ex, h, h)]
        c = 2 * ex
        if f in pools:
            h = pool_out(h)
    out.append(Layer("conv10", "conv", 1, 1, 0, c, arch["classes"], h, h))
    return out


def forward(params: dict, x, arch: dict, precision: str):
    """Logits ``(N, classes)`` of NHWC images ``x``."""
    ls = {l.name: l for l in layers(arch)}
    pools = set(arch["pool_after"])

    def run(name, h):
        l, p = ls[name], params[name]
        y = conv(h, p["w"], p["b"], stride=l.stride, pad=l.pad,
                 precision=precision)
        return jnp.maximum(y, 0.0)

    h = run("conv1", x)
    if "conv1" in pools:
        h = maxpool(h)
    for i in range(2, 2 + len(arch["fires"])):
        f = f"fire{i}"
        sq = run(f"{f}_squeeze", h)
        h = jnp.concatenate([run(f"{f}_e1x1", sq), run(f"{f}_e3x3", sq)],
                            axis=-1)
        if f in pools:
            h = maxpool(h)
    return global_avgpool(run("conv10", h))
