"""MobileNetV2, the plain reference (Sandler et al., arXiv:1801.04381).

Table 2 of the paper, read from the configuration's ``blocks`` as
``(t, c, n, s)`` rows: a 3x3 stride-2 stem, inverted residual blocks
(1x1 expand by ``t`` with ReLU6, 3x3 depthwise with ReLU6, linear 1x1
project, an identity shortcut where stride is 1 and the channels match),
a 1x1 conv to ``last_channels`` with ReLU6, global average pooling and a
linear classifier.  Departures from the paper: no batch normalisation (the
served model has none; with random weights it would fold into the
weights), and no dropout (inference).
"""
from __future__ import annotations

from chipbench.refops import (Layer, conv, conv_out, dense, global_avgpool,
                              relu6)


def layers(arch: dict) -> list[Layer]:
    """The weighted layers in order, with their map sizes."""
    h = arch["image_size"]
    stem = arch["stem"]
    k, s = stem["kernel"], stem["stride"]
    ho = conv_out(h, k, s, k // 2)
    out = [Layer("conv1", "conv", k, s, k // 2, 3, stem["channels"], h, ho)]
    h, c, bi = ho, stem["channels"], 0
    for t, c_out, n, s in arch["blocks"]:
        for r in range(n):
            bi += 1
            stride = s if r == 0 else 1
            mid = c * t
            if t != 1:
                out.append(Layer(f"b{bi}_expand", "conv", 1, 1, 0, c, mid,
                                 h, h))
            ho = conv_out(h, 3, stride, 1)
            out.append(Layer(f"b{bi}_dw", "dwconv", 3, stride, 1, mid, mid,
                             h, ho))
            out.append(Layer(f"b{bi}_project", "conv", 1, 1, 0, mid, c_out,
                             ho, ho))
            h, c = ho, c_out
    out.append(Layer("conv_last", "conv", 1, 1, 0, c, arch["last_channels"],
                     h, h))
    out.append(Layer("fc", "fc", 1, 1, 0, arch["last_channels"],
                     arch["classes"], 1, 1))
    return out


def forward(params: dict, x, arch: dict, precision: str):
    """Logits ``(N, classes)`` of NHWC images ``x``."""
    ls = {l.name: l for l in layers(arch)}

    def run(name, h, act=None):
        l, p = ls[name], params[name]
        y = conv(h, p["w"], p["b"], stride=l.stride, pad=l.pad,
                 precision=precision, depthwise=l.op == "dwconv")
        return relu6(y) if act else y

    h = run("conv1", x, act=True)
    bi = 0
    for t, _, n, _ in arch["blocks"]:
        for _ in range(n):
            bi += 1
            block_in = h
            if t != 1:
                h = run(f"b{bi}_expand", h, act=True)
            h = run(f"b{bi}_dw", h, act=True)
            h = run(f"b{bi}_project", h)
            if h.shape == block_in.shape:
                h = h + block_in
    h = run("conv_last", h, act=True)
    fc = params["fc"]
    w = fc["w"].reshape(fc["w"].shape[-2], fc["w"].shape[-1])
    return dense(global_avgpool(h), w, fc["b"], precision=precision)
