"""MobileNetV1, the plain reference (Howard et al., arXiv:1704.04861).

Table 1 of the paper, read from the configuration's ``blocks`` as
``(stride, c_out)`` rows: a 3x3 stride-2 stem, thirteen depthwise-separable
blocks (3x3 depthwise with its stride, then a 1x1 pointwise conv to
``c_out``, each followed by its activation), global average pooling and a
linear classifier.  Departures from the paper: no batch normalisation (the
served model has none; with random weights it would fold into the
weights); ReLU6 after every conv, as TF-Slim's MobileNetV1 has it, where
the paper says ReLU; Table 1's last depthwise layer at stride 1 (its
7x7 input and output say so, not its "s2"); no dropout (inference).
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
    h, c = ho, stem["channels"]
    for i, (stride, c_out) in enumerate(arch["blocks"], start=1):
        ho = conv_out(h, 3, stride, 1)
        out.append(Layer(f"dw{i}", "dwconv", 3, stride, 1, c, c, h, ho))
        out.append(Layer(f"pw{i}", "conv", 1, 1, 0, c, c_out, ho, ho))
        h, c = ho, c_out
    out.append(Layer("fc", "fc", 1, 1, 0, c, arch["classes"], 1, 1))
    return out


def forward(params: dict, x, arch: dict, precision: str):
    """Logits ``(N, classes)`` of NHWC images ``x``."""
    h = x
    for l in layers(arch)[:-1]:
        p = params[l.name]
        h = relu6(conv(h, p["w"], p["b"], stride=l.stride, pad=l.pad,
                       precision=precision, depthwise=l.op == "dwconv"))
    fc = params["fc"]
    w = fc["w"].reshape(fc["w"].shape[-2], fc["w"].shape[-1])
    return dense(global_avgpool(h), w, fc["b"], precision=precision)
