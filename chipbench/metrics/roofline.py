"""Kernels: one kernel family's share of its roofline in the traced
window, for every metric named ``<family>_roofline``.

Least time (the larger of FLOPs over the bf16 peak and least HBM bytes
over the HBM bandwidth, per call, from ``chipbench/work.py``) over the
summed device time of the family's events, matched by the names of the
jitted functions that call its Pallas kernels.  A family missing here gets
a file of its own, ``metrics/<family>_roofline.py``."""

from chipbench.work import roofline_share

KERNELS = {
    "conv_gemm": ("matmul_bias_act", "conv2d_implicit_gemm"),
    "depthwise": ("depthwise_conv2d",),
    "fused_block": ("fused_dw_pw_conv", "fused_pw_dw_pw_conv"),
}


def read(run, name):
    """The metric's value in ``run``, or None where it has nothing to read."""
    family = name.removesuffix("_roofline")
    return roofline_share(run, family, KERNELS[family])
