"""Operations and least bytes per kernel call, against hand counts."""
from chipbench import work
from chipbench.refops import Layer


def test_stem_conv():
    # mobilenet_v2 conv1: 3x3 stride 2, 3 -> 32, 224 -> 112
    """mobilenet_v2's stem conv."""
    l = Layer("conv1", "conv", 3, 2, 1, 3, 32, 224, 112)
    assert work.layer_flops(l) == 2 * 112 * 112 * 3 * 3 * 3 * 32
    c = work.call_work("conv1", [l])
    assert c.family == "conv_gemm"
    assert c.map_bytes == 4 * (224 * 224 * 3 + 112 * 112 * 32)
    assert c.weight_bytes == 4 * (3 * 3 * 3 * 32 + 32)


def test_depthwise():
    # mobilenet_v2 b2_dw: 3x3 stride 2 on 96 channels, 112 -> 56
    """A stride-2 depthwise layer."""
    l = Layer("b2_dw", "dwconv", 3, 2, 1, 96, 96, 112, 56)
    assert work.layer_flops(l) == 2 * 56 * 56 * 96 * 9
    c = work.call_work("b2_dw", [l])
    assert c.family == "depthwise"
    assert c.map_bytes == 4 * (112 * 112 * 96 + 56 * 56 * 96)
    assert c.weight_bytes == 4 * (3 * 3 * 96 + 96)


def test_pointwise_and_fused_block():
    # a MobileNetV1-style dw+pw block: dw 3x3 s2, 64 channels, 112 -> 56,
    # then pw 64 -> 128; the 56x56x64 intermediate never reaches HBM
    """A pointwise layer alone and fused behind its depthwise."""
    dw = Layer("dw2", "dwconv", 3, 2, 1, 64, 64, 112, 56)
    pw = Layer("pw2", "conv", 1, 1, 0, 64, 128, 56, 56)
    assert work.layer_flops(pw) == 2 * 56 * 56 * 64 * 128
    alone = work.call_work("pw2", [pw])
    assert alone.family == "conv_gemm"
    assert alone.map_bytes == 4 * 56 * 56 * (64 + 128)
    fused = work.call_work("dw2+pw2", [dw, pw])
    assert fused.family == "fused_block"
    assert fused.flops == 2 * 56 * 56 * 64 * (9 + 128)
    assert fused.map_bytes == 4 * (112 * 112 * 64 + 56 * 56 * 128)
    assert fused.weight_bytes == 4 * (9 * 64 + 64 + 64 * 128 + 128)


def test_least_time_takes_the_larger_bound():
    """The least time is the larger of the compute and memory bounds."""
    pw = work.call_work("pw", [Layer("pw", "conv", 1, 1, 0, 64, 128, 56,
                                     56)])
    t, which = pw.least_s(8, peak_flops=1e12, hbm_bw=1e15)
    assert which == "compute" and t == 8 * pw.flops / 1e12
    t, which = pw.least_s(8, peak_flops=1e18, hbm_bw=1e9)
    assert which == "memory"
    assert t == (8 * pw.map_bytes + pw.weight_bytes) / 1e9


def test_fc_head():
    """The classifier head."""
    l = Layer("fc", "fc", 1, 1, 0, 1280, 1000, 1, 1)
    assert work.layer_flops(l) == 2 * 1280 * 1000
    assert work.call_work("fc", [l]).map_bytes == 4 * (1280 + 1000)
