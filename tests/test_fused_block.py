"""Fused dw->pw block kernels, implicit-GEMM conv across the model zoo,
the graph fusion pass, and the block-shape autotuner cache."""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fusion import fused_layer_counts, plan_fusion
from repro.core.graph import LayerSpec
from repro.kernels import autotune
from repro.kernels import util as kernel_util
from repro.kernels.fused_block import kernel as fused_kernel
from repro.kernels.conv_gemm.ops import conv2d_gemm, implicit_gemm_conv
from repro.kernels.conv_gemm.ref import conv2d_ref
from repro.kernels.fused_block.kernel import (fused_dw_pw_conv,
                                              fused_pw_dw_pw_conv)
from repro.kernels.fused_block.ops import fused_dw_pw
from repro.kernels.fused_block.ref import (fused_dw_pw_ref,
                                           fused_pw_dw_pw_ref)
from repro.models.zoo import get_graph

KEYS = jax.random.split(jax.random.PRNGKey(11), 8)


def rand(key, shape, scale=1.0, dtype=jnp.float32):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# --------------------------------------------------------------------------
# fused dw->pw vs the composed reference ops
# --------------------------------------------------------------------------
def _case(h, w, c, co, s, bc, act, limit=0, id=None):
    return pytest.param(h, w, c, co, s, bc, act, limit,
                        id=id or f"{h}-{w}-{c}-{co}-{s}-{bc}-{act}")


@pytest.mark.parametrize("h,w,c,co,s,bc,act,limit", [
    _case(14, 14, 64, 128, 1, 32, "relu6"),
    _case(15, 13, 48, 56, 1, 32, "relu6"),     # odd H/W
    _case(28, 28, 100, 64, 2, 48, "relu6"),    # stride 2, C % block_c != 0
    _case(9, 9, 24, 40, 2, 64, "relu"),        # odd + stride 2 + bc > C
    _case(7, 7, 96, 32, 1, 8, None),
    # the zero border built in VMEM: odd H and W at stride 2 over several
    # row tiles (the top one in the border, a bottom one overhanging)
    _case(13, 11, 48, 56, 2, 48, "relu6", 2, id="odd-s2-tiles"),
    # C > 128: two lane tiles of one channel block, then two channel
    # blocks; C_out over two tiles, the last one overhanging
    _case(15, 13, 200, 160, 1, 256, "relu6", 3, id="c200-s1-tiles"),
    _case(11, 9, 200, 160, 2, 128, None, 2, id="c200-two-blocks-s2-tiles"),
])
def test_fused_dw_pw_matches_composed(h, w, c, co, s, bc, act, limit,
                                      monkeypatch):
    """Against the composed reference on maps of non-zero mean with a
    non-zero dw bias and relu6, so a border not read as zeros shows."""
    x = rand(KEYS[0], (2, h, w, c), 0.5) + 0.5
    dw_w = rand(KEYS[1], (3, 3, c), 0.3)
    dw_b = rand(KEYS[2], (c,), 0.1) + 0.5
    pw_w = rand(KEYS[3], (c, co), 0.2)
    pw_b = rand(KEYS[4], (co,), 0.1)
    if limit:                       # ``limit`` output rows per row tile
        monkeypatch.setattr(fused_kernel, "row_tiling", functools.partial(
            kernel_util.row_tiling, limit=limit))
        jax.clear_caches()
    try:
        out = fused_dw_pw_conv(x, dw_w, dw_b, pw_w, pw_b, stride=s, pad=1,
                               dw_act="relu6", pw_act=act, block_c=bc,
                               block_n=64)
    finally:
        if limit:
            jax.clear_caches()
    ref = fused_dw_pw_ref(x, dw_w, dw_b, pw_w, pw_b, stride=s, pad=1,
                          dw_act="relu6", pw_act=act)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_fused_dw_pw_no_bias():
    x = rand(KEYS[0], (1, 10, 10, 16), 0.5)
    dw_w = rand(KEYS[1], (3, 3, 16), 0.3)
    pw_w = rand(KEYS[2], (16, 24), 0.2)
    out = fused_dw_pw_conv(x, dw_w, None, pw_w, None, stride=1, pad=1)
    ref = fused_dw_pw_ref(x, dw_w, None, pw_w, None, stride=1, pad=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("h,ci,cm,co,s,res", [
    (14, 32, 96, 32, 1, True),            # residual add fused
    (14, 32, 96, 48, 1, False),
    (15, 24, 144, 32, 2, False),          # odd H + stride 2
])
def test_fused_inverted_residual_matches_composed(h, ci, cm, co, s, res):
    x = rand(KEYS[0], (1, h, h, ci), 0.5)
    ew = rand(KEYS[1], (ci, cm), 0.2)
    eb = rand(KEYS[2], (cm,), 0.1)
    dw_w = rand(KEYS[3], (3, 3, cm), 0.3)
    db = rand(KEYS[4], (cm,), 0.1)
    pw = rand(KEYS[5], (cm, co), 0.2)
    pb = rand(KEYS[6], (co,), 0.1)
    residual = x if res else None
    out = fused_pw_dw_pw_conv(x, ew, eb, dw_w, db, pw, pb, residual,
                              stride=s, pad=1, block_c=32, block_n=32)
    ref = fused_pw_dw_pw_ref(x, ew, eb, dw_w, db, pw, pb, residual,
                             stride=s, pad=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_fused_ops_accept_4d_pointwise_weights():
    """models/cnn stores 1x1 weights as (1,1,Ci,Co); the ops reshape."""
    x = rand(KEYS[0], (1, 8, 8, 16), 0.5)
    dw_w = rand(KEYS[1], (3, 3, 16), 0.3)
    pw_w4 = rand(KEYS[2], (1, 1, 16, 24), 0.2)
    out = fused_dw_pw(x, dw_w, None, pw_w4, None, stride=1, pad=1)
    ref = fused_dw_pw_ref(x, dw_w, None, pw_w4.reshape(16, 24), None,
                          stride=1, pad=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# implicit-GEMM conv across every conv layer shape in the model zoo
# --------------------------------------------------------------------------
def _zoo_conv_sigs():
    seen, sigs = set(), []
    for name in ("mobilenet_v1", "mobilenet_v2", "squeezenet"):
        for l in get_graph(name).layers:
            if l.op not in ("conv", "fc"):
                continue
            sig = (l.H, l.W, l.C_i, l.C_o, l.K_h, l.K_w, l.stride, l.pad)
            if sig not in seen:
                seen.add(sig)
                sigs.append(sig)
    return sigs


@pytest.mark.parametrize("h,w,ci,co,kh,kw,s,p", _zoo_conv_sigs())
def test_implicit_gemm_zoo_layer(h, w, ci, co, kh, kw, s, p):
    """Acceptance: ``conv2d_gemm`` matches conv2d_ref to 1e-4 on every
    conv layer in the model zoo, on the route it takes there: the tiled
    GEMM for 1x1 convs, XLA's convolution for every other conv
    (``xla_routed``).  The implicit-GEMM kernel's own cases are
    ``test_implicit_gemm_kernel_matches_ref``."""
    x = rand(KEYS[0], (1, h, w, ci), 0.5)
    wgt = rand(KEYS[1], (kh, kw, ci, co), 0.2)
    b = rand(KEYS[2], (co,), 0.1)
    out = conv2d_gemm(x, wgt, b, stride=s, pad=p, act="relu6")
    ref = conv2d_ref(x, wgt, b, stride=s, pad=p, act="relu6")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def _assert_no_patch_matrix(conv):
    n, h, ci, co, k, s, p = 1, 28, 32, 64, 3, 1, 1
    ho = (h + 2 * p - k) // s + 1
    forbidden = {(n * ho * ho, k * k * ci)}

    x = jnp.zeros((n, h, h, ci))
    w = jnp.zeros((k, k, ci, co))
    jaxpr = jax.make_jaxpr(
        lambda a, b: conv(a, b, stride=s, pad=p))(x, w)

    def walk(jx):
        for eqn in jx.eqns:
            for v in eqn.outvars:
                shape = tuple(getattr(v.aval, "shape", ()))
                assert shape not in forbidden, (
                    f"HBM patch matrix {shape} materialized by "
                    f"{eqn.primitive}")
            for sub in eqn.params.values():
                if hasattr(sub, "eqns"):
                    walk(sub)
                elif hasattr(sub, "jaxpr") and hasattr(sub.jaxpr, "eqns"):
                    walk(sub.jaxpr)

    walk(jaxpr.jaxpr)


def test_implicit_gemm_never_materializes_patch_matrix():
    """Acceptance: no (N*Ho*Wo, Kh*Kw*C) intermediate anywhere in the
    jaxpr of the conv path."""
    _assert_no_patch_matrix(conv2d_gemm)


def test_implicit_gemm_kernel_never_materializes_patch_matrix():
    """Nor in the implicit-GEMM kernel's, called directly (``conv2d_gemm``
    runs XLA's convolution for a 3x3 conv)."""
    _assert_no_patch_matrix(implicit_gemm_conv)


# --------------------------------------------------------------------------
# graph fusion pass
# --------------------------------------------------------------------------
def test_fusion_plan_zoo_counts():
    assert fused_layer_counts(get_graph("mobilenet_v1")) == {
        "single": 2, "dw_pw": 13}
    assert fused_layer_counts(get_graph("mobilenet_v2")) == {
        "single": 3, "dw_pw": 1, "pw_dw_pw": 16}
    # no dwconv anywhere -> nothing fuses
    assert fused_layer_counts(get_graph("squeezenet")) == {"single": 26}


def test_fusion_plan_covers_each_layer_once():
    for name in ("mobilenet_v1", "mobilenet_v2", "squeezenet"):
        g = get_graph(name)
        names = [n for grp in plan_fusion(g) for n in grp.layers]
        assert sorted(names) == sorted(l.name for l in g.layers)


def test_fusion_requires_linear_chain():
    """A dw whose output has two consumers must not fuse."""
    layers = [
        LayerSpec("dw", "dwconv", 8, 8, 16, 16, 3, 3, 1, pad=1),
        LayerSpec("pw_a", "conv", 8, 8, 16, 32, 1, 1, 1),
        LayerSpec("pw_b", "conv", 8, 8, 16, 32, 1, 1, 1),
    ]
    from repro.core.graph import LayerGraph
    g = LayerGraph("fanout", layers,
                   edges=[("dw", "pw_a"), ("dw", "pw_b")])
    assert all(grp.kind == "single" for grp in plan_fusion(g))


def test_fused_model_forward_matches_xla():
    """End-to-end: the fused Pallas plan reproduces the XLA forward."""
    from repro.models.cnn import build_model
    params, fwd, g = build_model("mobilenet_v2")
    x = rand(KEYS[0], (1, 224, 224, 3), 0.5)
    a = fwd(params, x)
    b = fwd(params, x, use_pallas=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-3, atol=2e-3)


# --------------------------------------------------------------------------
# autotuner cache
# --------------------------------------------------------------------------
def test_autotune_cache_roundtrip_deterministic(tmp_path):
    path = str(tmp_path / "autotune.json")
    sig = autotune.LayerSig("conv", 8, 8, 8, 8, 3, 3, 1, 1)
    cfg = autotune.tune_layer(sig, path=path, reps=1)
    assert set(cfg) == {"block_h", "block_n"}
    # the JSON file round-trips to the same config
    with open(path) as f:
        raw = json.load(f)
    assert raw["version"] == autotune.CACHE_VERSION
    assert raw["entries"][sig.key()]["config"] == cfg
    autotune.clear_memory_cache()
    assert autotune.get_config(sig, path=path) == cfg
    # a second tune short-circuits on the cache: no benchmarking happens
    def boom(_cfg):
        raise AssertionError("re-benchmarked despite cache hit")
    assert autotune.tune(sig, boom, path=path) == cfg


def test_autotune_miss_falls_back_to_heuristic(tmp_path):
    path = str(tmp_path / "empty.json")
    sig = autotune.LayerSig("depthwise", 14, 14, 64, 64, 3, 3, 1, 1)
    assert autotune.get_config(sig, path=path) is None
    cfg = autotune.heuristic_config(sig)
    assert cfg["block_c"] >= 8


def test_autotune_key_distinguishes_shapes():
    a = autotune.LayerSig("conv", 14, 14, 32, 64, 3, 3, 1, 1)
    b = autotune.LayerSig("conv", 14, 14, 32, 64, 3, 3, 2, 1)
    assert a.key() != b.key()
