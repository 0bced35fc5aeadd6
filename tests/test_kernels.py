"""Per-kernel validation: shape/dtype sweeps + hypothesis property tests,
all against the pure-jnp oracles, in Pallas interpret mode (CPU)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.conv_gemm.kernel import matmul_bias_act
from repro.kernels.conv_gemm.ops import (conv2d_gemm, pointwise_conv,
                                         xla_routed)
from repro.kernels.conv_gemm.ref import conv2d_ref, matmul_bias_act_ref
from repro.kernels import util as kernel_util
from repro.kernels.depthwise import kernel as depthwise_kernel
from repro.kernels.depthwise.ops import depthwise
from repro.kernels.depthwise.ref import depthwise_conv2d_ref
from repro.kernels.attention.kernel import flash_attention
from repro.kernels.attention.ref import attention_ref
from repro.kernels.rmsnorm.kernel import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.models.zoo import get_graph


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=3e-4, atol=3e-4)


def rand(key, shape, dtype, scale=1.0):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


KEYS = jax.random.split(jax.random.PRNGKey(42), 8)


# --------------------------------------------------------------------------
# conv_gemm (c-core analogue)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n", [(8, 8, 8), (128, 128, 128),
                                   (100, 70, 30), (257, 129, 65),
                                   (1, 512, 1000)])
def test_matmul_shapes(m, k, n, dtype):
    x = rand(KEYS[0], (m, k), dtype, 0.3)
    w = rand(KEYS[1], (k, n), dtype, 0.3)
    b = rand(KEYS[2], (n,), dtype)
    out = matmul_bias_act(x, w, b, act="relu")
    ref = matmul_bias_act_ref(x, w, b, act="relu")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(st.integers(1, 64), st.integers(1, 64), st.integers(1, 64),
       st.sampled_from([None, "relu", "relu6"]))
def test_matmul_property(m, k, n, act):
    x = rand(KEYS[0], (m, k), jnp.float32, 0.3)
    w = rand(KEYS[1], (k, n), jnp.float32, 0.3)
    out = matmul_bias_act(x, w, None, act=act, block=(32, 32, 32))
    ref = matmul_bias_act_ref(x, w, None, act=act)
    np.testing.assert_allclose(out, ref, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("h,ci,co,k,s,pad", [
    (14, 32, 64, 3, 1, 1), (28, 16, 24, 3, 2, 1),
    (8, 8, 16, 1, 1, 0), (224 // 8, 3, 32, 3, 2, 1), (7, 128, 32, 3, 1, 1)])
def test_conv2d_gemm(h, ci, co, k, s, pad, dtype):
    x = rand(KEYS[0], (2, h, h, ci), dtype, 0.5)
    w = rand(KEYS[1], (k, k, ci, co), dtype, 0.2)
    b = rand(KEYS[2], (co,), dtype)
    out = conv2d_gemm(x, w, b, stride=s, pad=pad, act="relu6")
    ref = conv2d_ref(x, w, b, stride=s, pad=pad, act="relu6")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


def test_pointwise_matches_conv():
    x = rand(KEYS[0], (2, 7, 7, 64), jnp.float32, 0.5)
    w = rand(KEYS[1], (1, 1, 64, 32), jnp.float32, 0.2)
    np.testing.assert_allclose(pointwise_conv(x, w),
                               conv2d_ref(x, w, stride=1, pad=0),
                               rtol=3e-4, atol=3e-4)


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in eqn.params.values():
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield from _eqns(sub)


def _conv_route(x, w, b, stride, pad) -> str:
    """Which implementation ``conv2d_gemm`` traces for these shapes.
    ``interpret`` is explicit: a kernel traced with ``interpret=None``
    caches the interpret-mode body under that key for the process."""
    jaxpr = jax.make_jaxpr(lambda a, k, c: conv2d_gemm(
        a, k, c, stride=stride, pad=pad, act="relu", interpret=True))(
            x, w, b)
    eqns = list(_eqns(jaxpr.jaxpr))
    prims = {e.primitive.name for e in eqns}
    if "conv_general_dilated" in prims:
        assert "pallas_call" not in prims, prims
        return "xla"
    names = {e.params.get("name") for e in eqns}
    if "matmul_bias_act" in names:
        return "matmul_bias_act"
    raise AssertionError(f"no conv route in {sorted(prims)}")


@pytest.mark.parametrize("model", ["mobilenet_v1", "mobilenet_v2",
                                   "squeezenet"])
def test_conv_route_table(model):
    """Every zoo conv off the plain 1x1 path (the RGB stems, the fire
    expands) takes XLA's convolution, and every 1x1 conv the tiled
    GEMM."""
    routes = {}
    for l in get_graph(model).layers:
        if l.op != "conv":
            continue
        x = jax.ShapeDtypeStruct((1, l.H, l.W, l.C_i), jnp.float32)
        w = jax.ShapeDtypeStruct((l.K_h, l.K_w, l.C_i, l.C_o), jnp.float32)
        b = jax.ShapeDtypeStruct((l.C_o,), jnp.float32)
        routes[l.name] = _conv_route(x, w, b, l.stride, l.pad)
    want = {"conv1"} | {n for n in routes if n.endswith("_e3x3")}
    assert {n for n, r in routes.items() if r == "xla"} == want
    for name, route in routes.items():
        if name not in want:
            assert route == "matmul_bias_act", (name, route)


@pytest.mark.parametrize("kh,ci,stride,pad,routed", [
    (3, 3, 2, 1, True),            # the RGB stem
    (3, 64, 1, 1, True),           # squeezenet fire8/9 e3x3
    (3, 128, 1, 1, True),          # lane-full 3x3
    (1, 64, 2, 0, True),           # strided 1x1
    (1, 128, 1, 1, True),          # padded 1x1, lane-full
    (1, 3, 1, 0, False)])          # plain 1x1: the tiled GEMM
def test_xla_route_rule(kh, ci, stride, pad, routed):
    """``xla_routed`` decides the route, and ``conv2d_gemm`` follows it."""
    assert xla_routed(kh, kh, stride, pad) is routed
    x = jax.ShapeDtypeStruct((1, 16, 16, ci), jnp.float32)
    w = jax.ShapeDtypeStruct((kh, kh, ci, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((32,), jnp.float32)
    assert _conv_route(x, w, b, stride, pad) == (
        "xla" if routed else "matmul_bias_act")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("h,ci,co,s", [
    (28, 3, 32, 2),                # the stem's shape, at 28 px
    (14, 32, 64, 1),
    (8, 128, 64, 1)])              # lane-full
def test_xla_routed_conv_matches_ref(h, ci, co, s, dtype):
    """``conv2d_gemm`` on its XLA route at batch 2, f32 and bf16: the
    stem's shape, a fire-like one, lane-full."""
    assert xla_routed(3, 3, s, 1)
    x = rand(KEYS[0], (2, h, h, ci), dtype, 0.5)
    w = rand(KEYS[1], (3, 3, ci, co), dtype, 0.2)
    b = rand(KEYS[2], (co,), dtype)
    out = conv2d_gemm(x, w, b, stride=s, pad=1, act="relu6")
    ref = conv2d_ref(x, w, b, stride=s, pad=1, act="relu6")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("with_bias", [True, False])
def test_stem_route_is_one_xla_conv(dtype, with_bias):
    """The stem route calls no kernel and pads nothing to 128 lanes: one
    ``conv_general_dilated`` (XLA pads the window inside it), with
    conv2d_ref's answer."""
    x = rand(KEYS[0], (2, 32, 32, 3), dtype, 0.5)
    w = rand(KEYS[1], (3, 3, 3, 32), dtype, 0.2)
    b = rand(KEYS[2], (32,), dtype) if with_bias else None

    def stem(a, k, c):
        return conv2d_gemm(a, k, c, stride=2, pad=1, act="relu6")

    eqns = list(_eqns(jax.make_jaxpr(stem)(x, w, b).jaxpr))
    prims = [e.primitive.name for e in eqns]
    assert prims.count("conv_general_dilated") == 1, prims
    assert "pallas_call" not in prims and "pad" not in prims, prims
    assert not any(v.aval.shape[-1:] == (128,)
                   for e in eqns for v in e.outvars)
    out = stem(x, w, b)
    assert out.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(conv2d_ref(x, w, b, stride=2, pad=1, act="relu6"),
                   np.float32), **tol(dtype))


# --------------------------------------------------------------------------
# depthwise (p-core analogue)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("h,w,c,s,limit,block_c", [
    pytest.param(h, h, c, s, 0, None, id=f"{h}-{c}-{s}")
    for h, c, s in [(14, 512, 1), (28, 256, 2), (7, 1024, 1), (9, 24, 2),
                    (112, 32, 1)]] + [
    # the zero border built in VMEM: odd H and W, several row tiles (the
    # top one in the border, interior ones, a bottom one overhanging)
    pytest.param(13, 11, 24, 2, 2, None, id="odd-s2-tiles"),
    pytest.param(15, 15, 48, 1, 3, None, id="s1-tiles"),
    # C > 128: two lane tiles of one channel block, and two channel blocks
    pytest.param(11, 9, 200, 2, 2, None, id="c200-s2-tiles"),
    pytest.param(11, 9, 200, 2, 2, 128, id="c200-two-blocks-s2-tiles"),
])
def test_depthwise_shapes(h, w, c, s, limit, block_c, dtype, monkeypatch):
    """Against the reference on maps of non-zero mean with a non-zero bias
    and relu6, so a border not read as zeros shows."""
    x = rand(KEYS[0], (2, h, w, c), dtype, 0.5) + 0.5
    k = rand(KEYS[1], (3, 3, c), dtype, 0.3)
    b = rand(KEYS[2], (c,), dtype)
    if limit:                       # ``limit`` output rows per row tile
        monkeypatch.setattr(depthwise_kernel, "row_tiling", functools.partial(
            kernel_util.row_tiling, limit=limit))
        jax.clear_caches()
    try:
        out = depthwise(x, k, b, stride=s, pad=1, act="relu6",
                        block_c=block_c)
    finally:
        if limit:
            jax.clear_caches()
    ref = depthwise_conv2d_ref(x, k, b, stride=s, pad=1, act="relu6")
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


@settings(max_examples=15, deadline=None)
@given(st.integers(4, 32), st.sampled_from([8, 16, 56]),
       st.sampled_from([1, 2]), st.sampled_from([3, 5]))
def test_depthwise_property(h, c, s, k):
    x = rand(KEYS[0], (1, h, h, c), jnp.float32, 0.5)
    w = rand(KEYS[1], (k, k, c), jnp.float32, 0.3)
    pad = k // 2
    out = depthwise(x, w, None, stride=s, pad=pad)
    ref = depthwise_conv2d_ref(x, w, None, stride=s, pad=pad)
    np.testing.assert_allclose(out, ref, rtol=3e-4, atol=3e-4)


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hkv,sq,sk,d,causal", [
    (2, 8, 2, 64, 64, 32, True),      # GQA
    (1, 4, 4, 128, 128, 64, True),    # MHA
    (2, 6, 1, 1, 256, 64, False),     # MQA decode shape
    (1, 14, 2, 37, 37, 64, True),     # qwen2-0.5b heads (non-pow2)
    (1, 2, 2, 8, 200, 128, False),    # cross-attn shape (sq != sk)
])
def test_flash_attention(b, hq, hkv, sq, sk, d, causal, dtype):
    q = rand(KEYS[0], (b, hq, sq, d), dtype, 0.5)
    k = rand(KEYS[1], (b, hkv, sk, d), dtype, 0.5)
    v = rand(KEYS[2], (b, hkv, sk, d), dtype, 0.5)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               **(dict(rtol=3e-2, atol=3e-2)
                                  if dtype == jnp.bfloat16
                                  else dict(rtol=2e-4, atol=2e-4)))


@pytest.mark.slow
@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3), st.sampled_from([(4, 2), (8, 1), (6, 6)]),
       st.integers(1, 80), st.sampled_from([32, 64]))
def test_flash_attention_property(b, heads, sq, d):
    hq, hkv = heads
    q = rand(KEYS[0], (b, hq, sq, d), jnp.float32, 0.5)
    k = rand(KEYS[1], (b, hkv, sq, d), jnp.float32, 0.5)
    v = rand(KEYS[2], (b, hkv, sq, d), jnp.float32, 0.5)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, rtol=3e-4, atol=3e-4)


def test_flash_attention_softmax_rows_sum():
    """Property: attention output of constant-V equals that constant."""
    b, hq, hkv, s, d = 1, 4, 2, 64, 32
    q = rand(KEYS[0], (b, hq, s, d), jnp.float32)
    k = rand(KEYS[1], (b, hkv, s, d), jnp.float32)
    v = jnp.ones((b, hkv, s, d), jnp.float32) * 3.5
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    np.testing.assert_allclose(out, jnp.full_like(out, 3.5), rtol=1e-5)


# --------------------------------------------------------------------------
# rmsnorm
# --------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 256), (2, 16, 896), (1, 1, 12288),
                                   (3, 7, 1024)])
def test_rmsnorm(shape, dtype):
    x = rand(KEYS[0], shape, dtype, 2.0)
    w = rand(KEYS[1], shape[-1:], dtype)
    out = rmsnorm(x, w)
    ref = rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 100), st.sampled_from([64, 896, 1536]))
def test_rmsnorm_property(rows, d):
    x = rand(KEYS[0], (rows, d), jnp.float32, 2.0)
    w = jnp.ones((d,), jnp.float32)
    out = rmsnorm(x, w)
    # unit weight: per-row RMS of output ~= 1
    rms = np.sqrt(np.mean(np.asarray(out) ** 2, axis=-1))
    np.testing.assert_allclose(rms, np.ones_like(rms), rtol=1e-3)
