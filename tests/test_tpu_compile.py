"""The CNN kernels compile for a TPU v5e at their 224-px zoo shapes.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: dynamic slices of loaded values, strided slices, strided reads
wider than one lane tile, blocks that are not lane-aligned, tiles that
overflow VMEM.  Each test here compiles one kernel for a *described* v5e
chip — no chip attached — and checks the program holds the Pallas kernel
(``tpu_custom_call``), or, for a conv that ``conv2d_gemm`` routes to XLA
(``xla_routed``: every conv but a plain 1x1), a ``convolution`` and no
kernel.  Nothing runs, so nothing here is a time.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and under pytest-xdist
every worker imports this file.  Keep these tests in this one file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.conv_gemm.ops import conv2d_gemm, xla_routed
from repro.kernels.depthwise.ops import depthwise
from repro.kernels.fused_block.ops import fused_dw_pw, fused_inverted_residual


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache off here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _hlo(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _compile(fn, sharding, *shapes):
    assert "tpu_custom_call" in _hlo(fn, sharding, *shapes)


# an XLA convolution in HLO text: ``%name = f32[...] convolution(...)``
XLA_CONV = " convolution("


def _compiles_to_xla_conv(sharding, xs, ws, stride):
    text = _hlo(lambda x, w, b: conv2d_gemm(x, w, b, stride=stride, pad=1,
                                            act="relu6", interpret=False),
                sharding, xs, ws, ws[-1:])
    assert XLA_CONV in text and "tpu_custom_call" not in text


def test_stem_conv_compiles(one_chip):
    """The 3x3 s2 stem of all three zoo models compiles to XLA's
    convolution (``xla_routed``), with no Pallas kernel."""
    _compiles_to_xla_conv(one_chip, (1, 224, 224, 3), (3, 3, 3, 32), 2)


@pytest.mark.parametrize("xs,ws", [
    ((1, 56, 56, 16), (3, 3, 16, 64)),          # squeezenet fire2_e3x3
    ((1, 14, 14, 128), (3, 3, 128, 128))])      # lane-full, no zoo layer
def test_xla_routed_conv_compiles(one_chip, xs, ws):
    """Every other conv off the plain 1x1 path does too."""
    _compiles_to_xla_conv(one_chip, xs, ws, 1)


@pytest.mark.parametrize("shape,stride", [
    ((1, 112, 112, 64), 2),        # mobilenet_v1 dw2: stride 2
    ((1, 28, 28, 256), 2),         # mobilenet_v1 dw6: C > 128, stride 2
    ((1, 56, 56, 144), 1),         # mobilenet_v2 b3_dw: C > 128, C % 128
])
def test_depthwise_compiles(one_chip, shape, stride):
    c = shape[-1]
    _compile(lambda x, w, b: depthwise(x, w, b, stride=stride, pad=1,
                                       act="relu6", interpret=False),
             one_chip, shape, (3, 3, c), (c,))


# an instruction of the entry computation: name, shape, opcode
_ENTRY_OP = re.compile(
    r"^\s*(?:ROOT )?%([\w.-]+) = \w+\[([\d,]*)\]\{[^}]*\} ([\w-]+)\(", re.M)
_GLUE = ("copy", "pad", "slice", "transpose")


@pytest.mark.parametrize("kind,shape,stride,co", [
    ("fused_dw_pw", (32, 112, 112, 32), 1, 16),     # mobilenet_v2 b1
    ("depthwise", (32, 112, 112, 96), 2, None),     # b2_dw
    ("depthwise", (32, 56, 56, 144), 1, None),      # b3_dw
    ("fused_dw_pw", (32, 56, 56, 144), 2, 32),      # b4 dw + project
    ("fused_dw_pw", (32, 14, 14, 576), 2, 160),     # b14 dw + project
    ("depthwise", (32, 112, 112, 64), 2, None),     # mobilenet_v1 dw2
])
def test_depthwise_halo_built_in_vmem(one_chip, kind, shape, stride, co):
    """At batch 32 the depthwise kernels take the map as it is: the
    program holds the kernel, no ``pad``, ``slice`` or ``transpose`` of an
    activation's size (conv border, stride phases, overhanging rows), and
    no activation-sized ``copy`` but one layout conversion per map at the
    program's boundary."""
    n, h, w, c = shape
    ho, wo = (h - 1) // stride + 1, (w - 1) // stride + 1
    out = (n, ho, wo, c if co is None else co)
    if kind == "depthwise":
        text = _hlo(lambda x, k, b: depthwise(
            x, k, b, stride=stride, pad=1, act="relu6", interpret=False),
            one_chip, shape, (3, 3, c), (c,))
        kernel = "depthwise_conv2d"
    else:
        text = _hlo(lambda x, dw, db, pw, pb: fused_dw_pw(
            x, dw, db, pw, pb, stride=stride, pad=1, interpret=False),
            one_chip, shape, (3, 3, c), (c,), (c, co), (co,))
        kernel = "fused_dw_pw_conv"
    act = min(n * h * w * c, n * ho * wo * out[-1])
    glue = []
    for name, dims, op in _ENTRY_OP.findall(text[text.index("\nENTRY"):]):
        dims = tuple(int(d) for d in dims.split(",") if d)
        size = 1
        for d in dims:
            size *= d
        kinds = {op, name.split(".")[0]} & set(_GLUE)
        if kinds and size >= act:
            glue.append((kinds.pop(), dims))
    assert re.search(rf"%{kernel}[.\d]* = .*custom-call\(", text)
    assert all(op == "copy" for op, _ in glue), glue
    copies = [dims for _, dims in glue]
    assert set(copies) <= {shape, out} and len(set(copies)) == len(copies), \
        glue


def test_fused_inverted_residual_stride2_compiles(one_chip):
    """mobilenet_v2 b2: pw-expand 16->96, dw s2, pw-project 96->24."""
    _compile(lambda x, ew, eb, dw, db, pw, pb: fused_inverted_residual(
                 x, ew, eb, dw, db, pw, pb, stride=2, pad=1,
                 interpret=False),
             one_chip, (1, 112, 112, 16), (16, 96), (96,), (3, 3, 96),
             (96,), (96, 24), (24,))


def test_fused_dw_pw_compiles(one_chip):
    """mobilenet_v1 dw1+pw1: dw 3x3 s1 on 32 channels, pw 32->64."""
    _compile(lambda x, dw, db, pw, pb: fused_dw_pw(
                 x, dw, db, pw, pb, stride=1, pad=1, pw_act="relu6",
                 interpret=False),
             one_chip, (1, 112, 112, 32), (3, 3, 32), (32,), (32, 64),
             (64,))


# the device-op names the benchmark's roofline readers match
KERNEL_OPS = {"matmul_bias_act", "depthwise_conv2d", "fused_dw_pw_conv",
              "fused_pw_dw_pw_conv"}
_CUSTOM_CALL = re.compile(
    r"^\s*(?:ROOT )?%([\w-]+?)(?:\.\d+)* = .*custom-call\(", re.M)
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = ", re.M)


def test_exec_groups_compile_under_stable_names(one_chip, monkeypatch):
    """Every mobilenet_v2 exec group at 224 px compiles to a program named
    ``jit_dualcore_g<NN>_<core>``; its kernels keep the instruction names
    the device trace is read by (a group of XLA-routed layers alone holds
    a convolution and no kernel), and its instructions are named as when
    the group compiled under its old name, ``group_fn``."""
    import repro.kernels.util as kutil
    from repro.core.arch import DUAL_BASELINE, BoardModel
    from repro.core.scheduler import build_schedule
    from repro.dualcore.runtime import DualCoreRunner
    from repro.models.cnn import build_model

    monkeypatch.setattr(kutil, "default_interpret", lambda: False)
    # the kernels resolve ``interpret=None`` while tracing, so a trace an
    # earlier test cached for the same shapes is an interpret-mode body
    jax.clear_caches()
    params, _, g = build_model("mobilenet_v2")
    runner = DualCoreRunner("mobilenet_v2", params, build_schedule(
        g, DUAL_BASELINE, BoardModel(), "balanced"))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    env = {"h": jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32)}
    for gi, (fn, group) in enumerate(zip(runner._fns, runner.groups)):
        params = runner._params[group.core]
        name = f"dualcore_g{gi:02d}_{group.core}"
        text = fn.lower(on_chip(params), on_chip(env)).compile().as_text()
        assert text.startswith(f"HloModule jit_{name},")
        kernels = _CUSTOM_CALL.findall(text)
        if all(l.op == "conv" and xla_routed(l.K_h, l.K_w, l.stride, l.pad)
               for l in map(g.layer, group.layers)):
            assert XLA_CONV in text and not kernels, kernels
        else:
            assert kernels and set(kernels) <= KERNEL_OPS, kernels
        if gi < 2:                       # one group of each core

            def group_fn(params, env, body=fn.__wrapped__):
                return body(params, env)

            old = jax.jit(group_fn).lower(on_chip(params), on_chip(env))
            assert _INSTRUCTION.findall(old.compile().as_text()) == \
                _INSTRUCTION.findall(text)
        env = jax.eval_shape(fn, params, env)
