"""Fused depthwise->pointwise Pallas kernels (DESIGN.md §3).

The paper's dual-OPU overlaps a communication-bound depthwise layer on the
p-core with the compute-bound pointwise layers on the c-core, keeping the
intermediate feature map on-chip.  The seed's software analogue did the
opposite: ``models/cnn.py`` round-tripped every activation through HBM
between the depthwise and pointwise kernels of a MobileNet block.  These
kernels run the whole block in ONE pallas_call, gridded over (image,
output-row tile, C_out tile):

  fused_dw_pw_conv      dw(KxK, stride s) -> pw(1x1)
  fused_pw_dw_pw_conv   pw-expand -> dw(KxK, stride s) -> pw-project
                        (MobileNet-v2 inverted residual, optional fused
                        residual add)

The depthwise result never leaves VMEM: at the first C_out tile of each
row tile the VPU computes the dw taps channel-block-by-channel-block from
the halo rows (p-core analogue; an element-indexed block, so consecutive
row tiles overlap by K_h - stride rows and VMEM use is bounded whatever
the map size) into a persistent float32 VMEM scratch; every C_out tile
then feeds that scratch to an MXU GEMM against its pointwise-weight
columns (c-core analogue).  The C_out grid dimension is innermost, so the
scratch survives across tiles and the dw pass runs once per row tile.
HBM sees the block input once (plus the halo rows) and the block output
once.

``fused_dw_pw_conv`` takes the map unpadded: ``util.dw_halo_tile`` builds
the zero border in a VMEM halo tile and reads stride-2 columns from it
with a strided read, one lane tile at a time, so neither the conv padding
nor a stride-phase split exists in HBM, and the overhanging last row and
C_out tiles are dropped by Pallas instead of sliced off.  The inverted
residual still zero-pads its narrow pre-expand input in the wrapper and
reads stride-2 taps from phase-split columns (``util.window_tap``): the
TPU compiler refuses strided slices of loaded values and strided ref
reads wider than one lane tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.util import (LANES, apply_act as _act, cdiv,
                                dw_halo_tile, halo_block, halo_start,
                                halo_tile_width, lane_tile, mxu_dot,
                                pad_axis, pad_to, resolve_interpret,
                                row_tiling, split_w_phases, vmem_row_bytes,
                                window_tap)


def _dw_tile(ref, lead: tuple, x_c0, w_ref, w_c0, bc, kh, kw, stride, bh,
             wo, wh):
    """Depthwise conv of one channel block of a VMEM halo tile ``ref``
    (indexed ``ref[*lead, rows, cols, channels]``, columns stride-phase
    split with phase width ``wh``; data from channel ``x_c0``, weights
    from channel ``w_c0``) -> f32 (bh, wo, bc).  Every tap re-reads the
    same VMEM tile (line-buffer reuse, DESIGN.md §2)."""
    acc = jnp.zeros((bh, wo, bc), jnp.float32)
    for i in range(kh):
        for j in range(kw):
            tap = window_tap(ref, lead, i, j, bh, wo, stride, wh,
                             pl.ds(x_c0, bc))
            acc = acc + tap.astype(jnp.float32) * \
                w_ref[i, j, w_c0:w_c0 + bc].astype(jnp.float32)
    return acc


def _fused_dw_pw_kernel(x_ref, dw_w_ref, *rest, h, pad, kh, kw, stride,
                        has_dw_b, has_pw_b, has_res, dw_act, pw_act):
    """Grid step (n, ht, co): x_ref (1,rows,W,Cp) halo rows of the unpadded
    map (``util.halo_start``); dw_w_ref (kh,kw,Cp); optional dw_b (1,Cp) /
    pw_b (1,bn) / res (1,bh,wo,bn); pw_w (Cp,bn); o_ref (1,bh,wo,bn);
    dws_ref (bh*wo, Cp) f32 scratch; xs_ref the VMEM halo tile, one lane
    tile of channels at a time (``util.dw_halo_tile``).

    The depthwise result of the row tile is computed channel-block-by-
    channel-block into the persistent VMEM scratch ONCE (co is the
    innermost grid dim, so the scratch survives across the C_out tiles)
    and every co step feeds it straight to the MXU — it never exists in
    HBM.
    """
    rest = list(rest)
    dw_b_ref = rest.pop(0) if has_dw_b else None
    pw_w_ref = rest.pop(0)
    pw_b_ref = rest.pop(0) if has_pw_b else None
    res_ref = rest.pop(0) if has_res else None
    o_ref, dws_ref, xs_ref = rest
    _, bh, wo, bn = o_ref.shape
    cp = dws_ref.shape[1]
    tile = pl.program_id(1)

    @pl.when(pl.program_id(2) == 0)
    def _compute_dw():
        for c0 in range(0, cp, LANES):   # p-core analogue, one lane tile
            cw = min(LANES, cp - c0)     # of the halo at a time
            dw = dw_halo_tile(x_ref, xs_ref, dw_w_ref, c0, cw,
                              tile=tile, h=h, pad=pad, kh=kh,
                              kw=kw, stride=stride, bh=bh, wo=wo)
            if dw_b_ref is not None:
                dw = dw + dw_b_ref[0, c0:c0 + cw].astype(jnp.float32)
            dws_ref[:, c0:c0 + cw] = _act(dw, dw_act).reshape(bh * wo, cw)

    out = mxu_dot(dws_ref[...], pw_w_ref[...].astype(jnp.float32))
    if pw_b_ref is not None:
        out = out + pw_b_ref[...].astype(jnp.float32)
    out = _act(out, pw_act)
    out = out.reshape(bh, wo, bn)
    if res_ref is not None:
        out = out + res_ref[0].astype(jnp.float32)
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("stride", "pad", "dw_act",
                                             "pw_act", "block_c", "block_n",
                                             "interpret"))
def fused_dw_pw_conv(x: jax.Array, dw_w: jax.Array,
                     dw_b: jax.Array | None, pw_w: jax.Array,
                     pw_b: jax.Array | None,
                     residual: jax.Array | None = None, *, stride: int = 1,
                     pad: int = 1, dw_act: str | None = "relu6",
                     pw_act: str | None = None, block_c: int = 128,
                     block_n: int = 128,
                     interpret: bool | None = None) -> jax.Array:
    """dw(KhxKw, stride) -> pw(1x1) in one pallas_call.

    x: (N,H,W,C); dw_w: (Kh,Kw,C); pw_w: (C,Co); biases (C,)/(Co,) or None;
    residual: (N,Ho,Wo,Co) or None (added after pw_act).  ``block_c`` and
    ``block_n`` are lane-aligned by ``lane_tile``; the output-row tile is
    sized to the VMEM budget.  The map goes to the kernel unpadded: the
    halo tile and its zero border are built in VMEM.
    """
    interpret = resolve_interpret(interpret)
    n, h, wd, c = x.shape
    kh, kw, cw = dw_w.shape
    assert cw == c and pw_w.shape[0] == c, (x.shape, dw_w.shape, pw_w.shape)
    co = pw_w.shape[1]
    ho = (h + 2 * pad - kh) // stride + 1
    wo = (wd + 2 * pad - kw) // stride + 1
    bc = lane_tile(block_c, c)
    bn = lane_tile(block_n, co)
    cp = cdiv(c, bc) * bc
    tile = (halo_tile_width(wd, pad), min(cp, LANES))  # VMEM halo tile row
    row = (2 * stride * vmem_row_bytes(wd, cp)       # halo, double-buffered
           + stride * vmem_row_bytes(*tile)          # halo tile
           + vmem_row_bytes(wo, cp)                  # dw scratch
           + 4 * vmem_row_bytes(wo, bn))             # output (+res) x2
    bh, n_ht, span, _ = row_tiling(ho, stride, kh, h + 2 * pad, row)
    rows = min(span, h)
    # channels padded only where the block does not divide them: the
    # padded lanes must be zeros, since they meet zero pw rows in the GEMM
    xp = pad_axis(x, 3, bc)
    dw_wp = pad_axis(dw_w, 2, bc)
    pw_wp = pad_to(pad_axis(pw_w, 0, bc), (cp, bn))
    grid = (n, n_ht, pw_wp.shape[1] // bn)
    in_specs = [
        pl.BlockSpec(halo_block(rows, wd, cp),
                     lambda i, t, j: (i, halo_start(t, bh, stride, pad, h,
                                                    rows), 0, 0)),
        pl.BlockSpec((kh, kw, cp), lambda i, t, j: (0, 0, 0)),
    ]
    operands: list[jax.Array] = [xp, dw_wp]
    if dw_b is not None:
        in_specs.append(pl.BlockSpec((1, cp), lambda i, t, j: (0, 0)))
        operands.append(pad_to(dw_b.reshape(1, c), (1, cp)))
    in_specs.append(pl.BlockSpec((cp, bn), lambda i, t, j: (0, j)))
    operands.append(pw_wp)
    if pw_b is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, t, j: (0, j)))
        operands.append(pad_to(pw_b.reshape(1, co), (1, bn)))
    if residual is not None:
        assert residual.shape == (n, ho, wo, co), residual.shape
        in_specs.append(pl.BlockSpec((1, bh, wo, bn),
                                     lambda i, t, j: (i, t, 0, j)))
        operands.append(residual)
    return pl.pallas_call(
        functools.partial(_fused_dw_pw_kernel, h=h, pad=pad, kh=kh, kw=kw,
                          stride=stride, has_dw_b=dw_b is not None,
                          has_pw_b=pw_b is not None,
                          has_res=residual is not None, dw_act=dw_act,
                          pw_act=pw_act),
        grid=grid,
        in_specs=in_specs,
        # the last row tile and C_out tile may overhang the output (and
        # the residual): Pallas drops what lies past (ho, co), so neither
        # is padded or sliced in HBM
        out_specs=pl.BlockSpec((1, bh, wo, bn), lambda i, t, j: (i, t, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, ho, wo, co), x.dtype),
        scratch_shapes=[pltpu.VMEM((bh * wo, cp), jnp.float32),
                        pltpu.VMEM((span, *tile), jnp.float32)],
        interpret=interpret,
    )(*operands)


def _fused_pw_dw_pw_kernel(x_ref, exp_w_ref, *rest, kh, kw, stride, pad, h,
                           wd, wh, bc, nc, has_exp_b, has_dw_b, has_proj_b,
                           has_res, exp_act, dw_act, proj_act):
    """Grid step (n, ht, co) of the inverted residual.

    x_ref (1,span,Wp,Ci) — the halo rows of the zero-padded *input*,
    columns stride-phase split (phase width ``wh``; the expand is 1x1, so
    it commutes with the split);
    exp_w (Ci,Cmp); optional exp_b (1,Cmp); dw_w (kh,kw,Cmp); optional
    dw_b (1,Cmp); proj_w (Cmp,bn); optional proj_b (1,bn); optional res
    (1,bh,wo,bn); o_ref (1,bh,wo,bn); dws_ref (bh*wo,Cmp) f32 — the
    expand+dw result of the row tile, computed once (co innermost) and
    reused across C_out tiles; eb_ref (span,Wp,bc) f32 — one channel block
    of the expanded halo tile.  The expand runs on the padded rows too;
    positions that are conv padding are then forced to zero (the padding
    of the *expanded* map), so neither the expanded map nor the dw result
    ever exists in HBM.  ``h``/``wd`` are the unpadded input dims.
    """
    rest = list(rest)
    exp_b_ref = rest.pop(0) if has_exp_b else None
    dw_w_ref = rest.pop(0)
    dw_b_ref = rest.pop(0) if has_dw_b else None
    proj_w_ref = rest.pop(0)
    proj_b_ref = rest.pop(0) if has_proj_b else None
    res_ref = rest.pop(0) if has_res else None
    o_ref, dws_ref, eb_ref = rest
    _, bh, wo, bn = o_ref.shape
    _, span, wp_, ci = x_ref.shape
    row0 = pl.program_id(1) * (bh * stride)   # padded-map row of the halo

    @pl.when(pl.program_id(2) == 0)
    def _compute_expand_dw():
        xm = x_ref[0].reshape(span * wp_, ci)
        # padded-map coordinates of every halo position of this row tile
        rows = jax.lax.broadcasted_iota(jnp.int32, (span, wp_, bc), 0) + row0
        q = jax.lax.broadcasted_iota(jnp.int32, (span, wp_, bc), 1)
        inside_w = jnp.zeros((span, wp_, bc), jnp.bool_)
        for ph in range(stride):     # undo the phase split: column q of
            col = (q - ph * wh) * stride + ph           # phase ph
            inside_w |= ((q >= ph * wh) & (q < (ph + 1) * wh)
                         & (col >= pad) & (col < pad + wd))
        inside = inside_w & (rows >= pad) & (rows < pad + h)
        for cblk in range(nc):
            c0 = cblk * bc
            # pw-expand for this channel block (MXU), epilogue in f32
            e = mxu_dot(xm, exp_w_ref[:, c0:c0 + bc])
            if exp_b_ref is not None:
                e = e + exp_b_ref[0, c0:c0 + bc].astype(jnp.float32)
            e = _act(e, exp_act).reshape(span, wp_, bc)
            eb_ref[...] = jnp.where(inside, e, 0.0)
            dw = _dw_tile(eb_ref, (), 0, dw_w_ref, c0, bc, kh, kw, stride,
                          bh, wo, wh)
            if dw_b_ref is not None:
                dw = dw + dw_b_ref[0, c0:c0 + bc].astype(jnp.float32)
            dws_ref[:, c0:c0 + bc] = _act(dw, dw_act).reshape(bh * wo, bc)

    out = mxu_dot(dws_ref[...], proj_w_ref[...].astype(jnp.float32))
    if proj_b_ref is not None:
        out = out + proj_b_ref[...].astype(jnp.float32)
    out = _act(out, proj_act)
    out = out.reshape(bh, wo, bn)
    if res_ref is not None:
        out = out + res_ref[0].astype(jnp.float32)
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("stride", "pad", "exp_act",
                                             "dw_act", "proj_act", "block_c",
                                             "block_n", "interpret"))
def fused_pw_dw_pw_conv(x: jax.Array, exp_w: jax.Array,
                        exp_b: jax.Array | None, dw_w: jax.Array,
                        dw_b: jax.Array | None, proj_w: jax.Array,
                        proj_b: jax.Array | None,
                        residual: jax.Array | None = None, *,
                        stride: int = 1, pad: int = 1,
                        exp_act: str | None = "relu6",
                        dw_act: str | None = "relu6",
                        proj_act: str | None = None, block_c: int = 128,
                        block_n: int = 128,
                        interpret: bool | None = None) -> jax.Array:
    """pw-expand -> dw(KhxKw, stride) -> pw-project in one pallas_call
    (MobileNet-v2 inverted residual; ``residual`` is fused into the
    epilogue when given).

    x: (N,H,W,Ci); exp_w: (Ci,Cm); dw_w: (Kh,Kw,Cm); proj_w: (Cm,Co).
    ``block_c`` / ``block_n`` are lane-aligned by ``lane_tile``; the
    output-row tile is sized to the VMEM budget.
    """
    interpret = resolve_interpret(interpret)
    n, h, wd, ci = x.shape
    cm = exp_w.shape[1]
    kh, kw, cmw = dw_w.shape
    assert exp_w.shape[0] == ci and cmw == cm and proj_w.shape[0] == cm
    co = proj_w.shape[1]
    ho = (h + 2 * pad - kh) // stride + 1
    wo = (wd + 2 * pad - kw) // stride + 1
    bc = lane_tile(block_c, cm)
    bn = lane_tile(block_n, co)
    exp_wp = pad_axis(exp_w, 1, bc)
    cmp_ = exp_wp.shape[1]
    wpad = wd + 2 * pad + 2 * 8                      # + phase/sublane pad
    row = (stride * (2 * vmem_row_bytes(wpad, ci)    # halo, double-buffered
                     + 2 * vmem_row_bytes(wpad, bc))  # expand value + eb
           + vmem_row_bytes(wo, cmp_)                # dw scratch
           + 4 * vmem_row_bytes(wo, bn))             # output (+res) x2
    bh, n_ht, span, extra_h = row_tiling(ho, stride, kh, h + 2 * pad, row)
    xp, wh = split_w_phases(
        jnp.pad(x, ((0, 0), (pad, pad + extra_h), (pad, pad), (0, 0))),
        stride)
    # width rounded up to the sublane tile, so the in-kernel
    # (rows, Wp, Ci) -> (rows*Wp, Ci) expand reshape is layout-free
    xp = pad_axis(xp, 2, 8)
    wp_ = xp.shape[2]
    dw_wp = pad_axis(dw_w, 2, bc)
    proj_wp = pad_to(pad_axis(proj_w, 0, bc), (cmp_, bn))
    cop = proj_wp.shape[1]
    grid = (n, n_ht, cop // bn)
    in_specs = [
        pl.BlockSpec(halo_block(span, wp_, ci),
                     lambda i, t, j: (i, t * bh * stride, 0, 0)),
        pl.BlockSpec((ci, cmp_), lambda i, t, j: (0, 0)),
    ]
    operands: list[jax.Array] = [xp, exp_wp]
    if exp_b is not None:
        in_specs.append(pl.BlockSpec((1, cmp_), lambda i, t, j: (0, 0)))
        operands.append(pad_to(exp_b.reshape(1, cm), (1, cmp_)))
    in_specs.append(pl.BlockSpec((kh, kw, cmp_), lambda i, t, j: (0, 0, 0)))
    operands.append(dw_wp)
    if dw_b is not None:
        in_specs.append(pl.BlockSpec((1, cmp_), lambda i, t, j: (0, 0)))
        operands.append(pad_to(dw_b.reshape(1, cm), (1, cmp_)))
    in_specs.append(pl.BlockSpec((cmp_, bn), lambda i, t, j: (0, j)))
    operands.append(proj_wp)
    if proj_b is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, t, j: (0, j)))
        operands.append(pad_to(proj_b.reshape(1, co), (1, bn)))
    if residual is not None:
        assert residual.shape == (n, ho, wo, co), residual.shape
        in_specs.append(pl.BlockSpec((1, bh, wo, bn),
                                     lambda i, t, j: (i, t, 0, j)))
        operands.append(pad_axis(pad_axis(residual, 3, bn), 1, bh))
    out = pl.pallas_call(
        functools.partial(_fused_pw_dw_pw_kernel, kh=kh, kw=kw,
                          stride=stride, pad=pad, h=h, wd=wd, wh=wh, bc=bc,
                          nc=cmp_ // bc, has_exp_b=exp_b is not None,
                          has_dw_b=dw_b is not None,
                          has_proj_b=proj_b is not None,
                          has_res=residual is not None, exp_act=exp_act,
                          dw_act=dw_act, proj_act=proj_act),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bh, wo, bn), lambda i, t, j: (i, t, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, n_ht * bh, wo, cop), x.dtype),
        scratch_shapes=[pltpu.VMEM((bh * wo, cmp_), jnp.float32),
                        pltpu.VMEM((span, wp_, bc), jnp.float32)],
        interpret=interpret,
    )(*operands)
    return out[:, :ho, :, :co]
