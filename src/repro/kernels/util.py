"""Shared helpers for the Pallas kernel packages.

Every kernel wrapper needs the same things: ceil-division for grids,
zero-padding up to block multiples (so BlockSpec grids divide evenly), a
backend-aware default for Pallas ``interpret`` mode — interpret on CPU
(tests), compiled on a real TPU — and, for the conv family, the tiling the
TPU compiler accepts: lane-aligned channel blocks, output-row tiles whose
halo fits the VMEM budget, and window taps that never slice a loaded value
with a stride.  They live here so conv_gemm / depthwise / fused_block /
attention / rmsnorm stay in sync (DESIGN.md §5).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def cdiv(a: int, b: int) -> int:
    """Ceiling division (grid sizing)."""
    return -(-a // b)


def pad_to(x: jax.Array, mult: tuple[int, ...]) -> jax.Array:
    """Zero-pad each leading axis of ``x`` up to a multiple of ``mult[i]``.

    ``mult`` may be shorter than ``x.ndim``; trailing axes are left alone.
    """
    pads = [(0, -s % m) for s, m in zip(x.shape, mult)]
    pads += [(0, 0)] * (x.ndim - len(pads))
    if any(p[1] for p in pads):
        x = jnp.pad(x, pads)
    return x


def pad_axis(x: jax.Array, axis: int, mult: int) -> jax.Array:
    """Zero-pad a single axis of ``x`` up to a multiple of ``mult``."""
    extra = -x.shape[axis] % mult
    if not extra:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, extra)
    return jnp.pad(x, pads)


LANES = 128                          # last-axis tile of a TPU vreg
SUBLANES = 8                         # second-to-last axis tile (32-bit)
# VMEM one grid step of a conv-family kernel may plan for (inputs double-
# buffered, scratch, outputs).  The scoped VMEM default of a v5e core is
# 16 MiB, so tiles sized to this fit with room for Mosaic's own temporaries.
VMEM_TILE_BUDGET = 6 * 1024 * 1024


def lane_tile(block: int, dim: int) -> int:
    """A block size the TPU compiler accepts on a lane (last) axis: the
    full ``dim`` when ``block`` covers it (or ``dim`` fits one lane tile),
    else ``block`` rounded down to a multiple of 128."""
    if block >= dim or dim <= LANES:
        return dim
    return max(LANES, block - block % LANES)


def vmem_row_bytes(w: int, c: int, itemsize: int = 4) -> int:
    """VMEM footprint of one (w, c) row of an NHWC tile, with the
    sublane/lane padding the TPU layout adds."""
    return (cdiv(w, SUBLANES) * SUBLANES * cdiv(c, LANES) * LANES
            * itemsize)


def row_tiling(out_rows: int, stride: int, kh: int, in_rows: int,
               row_bytes: int, *, limit: int = 0,
               budget: int = VMEM_TILE_BUDGET) -> tuple[int, int, int, int]:
    """Tile the output rows of a KxK conv so one grid step's tiles stay
    within ``budget`` (``row_bytes`` = VMEM per output row, halo
    included; ``limit`` caps the rows when given).  Returns ``(bh, n_ht,
    span, extra)``: rows per tile (evened out so the last tile is not
    mostly padding), the tile count, the input rows one tile reads (tap
    ``i`` reads ``stride*bh`` contiguous rows from row ``i``, see
    :func:`window_tap`), and the bottom rows to pad the ``in_rows``-row
    (already conv-padded) input by so the last tile's halo stays in
    bounds."""
    bh = max(1, min(out_rows, budget // max(row_bytes, 1)))
    if limit > 0:
        bh = min(bh, limit)
    n_ht = cdiv(out_rows, bh)
    bh = cdiv(out_rows, n_ht)
    span = stride * bh + kh - 1
    return bh, n_ht, span, max(0, (n_ht - 1) * bh * stride + span - in_rows)


def halo_block(span: int, w: int, c: int):
    """Element-indexed block of ``span`` rows x the full width: the index
    map returns the first row of each output-row tile's halo, so
    consecutive tiles overlap by the window's extra rows and one grid step
    holds only its own rows in VMEM."""
    return (pl.Element(1), pl.Element(span), pl.Element(w), pl.Element(c))


def halo_start(tile, bh: int, stride: int, pad: int, h: int, rows: int):
    """First map row of the ``rows``-row block (``rows <= h``) that holds
    every in-map row of output-row tile ``tile``'s halo, whose first row
    is map row ``tile*stride*bh - pad`` (negative in the top border): that
    row, clipped so the block stays inside the ``h``-row map.  The index
    map and the kernel both call it, so they agree on the block."""
    return jnp.clip(tile * (stride * bh) - pad, 0, h - rows)


def halo_tile_width(w: int, pad: int) -> int:
    """Columns of the VMEM halo tile of :func:`dw_halo_tile` for a
    ``w``-column map: the map starts at the first sublane-aligned column
    at or after ``pad`` (so each row is copied in with aligned stores),
    with ``pad`` zero columns on either side of it."""
    return cdiv(pad, SUBLANES) * SUBLANES + w + pad


def dw_halo_tile(x_ref, xs_ref, w_ref, c0: int, cw: int, *, tile, h: int,
                 pad: int, kh: int, kw: int, stride: int, bh: int,
                 wo: int) -> jax.Array:
    """Depthwise conv of channels ``[c0, c0 + cw)`` of one output-row tile,
    from a halo block of the *unpadded* NHWC map -> f32 (bh, wo, cw).

    ``x_ref`` (1, rows, W, C) holds map rows ``[halo_start(...), ... +
    rows)``.  ``xs_ref`` (span, :func:`halo_tile_width`, >= cw) f32 is the
    VMEM halo tile: map rows ``[tile*stride*bh - pad, ... + span)``, the
    ``pad`` columns either side of the map and every row outside the map
    zero, so the conv's zero padding never exists in HBM.  Taps read
    ``xs_ref`` with the conv's stride on both axes (a strided ref read, at
    most one lane tile wide: ``cw <= 128``).  Same taps, same order and
    f32 arithmetic as the reference depthwise conv; ``w_ref`` (kh, kw, C)
    shares ``x_ref``'s channel index."""
    span, wp, _ = xs_ref.shape
    _, rows, w, _ = x_ref.shape
    lo = wp - w - pad                  # the map's first column in the tile
    row0 = tile * (stride * bh) - pad  # map row of the tile's first row
    start = halo_start(tile, bh, stride, pad, h, rows)
    if pad:
        zeros = jnp.zeros((span, pad, cw), jnp.float32)
        xs_ref[:, lo - pad:lo, :cw] = zeros
        xs_ref[:, lo + w:, :cw] = zeros

    def copy_row(k, carry):
        r = row0 + k
        v = x_ref[0, jnp.clip(r - start, 0, rows - 1), :, pl.ds(c0, cw)]
        xs_ref[k, lo:lo + w, :cw] = jnp.where(
            (r >= 0) & (r < h), v.astype(jnp.float32), 0.0)
        return carry

    jax.lax.fori_loop(0, span, copy_row, 0)
    acc = jnp.zeros((bh, wo, cw), jnp.float32)
    for i in range(kh):          # unrolled window taps — every tap reads the
        for j in range(kw):      # same VMEM tile (line-buffer reuse)
            tap = xs_ref[pl.ds(i, bh, stride=stride),
                         pl.ds(lo - pad + j, wo, stride=stride), :cw]
            acc = acc + tap * w_ref[i, j, pl.ds(c0, cw)].astype(jnp.float32)
    return acc


def split_w_phases(xp: jax.Array, stride: int) -> tuple[jax.Array, int]:
    """Stride-phase split of the W axis of a padded NHWC map: column
    ``k*stride + p`` moves to ``p*wh + k``.  Every window tap then reads
    ``wo`` *contiguous* columns.  The TPU compiler refuses a strided slice
    of a loaded value, and a strided ref read wider than one lane tile, so
    these taps are never strided along W in-kernel.  Only the kernel that
    zero-pads in the wrapper still uses it (the inverted residual, which
    pads its narrow pre-expand input); the depthwise kernels read
    stride-2 columns from the VMEM halo tile (:func:`dw_halo_tile`).
    Returns the rearranged map and the phase width ``wh``."""
    if stride == 1:
        return xp, xp.shape[2]
    n, h, w, c = xp.shape
    wh = cdiv(w, stride)
    xp = pad_axis(xp, 2, stride)
    xp = xp.reshape(n, h, wh, stride, c).transpose(0, 1, 3, 2, 4)
    return xp.reshape(n, h, stride * wh, c), wh


def window_tap(ref, lead: tuple, i: int, j: int, bh: int, wo: int,
               stride: int, wh: int, chan=slice(None)) -> jax.Array:
    """Tap (i, j) of a KxK window over a halo tile ``ref`` indexed
    ``ref[*lead, rows, cols, chan]`` whose columns are phase-split by
    :func:`split_w_phases` (phase width ``wh``) -> (bh, wo, C).  Rows are
    one contiguous read of ``stride*bh`` rows whose every ``stride``-th is
    kept by a reshape of the (untiled, so free) leading axis."""
    col = (j % stride) * wh + j // stride
    v = ref[(*lead, pl.ds(i, stride * bh), pl.ds(col, wo), chan)]
    if stride > 1:
        v = v.reshape(bh, stride, wo, v.shape[-1])[:, 0]
    return v


def mxu_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """The kernels' one MXU contraction, accumulated in f32.  f32 operands
    are contracted at full f32 precision: Mosaic's default runs an f32 dot
    as a single bf16 pass (2.4e-3 relative error per 256x512x256 matmul on
    a v5e, against 1.8e-7 at HIGHEST), which compounds to ~2.5e-2 over
    mobilenet_v2 — a model served "in f32" must compute in f32.  Lower
    precision is a dtype choice (bf16 operands), not a silent default."""
    precision = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
                 else None)
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=precision)


def apply_act(x: jax.Array, act: str | None) -> jax.Array:
    """The shared fused-epilogue activation (None | 'relu' | 'relu6')."""
    if act == "relu":
        return jnp.maximum(x, 0.0)
    if act == "relu6":
        return jnp.clip(x, 0.0, 6.0)
    return x


def bench_best_us(fn, reps: int = 3) -> float:
    """Best-of-``reps`` wall-clock of ``fn`` in microseconds, after one
    warm-up call (compile).  The one timing rule shared by the autotuner
    and the --smoke benchmark, so both rank kernels identically."""
    import time
    jax.block_until_ready(fn())            # compile / warm-up
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, (time.perf_counter() - t0) * 1e6)
    return best


def default_interpret() -> bool:
    """Pallas interpret-mode default: compiled on TPU, interpret elsewhere.

    All kernel wrappers take ``interpret: bool | None = None`` and resolve
    ``None`` through here, so a real-TPU run is fast by default while the
    CPU CI keeps validating the kernel bodies in interpret mode.  These
    kernels use TPU-specific scratch/memory spaces (pltpu.*), so any
    non-TPU backend (CPU *or* GPU) must interpret.
    """
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    return default_interpret() if interpret is None else bool(interpret)
