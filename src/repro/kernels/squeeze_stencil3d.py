"""Fused 3D block-level Squeeze stencil kernels — the v4/v5 kernel
family of kernels/squeeze_stencil.py lifted to 3D NBB fractals.

Both entry points are driven by the static block tables of
``compact3d.BlockLayout3D`` (built from the lambda3/nu3 maps) and
parameterized by a single-channel ``StencilWorkload`` over the 26-cell
3D Moore neighborhood:

  * ``stencil3d_step_fused_k`` (v4-style temporal fusion): the depth-k
    halo — six face slabs covering the full window frame — is gathered
    once by XLA over the static neighbor table, then the kernel runs k
    update substeps on a (rho+2k)^3 window held in VMEM before the
    single center write-back, for a tile of blocks per program.
    Per-window occupancy is rebuilt in-kernel from the shared periodic
    ``window_mask`` gated by each block's 26-direction existence row,
    delivered as a VMEM block (the 2D substep mask discipline, per
    region).

  * ``stencil3d_step_mxu_k`` (v5-style MXU stencil-as-matmul): the
    26-neighbor aggregation runs as banded matmul contractions *per
    z-slab*: each z-plane of the (3,3,3) weight tensor factors into
    <= 2 rank-1 terms (``workload.weight_factors3``), so slab z's
    aggregate is ``sum_dz sum_t R_t(dz) @ X[z+dz] @ C_t(dz)^T`` — MXU
    contractions on (rho+2k, P*(rho+2k)) slab matrices with P blocks
    lane-packed along x (``BlockLayout3D.macro_tiles``), instead of 26
    VPU shift-adds. Slot borders and the z-shifted window edges
    accumulate truncated-band garbage ring by ring; the center sits at
    distance >= k from every border, so the final extraction is exact
    (the same shrinking-window argument as the 2D v5 kernel).

State is (n_blocks, rho, rho, rho) indexed [b, z, y, x] (single-channel
workloads only, as the 3D engines). ``interpret=None`` auto-detects:
compiled Mosaic on TPU, the Pallas interpreter on the CPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.compact3d import BlockLayout3D
from repro.kernels.common import (MXU_PRECISION, block_tiling,
                                  compiler_params, resolve_interpret,
                                  to_dtype, work_dtype)
from repro.workloads.base import (MOORE3_DIRS, StencilWorkload,
                                  banded_operators)
from repro.workloads.rules import LIFE3D

#: direction -> MOORE3_DIRS index (the gather/table column order)
DIR3_INDEX = {d: i for i, d in enumerate(MOORE3_DIRS)}


def _gather_halo3_k(layout: BlockLayout3D, s: jnp.ndarray, k: int, table):
    """Depth-k halo slabs via slab-level XLA gathers over the static
    26-direction neighbor table (k <= rho, so every piece comes from one
    Moore neighbor). Returns six pieces whose union is the full window
    frame:

      zlo/zhi (C, nb, k, w, w)     — full-xy-extent z faces, including
                                     all 12 edge and 8 corner pieces at
                                     that z (w = rho + 2k);
      ylo/yhi (C, nb, rho, k, w)   — center-z y faces incl. x edges;
      xlo/xhi (C, nb, rho, rho, k) — center x faces.

    Ghost ids index an appended zero slab. No zero-weight skipping: a
    k >= 2 substep chain propagates diagonal values inward even under
    orthogonal-only weights (the radius-k L1 dependency cone).
    """
    rho = layout.rho
    nc = s.shape[0]

    def take(strip, d):  # strip (C, nb, ...), pre-sliced before the gather
        z = jnp.zeros((nc, 1) + strip.shape[2:], s.dtype)
        return jnp.concatenate([strip, z], 1)[:, table[:, DIR3_INDEX[d]]]

    x_src = {-1: slice(rho - k, rho), 0: slice(None), 1: slice(0, k)}

    def zface(dz):  # (C, nb, k, w, w): 9 pieces across (dy, dx)
        rows = []
        for dy in (-1, 0, 1):
            rows.append(jnp.concatenate(
                [take(s[:, :, x_src[dz], x_src[dy], x_src[dx]],
                      (dx, dy, dz)) for dx in (-1, 0, 1)], axis=-1))
        return jnp.concatenate(rows, axis=-2)

    def yface(dy):  # (C, nb, rho, k, w): 3 pieces across dx at dz = 0
        return jnp.concatenate(
            [take(s[:, :, :, x_src[dy], x_src[dx]], (dx, dy, 0))
             for dx in (-1, 0, 1)], axis=-1)

    return (zface(-1), zface(1), yface(-1), yface(1),
            take(s[:, :, :, :, rho - k:], (-1, 0, 0)),
            take(s[:, :, :, :, :k], (1, 0, 0)))


def _assemble_window(c, zlo, zhi, ylo, yhi, xlo, xhi):
    """(..., rho^3) center + six face slabs -> (..., w^3) window, by
    concatenation (x faces, then y faces, then z faces)."""
    mid = jnp.concatenate([xlo, c, xhi], axis=-1)
    mid = jnp.concatenate([ylo, mid, yhi], axis=-2)
    return jnp.concatenate([zlo, mid, zhi], axis=-3)


def _gate3(ex: jnp.ndarray, rho: int, k: int) -> jnp.ndarray:
    """(T, 26) existence rows -> (T, w, w, w) gate: 1 in the center and
    in the halo regions of existing neighbors, 0 in ghost regions."""
    t = ex.shape[0]
    size = {-1: k, 0: rho, 1: k}

    def region(dx, dy, dz):
        shape = (t, size[dz], size[dy], size[dx])
        if (dx, dy, dz) == (0, 0, 0):
            return jnp.ones(shape, ex.dtype)
        d = DIR3_INDEX[(dx, dy, dz)]
        return jnp.broadcast_to(ex[:, d:d + 1].reshape(t, 1, 1, 1), shape)

    return jnp.concatenate([
        jnp.concatenate([
            jnp.concatenate([region(dx, dy, dz) for dx in (-1, 0, 1)], -1)
            for dy in (-1, 0, 1)], -2)
        for dz in (-1, 0, 1)], -3)


def _tile_bytes3(d: int, h: int, w: int) -> int:
    """VMEM bytes of one (d, h, w) 32-bit volume, (8, 128)-tile padded."""
    return d * (-(-h // 8) * 8) * (-(-w // 128) * 128) * 4


def _pad_blocks(x: jnp.ndarray, n: int, axis: int = 1) -> jnp.ndarray:
    extra = n - x.shape[axis]
    if extra == 0:
        return x
    shape = list(x.shape)
    shape[axis] = extra
    return jnp.concatenate([x, jnp.zeros(shape, x.dtype)], axis=axis)


# ======================================================================
# v4-style: depth-k window assembled in VMEM, k substeps, one write
# ======================================================================
def _fused3_k_kernel(workload, k, ex_ref, c_ref, zlo_ref, zhi_ref, ylo_ref,
                     yhi_ref, xlo_ref, xhi_ref, wmask_ref, out_ref):
    """One grid step = a tile of T blocks: assemble the (T, w, w, w)
    windows, rebuild their occupancy (periodic window mask x each
    block's existence row per region), then run the workload's k fused
    substeps."""
    wd = work_dtype(workload)
    rho = c_ref.shape[-1]
    padded = _assemble_window(*(r[0].astype(wd) for r in (
        c_ref, zlo_ref, zhi_ref, ylo_ref, yhi_ref, xlo_ref, xhi_ref)))
    mask = _gate3(ex_ref[...], rho, k) * wmask_ref[...]
    nxt = workload.tile_rule_k(padded, mask, k, ndim=3, agg_dtype=wd)
    out_ref[0] = to_dtype(nxt, out_ref.dtype)


def stencil3d_step_fused_k(layout: BlockLayout3D, state: jnp.ndarray,
                           workload: StencilWorkload = LIFE3D, *,
                           k: int = 2,
                           interpret: Optional[bool] = None) -> jnp.ndarray:
    """Advance ``k`` exact 3D steps in ONE kernel launch (k <= rho).

    state (n_blocks, rho, rho, rho) -> same, k steps later. The depth-k
    halo is gathered once; the kernel runs k substeps on a (rho+2k)^3
    window in VMEM and writes the center back once.
    """
    if k < 1:
        raise ValueError(f"need k >= 1, got k={k}")
    if k > layout.rho:
        raise ValueError(
            f"fused 3D kernel needs k <= rho, got k={k} > rho={layout.rho} "
            "(use Squeeze3DBlockEngine.step_k for deeper halos)")
    layout.materialize()
    return _stencil3d_step_fused_k(layout, state, layout.dev_neighbor_table,
                                   workload, k,
                                   interpret=resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("layout", "workload", "k", "interpret"))
def _stencil3d_step_fused_k(layout: BlockLayout3D, state: jnp.ndarray,
                            table, workload: StencilWorkload, k: int, *,
                            interpret: bool) -> jnp.ndarray:
    rho, nb = layout.rho, layout.n_blocks
    w = rho + 2 * k
    tb, n_pad = block_tiling(nb, _tile_bytes3(w, w, w))
    s = _pad_blocks(state[None], n_pad)   # explicit channel axis
    pieces = [_pad_blocks(x, n_pad)
              for x in _gather_halo3_k(layout, state[None], k, table)]
    ex = _pad_blocks((table != layout.ghost).astype(jnp.int32), n_pad, 0)
    blk = lambda *shape: pl.BlockSpec((1, tb) + shape, lambda i: (0, i) + (0,) * len(shape))  # noqa: E731,E501
    out = pl.pallas_call(
        functools.partial(_fused3_k_kernel, workload, k),
        grid=(n_pad // tb,),
        in_specs=[
            pl.BlockSpec((tb, 26), lambda i: (i, 0)),
            blk(rho, rho, rho),
            blk(k, w, w), blk(k, w, w),          # z faces
            blk(rho, k, w), blk(rho, k, w),      # y faces
            blk(rho, rho, k), blk(rho, rho, k),  # x faces
            pl.BlockSpec((w, w, w), lambda i: (0, 0, 0)),
        ],
        out_specs=blk(rho, rho, rho),
        out_shape=jax.ShapeDtypeStruct((1, n_pad, rho, rho, rho),
                                       workload.dtype),
        interpret=interpret,
        compiler_params=compiler_params(interpret),
    )(ex, s, *pieces, jnp.asarray(layout.window_mask(k).astype(np.int32)))
    return out[0, :nb]


# ======================================================================
# v5-style: z-slab banded MXU contractions on lane-packed macro-tiles
# ======================================================================
@functools.lru_cache(maxsize=128)
def _mxu3_operators(workload: StencilWorkload, w: int, p: int):
    """Static MXU contraction operands for one (workload, window, pack):
    per rank-1 term of each nonzero z-plane of the weight tensor, the
    3-tap row factor and the block-diagonal (per lane-packed slot)
    transpose ``CT`` (p*w, p*w) of its banded column contraction, plus
    the static tuple of per-term z shifts — slab z's aggregate is
    ``sum_t rowmix_t(X[z + dz_t]) @ CT_t``."""
    rows, cts, dzs = [], [], []
    for dz in (-1, 0, 1):
        terms = workload.weight_factors3[dz + 1]
        if not terms:
            continue
        _, cm = banded_operators(terms, w, np.float32)
        for t, (row, _) in enumerate(terms):
            ct = np.zeros((p * w, p * w), np.float32)
            for sl in range(p):
                ct[sl * w:(sl + 1) * w, sl * w:(sl + 1) * w] = cm[t].T
            rows.append(tuple(row))
            cts.append(ct)
            dzs.append(dz)
    return tuple(rows), np.stack(cts), tuple(dzs)


def _zshift(x: jnp.ndarray, dz: int) -> jnp.ndarray:
    """out[z] = x[z + dz] over the trailing-3 z axis, zero-padded at the
    window border (border slabs are garbage-by-design: they sit outside
    the shrinking live window of the fused substeps)."""
    if dz == 0:
        return x
    nz = x.shape[-3]
    pad = jnp.zeros(x.shape[:-3] + (1,) + x.shape[-2:], x.dtype)
    if dz > 0:
        return jnp.concatenate([x[..., 1:, :, :], pad], axis=-3)
    return jnp.concatenate([pad, x[..., :nz - 1, :, :]], axis=-3)


def _yshift(x: jnp.ndarray, d: int) -> jnp.ndarray:
    """out[..., y, :] = x[..., y + d, :], zero past the window border."""
    z = jnp.zeros(x.shape[:-2] + (1,) + x.shape[-1:], x.dtype)
    if d > 0:
        return jnp.concatenate([x[..., 1:, :], z], axis=-2)
    return jnp.concatenate([z, x[..., :-1, :]], axis=-2)


def _mxu3_kernel(workload, k, p, rows, dzs, ex_ref, c_ref, zlo_ref,
                 zhi_ref, ylo_ref, yhi_ref, xlo_ref, xhi_ref, wmask_ref,
                 ct_ref, out_ref):
    """One grid step = a tile of T macro-tiles: assemble the (T, w, wr,
    P*w) lane-packed windows (P block slots side by side along x, rows
    padded to wr, a multiple of 8), rebuild each slot's occupancy from
    the shared periodic window mask gated by its existence row, then
    run k substeps whose 26-neighbor aggregation is, per rank-1 term of
    each z-plane, a 3-tap y combination of the z-shifted slabs followed
    by ONE MXU contraction of all rows with the banded column operand."""
    rho = c_ref.shape[-2]
    w = rho + 2 * k
    wr = -(-w // 8) * 8
    f32 = jnp.float32

    def ld(ref):
        return to_dtype(ref[0], f32)         # (T, d, h, cols)

    c, zlo, zhi, ylo, yhi, xlo, xhi = (ld(r) for r in (
        c_ref, zlo_ref, zhi_ref, ylo_ref, yhi_ref, xlo_ref, xhi_ref))
    ex = ex_ref[...]                         # (T, 26P)
    wm = wmask_ref[...]
    wins, masks = [], []
    for sl in range(p):
        sw, sk = slice(sl * w, (sl + 1) * w), slice(sl * k, (sl + 1) * k)
        wins.append(_assemble_window(
            c[..., sl * rho:(sl + 1) * rho], zlo[..., sw], zhi[..., sw],
            ylo[..., sw], yhi[..., sw], xlo[..., sk], xhi[..., sk]))
        masks.append(_gate3(ex[:, 26 * sl:26 * (sl + 1)], rho, k) * wm)
    cur = jnp.concatenate(wins, axis=-1)     # (T, w, w, P*w)
    mask = jnp.concatenate(masks, axis=-1)
    if wr > w:  # zero rows past the window: outside every live region
        cur = jnp.concatenate(
            [cur, jnp.zeros(cur.shape[:-2] + (wr - w, p * w), f32)], -2)
        mask = jnp.concatenate(
            [mask, jnp.zeros(mask.shape[:-2] + (wr - w, p * w),
                             mask.dtype)], -2)
    t = cur.shape[0]
    ct = ct_ref[...]                         # (n_terms, P*w, P*w) f32
    int_agg = jnp.issubdtype(jnp.dtype(workload.agg_dtype), jnp.integer)
    for _ in range(k):
        up, dn = _yshift(cur, -1), _yshift(cur, 1)
        agg = None
        for i, (row, dz) in enumerate(zip(rows, dzs)):
            y = _zshift(row[0] * up + row[1] * cur + row[2] * dn, dz)
            y = jnp.dot(y.reshape(t * w * wr, p * w), ct[i],
                        precision=MXU_PRECISION,
                        preferred_element_type=f32)
            agg = y if agg is None else agg + y
        agg = agg.reshape(t, w, wr, p * w)
        # integer CA aggregates: the f32 products reconstruct integer
        # neighbor counts to ~1e-5, so nearest-int rounding is bit-exact
        if int_agg:
            agg = jnp.rint(agg).astype(jnp.int32)
        cur = workload.apply(cur, agg, mask).astype(f32)

    out = jnp.concatenate(
        [cur[:, k:k + rho, k:k + rho, sl * w + k:sl * w + k + rho]
         for sl in range(p)], axis=-1)       # (T, rho, rho, P*rho)
    out_ref[0] = to_dtype(out, out_ref.dtype)


def _pack_macro3(arr: jnp.ndarray, nb: int, p: int, n_macro: int):
    """(L, nb, d, h, c) per-block slabs -> (L, n_macro, d, h, P*c)
    lane-packed macro slabs (zero-filled padding slots past nb)."""
    lead, _, d, h, cols = arr.shape
    a = _pad_blocks(arr, n_macro * p)
    a = a.reshape(lead, n_macro, p, d, h, cols).transpose(0, 1, 3, 4, 2, 5)
    return a.reshape(lead, n_macro, d, h, p * cols)


def stencil3d_step_mxu_k(layout: BlockLayout3D, state: jnp.ndarray,
                         workload: StencilWorkload = LIFE3D, *, k: int = 1,
                         p: Optional[int] = None,
                         interpret: Optional[bool] = None) -> jnp.ndarray:
    """v5-style 3D MXU step: ``k`` exact steps in one macro-tile launch
    whose 26-neighbor aggregation runs as banded matmuls per z-slab
    (k <= rho). state (n_blocks, rho, rho, rho) -> same. ``p`` overrides
    the macro-tile packing P (None = lane heuristic)."""
    if k < 1:
        raise ValueError(f"need k >= 1, got k={k}")
    if k > layout.rho:
        raise ValueError(
            f"mxu 3D kernel needs k <= rho, got k={k} > rho={layout.rho} "
            "(use Squeeze3DBlockEngine.step_k for deeper halos)")
    # resolve the packing override to its concrete P so the jit cache
    # keys on one value
    p = layout.macro_tiles(k, p=p)[0]
    layout.materialize()
    return _stencil3d_step_mxu_k(layout, state, layout.dev_neighbor_table,
                                 workload, k, p,
                                 interpret=resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("layout", "workload", "k", "p",
                                    "interpret"))
def _stencil3d_step_mxu_k(layout: BlockLayout3D, state: jnp.ndarray, table,
                          workload: StencilWorkload, k: int, p: int, *,
                          interpret: bool) -> jnp.ndarray:
    rho, nb = layout.rho, layout.n_blocks
    w = rho + 2 * k
    wr = -(-w // 8) * 8
    n_macro = -(-nb // p)
    tm, n_macro = block_tiling(n_macro, _tile_bytes3(w, wr, p * w))
    s = state[None]
    pieces = _gather_halo3_k(layout, s, k, table)

    def pack(arr):
        return _pack_macro3(arr, nb, p, n_macro)

    ex = _pad_blocks((table != layout.ghost).astype(jnp.int32),
                     n_macro * p, 0).reshape(n_macro, 26 * p)
    rows, ct, dzs = _mxu3_operators(workload, w, p)
    n_terms = ct.shape[0]

    def blk(d, h, cols):
        return pl.BlockSpec((1, tm, d, h, cols), lambda i: (0, i, 0, 0, 0))

    out = pl.pallas_call(
        functools.partial(_mxu3_kernel, workload, k, p, rows, dzs),
        grid=(n_macro // tm,),
        in_specs=[
            pl.BlockSpec((tm, 26 * p), lambda i: (i, 0)),
            blk(rho, rho, p * rho),
            blk(k, w, p * w), blk(k, w, p * w),          # z faces
            blk(rho, k, p * w), blk(rho, k, p * w),      # y faces
            blk(rho, rho, p * k), blk(rho, rho, p * k),  # x faces
            pl.BlockSpec((w, w, w), lambda i: (0, 0, 0)),
            pl.BlockSpec((n_terms, p * w, p * w), lambda i: (0, 0, 0)),
        ],
        out_specs=blk(rho, rho, p * rho),
        out_shape=jax.ShapeDtypeStruct((1, n_macro, rho, rho, p * rho),
                                       workload.dtype),
        interpret=interpret,
        compiler_params=compiler_params(interpret),
    )(ex, pack(s), *(pack(a) for a in pieces),
      jnp.asarray(layout.window_mask(k).astype(np.int32)), jnp.asarray(ct))
    out = out.reshape(n_macro, rho, rho, p, rho).transpose(0, 3, 1, 2, 4)
    return out.reshape(n_macro * p, rho, rho, rho)[:nb]
