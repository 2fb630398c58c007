"""Fused block-level Squeeze stencil kernels on a compact NBB fractal,
paper Sections 3.5 + 4 adapted to TPU.

All variants are driven by the static block-neighbor table built from
the paper's lambda/nu maps (compact.BlockLayout.neighbor_table), passed
to every entry point as a traced operand (``table=``; default: the
layout's device copy) so it never becomes a constant of the compiled
program, and all are parameterized by a ``StencilWorkload`` whose
``tile_rule`` supplies the traced in-tile update (the halo plumbing
below is rule-agnostic):

  * ``stencil_step_blocks``  (v1, paper-shaped): the Pallas grid walks
    compact blocks; the 8 Moore neighbor *blocks* are brought into VMEM
    through scalar-prefetch-dependent BlockSpec index maps (the TPU
    analogue of the paper's per-block shared-memory staging). Read
    amplification ~9x.

  * ``stencil_step_strips``  (v2, beyond-paper): the halo strips (2 rows,
    2 cols incl. corners) are pre-gathered by XLA; the kernel reads
    center + strips only, cutting HBM traffic from ~9 rho^2 to
    ~rho^2 + 4 rho per block.

  * ``stencil_step_fused``   (v3): strip reads fused into the kernel via
    scalar-prefetch index maps — no materialized halo array.

  * ``stencil_step_fused_k`` (v4, temporal fusion): one depth-k halo
    gather, then k update substeps entirely in VMEM before the single
    write-back — per simulated step this divides the dispatch, gather and
    center HBM traffic by ~k at the cost of a (rho+2k)^2 working tile and
    redundant halo-ring compute. The per-block window occupancy needed by
    the substep mask discipline is rebuilt in-kernel from the shared
    periodic ``window_mask`` gated by each block's row of the
    block-existence table (see DESIGN.md Section 2).

  * ``stencil_step_mxu[_k]`` (v5, MXU stencil-as-matmul): the Moore
    aggregation is recast as <= 3 pairs of banded matmul contractions
    ``R_i @ X @ C_i^T`` (rank-1 SVD terms of the 3x3 weight matrix,
    ``workload.weight_factors``): the 3-tap row combination on the VPU,
    then one MXU contraction per term over every row of the program's
    tiles with the banded column operand; P compact blocks are
    lane-packed per macro-tile so the ~128-lane registers are filled
    even at small rho, and
    ``stencil_step_mxu_batched`` adds a native (B, n_macro) batch grid —
    one dispatch for B simulations (see DESIGN.md Section 2.2).

On a TPU every kernel is compiled by Mosaic, so the kernels keep to what
it lowers: halo windows are assembled by concatenation (never by
in-kernel scatters), every program works on a tile of many blocks (v2,
v4) or macro-tiles (v5) so the grid stays short at r >= 17, and the
existence rows arrive as an ordinary VMEM block per program instead of
a whole-table scalar prefetch. v1 and v3 need the whole neighbor table
in SMEM for their data-dependent index maps; past ``SMEM_TABLE_BYTES``
they raise ``KernelCapacityError`` on TPU.

The v2 halo plumbing skips gathers the workload can never read: the
gathered direction set is derived from ``workload.weight(offset)``
(``halo_needs``), so e.g. HeatDiffusion (orthogonal-only) skips all four
corner gathers.

Public state is (nb, rho, rho) for single-channel workloads and
(C, nb, rho, rho) for multi-channel ones (e.g. Gray-Scott); the kernels
always run with an explicit channel axis internally. The ``life_step_*``
wrappers keep the original game-of-life entry points.

``interpret=None`` on every entry point means auto-detect: compiled
Mosaic on TPU, the Pallas interpreter on the CPU (see
``kernels.common.default_interpret``). Tests pass it explicitly to stay
deterministic.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.compact import BlockLayout, window_chunk
from repro.kernels.common import (MXU_PRECISION, KernelCapacityError,
                                  block_tiling, compiler_params,
                                  resolve_interpret, to_dtype, work_dtype)
from repro.workloads.base import StencilWorkload, halo_needs
from repro.workloads.rules import LIFE

#: bytes of SMEM the v1/v3 kernels may fill with the scalar-prefetched
#: (nb, 8) neighbor table; larger layouts raise KernelCapacityError
SMEM_TABLE_BYTES = 256 * 1024


def _with_channels(workload: StencilWorkload, state: jnp.ndarray):
    """Canonicalize to (C, nb, rho, rho); returns (state_c, had_channels)."""
    if workload.n_channels > 1:
        return state, True
    return state[None], False


def _tile_update(workload: StencilWorkload, c, padded, mask, agg_dtype):
    """Run the workload's tile rule on a (C, ..., rho, rho) tile. The
    rule's ``apply`` sees the channel axis only for multi-channel
    workloads."""
    if workload.n_channels > 1:
        return workload.tile_rule(c, padded, mask, agg_dtype=agg_dtype)
    return workload.tile_rule(c[0], padded[0], mask,
                              agg_dtype=agg_dtype)[None]


def _table(layout: BlockLayout, table):
    """The neighbor table operand: explicit (traced) or the layout's
    device copy."""
    if table is not None:
        return table
    layout.materialize()
    return layout.dev_neighbor_table


def _pad_rows(x: jnp.ndarray, n: int, fill, axis: int = 0) -> jnp.ndarray:
    """Extend ``axis`` of ``x`` to length ``n`` with ``fill``."""
    extra = n - x.shape[axis]
    if extra == 0:
        return x
    shape = list(x.shape)
    shape[axis] = extra
    return jnp.concatenate([x, jnp.full(shape, fill, x.dtype)], axis=axis)


def _assemble(c, top, bot, west, east):
    """(..., rho, rho) center + depth-k pieces -> (..., w, w) window:
    top/bot (..., k, w) full-width rows, west/east (..., rho, k)."""
    mid = jnp.concatenate([west, c, east], axis=-1)
    return jnp.concatenate([top, mid, bot], axis=-2)


def _gate(ex: jnp.ndarray, rho: int, k: int) -> jnp.ndarray:
    """(T, 8) existence rows -> (T, w, w) gate: 1 in the center and in
    the halo regions of existing Moore neighbors, 0 in ghost regions."""
    t = ex.shape[0]

    def g(d, h, w):
        return jnp.broadcast_to(ex[:, d:d + 1].reshape(t, 1, 1), (t, h, w))

    ones = jnp.ones((t, rho, rho), ex.dtype)
    # MOORE_DIRS order: NW 0, N 1, NE 2, W 3, E 4, SW 5, S 6, SE 7
    return _assemble(ones,
                     jnp.concatenate([g(0, k, k), g(1, k, rho),
                                      g(2, k, k)], axis=-1),
                     jnp.concatenate([g(5, k, k), g(6, k, rho),
                                      g(7, k, k)], axis=-1),
                     g(3, rho, k), g(4, rho, k))


def _tile_bytes(h: int, w: int) -> int:
    """VMEM bytes of one (h, w) 32-bit plane, padded to (8, 128) tiles."""
    return -(-h // 8) * 8 * (-(-w // 128) * 128) * 4


def _check_smem_table(layout: BlockLayout, interpret: bool, name: str):
    nbytes = layout.n_blocks * 8 * 4
    if not interpret and nbytes > SMEM_TABLE_BYTES:
        raise KernelCapacityError(
            f"{name}: the scalar-prefetched (nb={layout.n_blocks}, 8) "
            f"neighbor table needs {nbytes} B of SMEM, more than the "
            f"{SMEM_TABLE_BYTES} B this kernel may use; use the v4 fused "
            "kernel ('pallas-fused') or the MXU kernel ('pallas-mxu')")


# ======================================================================
# v1: neighbor blocks via scalar-prefetch index maps
# ======================================================================
def _blocks_kernel(workload, tbl_ref, c_ref, nw, n_, ne, w_, e_, sw, s_, se,
                   mask_ref, out_ref):
    del tbl_ref
    wd = work_dtype(workload)

    def ld(ref):
        return ref[:, 0].astype(wd)          # (C, rho, rho)

    c = ld(c_ref)
    top = jnp.concatenate([ld(nw)[..., -1:, -1:], ld(n_)[..., -1:, :],
                           ld(ne)[..., -1:, :1]], axis=-1)
    bot = jnp.concatenate([ld(sw)[..., :1, -1:], ld(s_)[..., :1, :],
                           ld(se)[..., :1, :1]], axis=-1)
    padded = _assemble(c, top, bot, ld(w_)[..., :, -1:], ld(e_)[..., :, :1])
    nxt = _tile_update(workload, c, padded, mask_ref[...], wd)
    out_ref[:, 0] = to_dtype(nxt, out_ref.dtype)


def stencil_step_blocks(layout: BlockLayout, state: jnp.ndarray,
                        workload: StencilWorkload = LIFE, *,
                        interpret: Optional[bool] = None,
                        table=None) -> jnp.ndarray:
    """One workload step; state (C?, n_blocks, rho, rho) -> same."""
    interpret = resolve_interpret(interpret)
    _check_smem_table(layout, interpret, "stencil_step_blocks (v1)")
    return _stencil_step_blocks(layout, state, _table(layout, table),
                                workload, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("layout", "workload", "interpret"))
def _stencil_step_blocks(layout: BlockLayout, state: jnp.ndarray, table,
                         workload: StencilWorkload = LIFE, *,
                         interpret: bool = True) -> jnp.ndarray:
    rho, nb = layout.rho, layout.n_blocks
    s, chan = _with_channels(workload, state)
    nc = s.shape[0]
    padded_src = jnp.concatenate(
        [s, jnp.zeros((nc, 1, rho, rho), s.dtype)], axis=1)

    def center_idx(i, tbl):
        del tbl
        return (0, i, 0, 0)

    def nbr_idx(d):
        def idx(i, tbl):
            return (0, tbl[i, d], 0, 0)
        return idx

    blk = pl.BlockSpec((nc, 1, rho, rho), center_idx)
    in_specs = ([blk] + [pl.BlockSpec((nc, 1, rho, rho), nbr_idx(d))
                         for d in range(8)]
                + [pl.BlockSpec((rho, rho), lambda i, tbl: (0, 0))])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((nc, 1, rho, rho), center_idx),
    )
    out = pl.pallas_call(
        functools.partial(_blocks_kernel, workload),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nc, nb, rho, rho), workload.dtype),
        interpret=interpret,
        compiler_params=compiler_params(interpret),
    )(table, *([padded_src] * 9),
      jnp.asarray(layout.micro_mask.astype(np.int32)))
    return out if chan else out[0]


# ======================================================================
# v2: pre-gathered halo strips (beyond-paper traffic optimization)
# ======================================================================
def _gather_halo_strips(layout: BlockLayout, s: jnp.ndarray,
                        needs=None, table=None) -> jnp.ndarray:
    """(C, nb, 4, rho+2) halo strips via strip-level XLA gathers.

    Only edge rows/cols of the neighbor blocks are touched (~4 rho per block
    instead of 8 rho^2), which is the v2 traffic win. ``needs`` (a
    ``workloads.base.halo_needs`` tuple) drops the gathers the workload's
    zero-weight directions can never read — dead pieces become constant
    zeros instead of table gathers.
    """
    rho = layout.rho
    nc, nb = s.shape[0], layout.n_blocks
    need_n, need_s, need_w, need_e, need_nw, need_ne, need_sw, need_se = \
        needs if needs is not None else (True,) * 8
    table = _table(layout, table)
    z_row = jnp.zeros((nc, 1, rho), s.dtype)
    z_cell = jnp.zeros((nc, 1), s.dtype)
    z_row_nb = jnp.zeros((nc, nb, rho), s.dtype)
    z_cell_nb = jnp.zeros((nc, nb, 1), s.dtype)

    bottom = jnp.concatenate([s[:, :, -1, :], z_row], 1)   # (C, nb+1, rho)
    top = jnp.concatenate([s[:, :, 0, :], z_row], 1)
    east = jnp.concatenate([s[:, :, :, -1], z_row], 1)
    west = jnp.concatenate([s[:, :, :, 0], z_row], 1)
    se_c = jnp.concatenate([s[:, :, -1, -1], z_cell], 1)   # (C, nb+1)
    sw_c = jnp.concatenate([s[:, :, -1, 0], z_cell], 1)
    ne_c = jnp.concatenate([s[:, :, 0, -1], z_cell], 1)
    nw_c = jnp.concatenate([s[:, :, 0, 0], z_cell], 1)

    # MOORE_DIRS order: NW, N, NE, W, E, SW, S, SE
    row_top = jnp.concatenate([
        # my NW corner = NW nbr's SE cell
        se_c[:, table[:, 0], None] if need_nw else z_cell_nb,
        bottom[:, table[:, 1]] if need_n else z_row_nb,  # N nbr's bottom row
        sw_c[:, table[:, 2], None] if need_ne else z_cell_nb,  # NE's SW cell
    ], axis=2)                               # (C, nb, rho+2)
    row_bot = jnp.concatenate([
        ne_c[:, table[:, 5], None] if need_sw else z_cell_nb,  # SW's NE cell
        top[:, table[:, 6]] if need_s else z_row_nb,     # S nbr's top row
        nw_c[:, table[:, 7], None] if need_se else z_cell_nb,  # SE's NW cell
    ], axis=2)
    col_w = jnp.pad(east[:, table[:, 3]] if need_w else z_row_nb,
                    ((0, 0), (0, 0), (0, 2)))    # W nbr's east col
    col_e = jnp.pad(west[:, table[:, 4]] if need_e else z_row_nb,
                    ((0, 0), (0, 0), (0, 2)))    # E nbr's west col
    return jnp.stack([row_top, row_bot, col_w, col_e], axis=2)


def gather_halo_strips(layout: BlockLayout, state: jnp.ndarray) -> jnp.ndarray:
    """Single-channel legacy entry point: (nb, rho, rho) -> (nb, 4, rho+2)."""
    return _gather_halo_strips(layout, state[None])[0]


def _window_kernel(workload, k, gated, *refs):
    """One program = a tile of T blocks: assemble each block's
    (C, rho+2k, rho+2k) window by concatenation, build its occupancy
    (the shared periodic window mask, gated per halo region by the
    block's existence row when ``gated``), then run the workload's k
    fused substeps and write the (C, T, rho, rho) centers back."""
    if gated:
        ex_ref, c_ref, top_ref, bot_ref, west_ref, east_ref, m_ref, \
            out_ref = refs
    else:
        c_ref, top_ref, bot_ref, west_ref, east_ref, m_ref, out_ref = refs
    wd = work_dtype(workload)
    rho = c_ref.shape[-1]
    padded = _assemble(c_ref[...].astype(wd), top_ref[...].astype(wd),
                       bot_ref[...].astype(wd), west_ref[...].astype(wd),
                       east_ref[...].astype(wd))   # (C, T, w, w)
    mask = m_ref[...]
    if gated:
        mask = _gate(ex_ref[...], rho, k) * mask
    if workload.n_channels > 1:
        nxt = workload.tile_rule_k(padded, mask, k, agg_dtype=wd)
    else:
        nxt = workload.tile_rule_k(padded[0], mask, k, agg_dtype=wd)[None]
    out_ref[...] = to_dtype(nxt, out_ref.dtype)


def _window_call(layout: BlockLayout, workload: StencilWorkload, k: int,
                 s, pieces, existence, interpret: bool):
    """Run ``_window_kernel`` over (C, n, rho, rho) blocks ``s`` with
    depth-k halo ``pieces`` (top, bot, west, east; rows padded to the
    same n). ``existence`` (n, 8) int32 gates the window mask (v4); None
    runs one ungated step with the plain micro mask (v2). Returns
    (C, n, rho, rho)."""
    rho = layout.rho
    nc, n = s.shape[0], s.shape[1]
    w = rho + 2 * k
    tb, n_pad = block_tiling(n, nc * _tile_bytes(w, w))
    s = _pad_rows(s, n_pad, 0, axis=1)
    pieces = [_pad_rows(p, n_pad, 0, axis=1) for p in pieces]
    gated = existence is not None
    if gated:
        existence = _pad_rows(existence, n_pad, 0)
    mask = jnp.asarray(layout.window_mask(k).astype(np.int32))

    def blk(h, cols):
        return pl.BlockSpec((nc, tb, h, cols), lambda i: (0, i, 0, 0))

    in_specs = [blk(rho, rho), blk(k, w), blk(k, w), blk(rho, k),
                blk(rho, k), pl.BlockSpec(mask.shape, lambda i: (0, 0))]
    operands = [s, *pieces, mask]
    if gated:
        in_specs.insert(0, pl.BlockSpec((tb, 8), lambda i: (i, 0)))
        operands.insert(0, existence.astype(jnp.int32))
    out = pl.pallas_call(
        functools.partial(_window_kernel, workload, k, gated),
        grid=(n_pad // tb,),
        in_specs=in_specs,
        out_specs=blk(rho, rho),
        out_shape=jax.ShapeDtypeStruct((nc, n_pad, rho, rho),
                                       workload.dtype),
        interpret=interpret,
        compiler_params=compiler_params(interpret),
    )(*operands)
    return out[:, :n]


def stencil_step_strips(layout: BlockLayout, state: jnp.ndarray,
                        workload: StencilWorkload = LIFE, *,
                        interpret: Optional[bool] = None,
                        table=None) -> jnp.ndarray:
    """One workload step, v2 (strip halos); state (C?, n_blocks, rho, rho)."""
    return _stencil_step_strips(layout, state, _table(layout, table),
                                workload,
                                interpret=resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("layout", "workload", "interpret"))
def _stencil_step_strips(layout: BlockLayout, state: jnp.ndarray, table,
                         workload: StencilWorkload = LIFE, *,
                         interpret: bool = True) -> jnp.ndarray:
    rho = layout.rho
    s, chan = _with_channels(workload, state)
    nc = s.shape[0]
    need_n, need_s, need_w, need_e, need_nw, need_ne, need_sw, need_se = \
        halo_needs(workload.weights2d)
    # MOORE_DIRS order: NW, N, NE, W, E, SW, S, SE
    live = (need_nw, need_n, need_ne, need_w, need_e, need_sw, need_s,
            need_se)
    tb, _ = block_tiling(layout.n_blocks, nc * _tile_bytes(rho + 2,
                                                           rho + 2))

    def update(center, nbr, rows):
        def strip(d):  # dead directions read constant zeros, no gather
            return nbr(d) if live[d] else jnp.zeros_like(center)
        return _window_call(layout, workload, 1, center,
                            _halo_pieces(strip, rho, 1), None, interpret)

    out = layout.map_chunks(s, table, window_chunk(rho, 1, nc, tb), update)
    return out if chan else out[0]


# ======================================================================
# v3: strip reads fused into the kernel (scalar-prefetch index maps) —
# no materialized (C, nb, 4, rho+2) halo array
# ======================================================================
def _fused_kernel(workload, tbl_ref, c_ref, top, bot, west, east,
                  c_nw, c_ne, c_sw, c_se, mask_ref, out_ref):
    del tbl_ref
    wd = work_dtype(workload)

    def ld(ref):
        return ref[:, 0].astype(wd)

    c = ld(c_ref)                            # (C, rho, rho)
    # neighbor strips (each ref already indexed at the right block);
    # pieces the workload's zero-weight directions never read are zero
    padded = _assemble(
        c,
        jnp.concatenate([ld(c_nw), ld(bot), ld(c_ne)], axis=-1),
        jnp.concatenate([ld(c_sw), ld(top), ld(c_se)], axis=-1),
        ld(east), ld(west))
    nxt = _tile_update(workload, c, padded, mask_ref[...], wd)
    out_ref[:, 0] = to_dtype(nxt, out_ref.dtype)


def stencil_step_fused(layout: BlockLayout, state: jnp.ndarray,
                       workload: StencilWorkload = LIFE, *,
                       interpret: Optional[bool] = None,
                       table=None) -> jnp.ndarray:
    """v3 entry point (fused strip reads); see ``_stencil_step_fused``."""
    interpret = resolve_interpret(interpret)
    _check_smem_table(layout, interpret, "stencil_step_fused (v3)")
    return _stencil_step_fused(layout, state, _table(layout, table),
                               workload, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("layout", "workload", "interpret"))
def _stencil_step_fused(layout: BlockLayout, state: jnp.ndarray, table,
                        workload: StencilWorkload = LIFE, *,
                        interpret: bool = True) -> jnp.ndarray:
    """One workload step, v3: per-direction strip/corner arrays are built
    with contiguous XLA slices and the kernel reads the neighbor's strip
    directly through a table-dependent BlockSpec — the halo tensor of v2
    is never materialised. Dead directions (zero workload weight) get a
    constant zero operand with a constant index map instead of a
    table-dependent strip read."""
    rho, nb = layout.rho, layout.n_blocks
    s, chan = _with_channels(workload, state)
    nc = s.shape[0]
    need_n, need_s, need_w, need_e, need_nw, need_ne, need_sw, need_se = \
        halo_needs(workload.weights2d)
    z_row = jnp.zeros((nc, 1, 1, rho), s.dtype)
    z_col = jnp.zeros((nc, 1, rho, 1), s.dtype)
    z1 = jnp.zeros((nc, 1, 1, 1), s.dtype)
    ext = functools.partial(_pad_rows, n=nb + 1, fill=0, axis=1)
    top = ext(s[:, :, 0:1, :])           # (C, nb+1, 1, rho)
    bot = ext(s[:, :, -1:, :])
    west = ext(s[:, :, :, 0:1])          # (C, nb+1, rho, 1)
    east = ext(s[:, :, :, -1:])
    c_nw = ext(s[:, :, 0:1, 0:1])        # (C, nb+1, 1, 1)
    c_ne = ext(s[:, :, 0:1, -1:])
    c_sw = ext(s[:, :, -1:, 0:1])
    c_se = ext(s[:, :, -1:, -1:])

    def at(d):
        def idx(i, tbl):
            return (0, tbl[i, d], 0, 0)
        return idx

    def const_idx(i, tbl):
        return (0, 0, 0, 0)

    def piece(arr, zero, d, need):
        """(operand, spec): the neighbor's piece through the table, or
        a constant zero piece when the direction is dead."""
        if need:
            return arr, pl.BlockSpec((nc, 1) + arr.shape[2:], at(d))
        return zero, pl.BlockSpec(zero.shape, const_idx)

    # MOORE_DIRS order: NW, N, NE, W, E, SW, S, SE. Corner args are the
    # DIAGONAL neighbor's opposite corner: e.g. my NW halo cell is the NW
    # neighbor's SE corner, hence c_se @ tbl[:, NW].
    operands_specs = [
        piece(top, z_row, 6, need_s),    # S neighbor's top row
        piece(bot, z_row, 1, need_n),    # N neighbor's bottom row
        piece(west, z_col, 4, need_e),   # E neighbor's west col
        piece(east, z_col, 3, need_w),   # W neighbor's east col
        piece(c_se, z1, 0, need_nw), piece(c_sw, z1, 2, need_ne),
        piece(c_ne, z1, 5, need_sw), piece(c_nw, z1, 7, need_se),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=(
            [pl.BlockSpec((nc, 1, rho, rho), lambda i, tbl: (0, i, 0, 0))]
            + [spec for _, spec in operands_specs]
            + [pl.BlockSpec((rho, rho), lambda i, tbl: (0, 0))]),
        out_specs=pl.BlockSpec((nc, 1, rho, rho), lambda i, tbl: (0, i, 0, 0)),
    )

    out = pl.pallas_call(
        functools.partial(_fused_kernel, workload),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nc, nb, rho, rho), workload.dtype),
        interpret=interpret,
        compiler_params=compiler_params(interpret),
    )(table, s, *[arr for arr, _ in operands_specs],
      jnp.asarray(layout.micro_mask.astype(np.int32)))
    return out if chan else out[0]


# ======================================================================
# v4: temporal fusion — depth-k halo gathered once, k substeps in VMEM
# ======================================================================
def _halo_pieces(nbr, rho: int, k: int):
    """Depth-k halo pieces from the 8 Moore neighbor blocks ``nbr(d)``
    (..., n, rho, rho), ghosts all zero (k <= rho, so every piece comes
    from one Moore neighbor): top/bot (..., n, k, rho+2k) full-width rows
    including the k x k diagonal corners, west/east (..., n, rho, k)
    center columns.

    No zero-weight skipping here: a k>=2 substep chain propagates corner
    values inward even under orthogonal-only weights (the dependency cone
    is the radius-k L1 ball), so every strip is live.
    """
    # MOORE_DIRS order: NW 0, N 1, NE 2, W 3, E 4, SW 5, S 6, SE 7
    top = jnp.concatenate([nbr(0)[..., rho - k:, rho - k:],
                           nbr(1)[..., rho - k:, :],
                           nbr(2)[..., rho - k:, :k]], axis=-1)
    bot = jnp.concatenate([nbr(5)[..., :k, rho - k:],
                           nbr(6)[..., :k, :],
                           nbr(7)[..., :k, :k]], axis=-1)
    return top, bot, nbr(3)[..., :, rho - k:], nbr(4)[..., :, :k]


def _gather_halo_k(layout: BlockLayout, s: jnp.ndarray, k: int,
                   table=None):
    """``_halo_pieces`` of every block of ``s`` (C, nb, rho, rho) through
    the neighbor table (default: the layout's device copy)."""
    table = _table(layout, table)
    nb, rho = layout.n_blocks, layout.rho
    flat = s.reshape(s.shape[:-3] + (nb, rho * rho))
    return _halo_pieces(
        lambda d: layout.block_rows(flat, table[:, d], nb, rho), rho, k)


def _check_depth(layout, k: int, what: str) -> None:
    if k < 1:
        raise ValueError(f"need k >= 1, got k={k}")
    if k > layout.rho:
        raise ValueError(
            f"{what} needs k <= rho, got k={k} > rho={layout.rho} "
            "(use SqueezeBlockEngine.step_k for deeper-than-one-block halos)")


def stencil_step_fused_k(layout: BlockLayout, state: jnp.ndarray,
                         workload: StencilWorkload = LIFE, *, k: int = 2,
                         interpret: Optional[bool] = None,
                         table=None) -> jnp.ndarray:
    """v4: advance ``k`` exact steps in ONE kernel launch.

    The depth-k halo is gathered once; the kernel runs k update substeps
    on a (rho+2k)^2 tile held in VMEM (window shrinking by one ring per
    substep) and writes the center back once — dispatch, table gather and
    center HBM traffic are paid once per k simulated steps. Requires
    k <= rho (deeper halos span multiple block rings; use the engines'
    XLA fallback ``SqueezeBlockEngine.step_k`` beyond that).
    state (C?, n_blocks, rho, rho) -> same, k steps later.
    """
    _check_depth(layout, k, "fused kernel")
    return _stencil_step_fused_k(layout, state, _table(layout, table),
                                 workload, k,
                                 interpret=resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("layout", "workload", "k", "interpret"))
def _stencil_step_fused_k(layout: BlockLayout, state: jnp.ndarray, table,
                          workload: StencilWorkload, k: int, *,
                          interpret: bool) -> jnp.ndarray:
    s, chan = _with_channels(workload, state)
    rho, nc = layout.rho, s.shape[0]
    tb, _ = block_tiling(layout.n_blocks, nc * _tile_bytes(rho + 2 * k,
                                                           rho + 2 * k))

    def update(center, nbr, rows):
        return _window_call(layout, workload, k, center,
                            _halo_pieces(nbr, rho, k),
                            rows != layout.ghost, interpret)

    out = layout.map_chunks(s, table, window_chunk(rho, k, nc, tb), update)
    return out if chan else out[0]


# ======================================================================
# v5: MXU stencil-as-matmul — lane-packed macro-tiles + native batch grid
# ======================================================================
@functools.lru_cache(maxsize=128)
def _mxu_operators(workload: StencilWorkload, w: int, p: int):
    """Static MXU contraction operands for one (workload, window, pack):
    ``R`` (T, w, w) banded row contractions and ``CT`` (T, p*w, p*w), the
    block-diagonal (per lane-packed slot) transpose of the banded column
    contractions, so the kernel's whole Moore aggregation is
    ``sum_t (R[t] @ X) @ CT[t]`` — two MXU matmuls per rank-1 term.
    float32 host build; cached per workload (the factor count T <= 3)."""
    from repro.workloads.base import banded_operators
    rm, cm = banded_operators(workload.weight_factors, w, np.float32)
    t = rm.shape[0]
    ct = np.zeros((t, p * w, p * w), np.float32)
    for i in range(t):
        for s in range(p):
            ct[i, s * w:(s + 1) * w, s * w:(s + 1) * w] = cm[i].T
    return rm, ct


def _shift_rows(x: jnp.ndarray, d: int) -> jnp.ndarray:
    """out[..., y, :] = x[..., y + d, :], zero past the window border."""
    z = jnp.zeros(x.shape[:-2] + (abs(d),) + x.shape[-1:], x.dtype)
    if d > 0:
        return jnp.concatenate([x[..., d:, :], z], axis=-2)
    return jnp.concatenate([z, x[..., :d, :]], axis=-2)


def _mxu_kernel(workload, k, p, ex_ref, c_ref, top_ref, bot_ref, west_ref,
                east_ref, wmask_ref, ct_ref, out_ref):
    """One grid step = (batch element, tile of T macro-tiles): assemble
    the (C, T, w, P*w) lane-packed windows (w = rho+2k, P slots of width
    w) by concatenation, rebuild each slot's occupancy from the shared
    periodic window mask gated by its existence row (the v4 discipline,
    per slot), then run k substeps whose Moore aggregation is, per
    rank-1 term, the 3-tap row combination followed by ONE MXU
    contraction of all T tiles' rows with the block-diagonal banded
    column operand. Rows are padded to a multiple of 8 so the T tiles
    stack into one (T*wr, P*w) matmul operand. Slot borders accumulate
    truncated-band garbage ring by ring (substep j corrupts cells closer
    than j to a slot edge); the center sits at distance >= k, so the
    final (C, T, rho, P*rho) extraction is exact — the same
    shrinking-window argument as v4, without shrinking the arrays."""
    rho = c_ref.shape[-2]
    w = rho + 2 * k
    wr = -(-w // 8) * 8
    f32 = jnp.float32

    def ld(ref):
        return to_dtype(ref[0], f32)         # (C, T, h, cols)

    c, top, bot, west, east = (ld(r) for r in (c_ref, top_ref, bot_ref,
                                                west_ref, east_ref))
    ex = ex_ref[...]                         # (T, 8P) existence rows
    wm = wmask_ref[...]
    wins, masks = [], []
    for s in range(p):
        sw, sk = slice(s * w, (s + 1) * w), slice(s * k, (s + 1) * k)
        wins.append(_assemble(c[..., s * rho:(s + 1) * rho],
                              top[..., sw], bot[..., sw], west[..., sk],
                              east[..., sk]))
        masks.append(_gate(ex[:, 8 * s:8 * (s + 1)], rho, k) * wm)
    cur = jnp.concatenate(wins, axis=-1)     # (C, T, w, P*w)
    mask = jnp.concatenate(masks, axis=-1)   # (T, w, P*w)
    if wr > w:  # zero rows below the window: outside every live region
        cur = jnp.concatenate(
            [cur, jnp.zeros(cur.shape[:-2] + (wr - w, p * w), f32)], -2)
        mask = jnp.concatenate(
            [mask, jnp.zeros(mask.shape[:-2] + (wr - w, p * w),
                             mask.dtype)], -2)
    nc, t = cur.shape[0], cur.shape[1]
    ct = ct_ref[...]                         # (n_terms, P*w, P*w) f32
    int_agg = jnp.issubdtype(jnp.dtype(workload.agg_dtype), jnp.integer)
    for _ in range(k):
        aggs = []
        for ci in range(nc):
            x = cur[ci]                      # (T, wr, P*w)
            up, dn = _shift_rows(x, -1), _shift_rows(x, 1)
            a = None
            for i, (row, _) in enumerate(workload.weight_factors):
                y = row[0] * up + row[1] * x + row[2] * dn
                y = jnp.dot(y.reshape(t * wr, p * w), ct[i],
                            precision=MXU_PRECISION,
                            preferred_element_type=f32)
                a = y if a is None else a + y
            aggs.append(a.reshape(t, wr, p * w))
        agg = jnp.stack(aggs)
        # integer CA aggregates: the f32 products reconstruct integer
        # neighbor counts to ~1e-5, so nearest-int rounding is bit-exact
        if int_agg:
            agg = jnp.rint(agg).astype(jnp.int32)
        if workload.n_channels > 1:
            nxt = workload.apply(cur, agg, mask)
        else:
            nxt = workload.apply(cur[0], agg[0], mask)[None]
        cur = nxt.astype(f32)

    out = jnp.concatenate(
        [cur[..., k:k + rho, s * w + k:s * w + k + rho] for s in range(p)],
        axis=-1)                             # (C, T, rho, P*rho)
    out_ref[0] = to_dtype(out, out_ref.dtype)


def _pack_macro(arr: jnp.ndarray, p: int, n_macro: int):
    """(L, n, h, c) per-block strips -> (L, n_macro, h, P*c) lane-packed
    macro strips (zero-filled padding slots past n)."""
    lead, _, h, cols = arr.shape
    a = _pad_rows(arr, n_macro * p, 0, axis=1)
    a = a.reshape(lead, n_macro, p, h, cols).transpose(0, 1, 3, 2, 4)
    return a.reshape(lead, n_macro, h, p * cols)


def _mxu_call(layout: BlockLayout, workload: StencilWorkload, k: int,
              p: int, s, pieces, existence, interpret: bool):
    """Run ``_mxu_kernel`` over B simulations: ``s`` (B, C, n, rho, rho),
    ``pieces`` the depth-k halo with matching (B, C, n) leading axes,
    ``existence`` (n, 8) {0,1} rows. Returns (B, C, n, rho, rho)."""
    rho = layout.rho
    w = rho + 2 * k
    b, nc, n = s.shape[0], s.shape[1], s.shape[2]
    n_macro = -(-n // p)
    tm, n_macro = block_tiling(n_macro, nc * _tile_bytes(w, p * w))

    def pack(arr):  # (B, C, n, h, cols) -> (B, C, n_macro, h, P*cols)
        flat = arr.reshape((b * nc,) + arr.shape[2:])
        m = _pack_macro(flat, p, n_macro)
        return m.reshape((b, nc) + m.shape[1:])

    ex = _pad_rows(existence.astype(jnp.int32), n_macro * p, 0)
    ex = ex.reshape(n_macro, p * 8)
    _, ct = _mxu_operators(workload, w, p)
    n_terms = ct.shape[0]

    def blk(h, cols):
        return pl.BlockSpec((1, nc, tm, h, cols),
                            lambda bi, i: (bi, 0, i, 0, 0))

    out = pl.pallas_call(
        functools.partial(_mxu_kernel, workload, k, p),
        grid=(b, n_macro // tm),
        in_specs=[
            pl.BlockSpec((tm, p * 8), lambda bi, i: (i, 0)),
            blk(rho, p * rho),
            blk(k, p * w), blk(k, p * w),      # top, bot macro rows
            blk(rho, p * k), blk(rho, p * k),  # west, east macro cols
            pl.BlockSpec((w, w), lambda bi, i: (0, 0)),
            pl.BlockSpec((n_terms, p * w, p * w),
                         lambda bi, i: (0, 0, 0)),
        ],
        out_specs=blk(rho, p * rho),
        out_shape=jax.ShapeDtypeStruct((b, nc, n_macro, rho, p * rho),
                                       workload.dtype),
        interpret=interpret,
        compiler_params=compiler_params(interpret),
    )(ex, pack(s), *(pack(x) for x in pieces),
      jnp.asarray(layout.window_mask(k).astype(np.int32)),
      jnp.asarray(ct))
    out = out.reshape(b, nc, n_macro, rho, p, rho).transpose(0, 1, 2, 4, 3, 5)
    return out.reshape(b, nc, n_macro * p, rho, rho)[:, :, :n]


def stencil_step_mxu_batched(layout: BlockLayout, states: jnp.ndarray,
                             workload: StencilWorkload = LIFE, *, k: int = 1,
                             p: Optional[int] = None,
                             interpret: Optional[bool] = None,
                             table=None) -> jnp.ndarray:
    """v5, native batch grid: advance B independent simulations ``k`` exact
    steps in ONE kernel dispatch over a (B, macro-tile tiles) grid.

    states (B, C?, n_blocks, rho, rho) -> same, k steps later. The halo
    strips are pre-gathered v2-style but emitted macro-tile-contiguous (P
    blocks lane-packed per macro-tile, P*(rho+2k) ~ 128 lanes). Requires
    k <= rho (one block ring, as v4). ``p`` overrides the macro-tile
    packing P (None = the ``macro_tiles`` lane heuristic; the autotuner
    sweeps explicit values).
    """
    _check_depth(layout, k, "mxu kernel")
    # the packing override is resolved to its concrete P here so the jit
    # cache keys on one value (explicit P equal to the lane heuristic's
    # choice shares the compiled kernel)
    p = layout.macro_tiles(k, p=p)[0]
    return _stencil_step_mxu_batched(layout, states, _table(layout, table),
                                     workload, k, p,
                                     interpret=resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("layout", "workload", "k", "p",
                                    "interpret"))
def _stencil_step_mxu_batched(layout: BlockLayout, states: jnp.ndarray,
                              table, workload: StencilWorkload, k: int,
                              p: int, *, interpret: bool) -> jnp.ndarray:
    rho = layout.rho
    chan = workload.n_channels > 1
    s = states if chan else states[:, None]  # (B, C, nb, rho, rho)
    nc = s.shape[1]
    w = rho + 2 * k
    tm, _ = block_tiling(-(-layout.n_blocks // p),
                         nc * _tile_bytes(w, p * w))

    def update(center, nbr, rows):
        return _mxu_call(layout, workload, k, p, center,
                         _halo_pieces(nbr, rho, k), rows != layout.ghost,
                         interpret)

    out = layout.map_chunks(s, table, window_chunk(rho, k, nc, tm * p),
                            update)
    return out if chan else out[:, 0]


def stencil_step_mxu(layout: BlockLayout, state: jnp.ndarray,
                     workload: StencilWorkload = LIFE, *,
                     p: Optional[int] = None,
                     interpret: Optional[bool] = None,
                     table=None) -> jnp.ndarray:
    """One workload step, v5 (MXU stencil-as-matmul on lane-packed
    macro-tiles); state (C?, n_blocks, rho, rho) -> same. ``p``
    overrides the macro-tile packing (None = lane heuristic)."""
    return stencil_step_mxu_batched(layout, state[None], workload, k=1,
                                    p=p, interpret=interpret,
                                    table=table)[0]


def stencil_step_mxu_k(layout: BlockLayout, state: jnp.ndarray,
                       workload: StencilWorkload = LIFE, *, k: int = 2,
                       p: Optional[int] = None,
                       interpret: Optional[bool] = None,
                       table=None) -> jnp.ndarray:
    """v5 temporal fusion: k exact steps in one MXU macro-tile launch,
    reusing the v4 mask discipline (k <= rho). ``p`` overrides the
    macro-tile packing (None = lane heuristic)."""
    return stencil_step_mxu_batched(layout, state[None], workload, k=k,
                                    p=p, interpret=interpret,
                                    table=table)[0]


# ======================================================================
# shard-local entry points — the distributed engine's compute halves.
#
# core/distributed.py exchanges depth-k edge strips and assembles the
# same halo-piece shapes ``_gather_halo_k`` produces; these entries run
# the v4 / v5 kernels on one shard's local blocks given those
# pre-assembled pieces. They are traced inline inside shard_map (no jit
# wrapper here — the enclosing distributed step is the compilation unit).
# ======================================================================
def stencil_step_fused_k_local(layout: BlockLayout, state: jnp.ndarray,
                               halo, existence: jnp.ndarray,
                               workload: StencilWorkload, *, k: int,
                               interpret: Optional[bool] = None
                               ) -> jnp.ndarray:
    """Shard-local v4: ``k`` fused substeps over local blocks.

    state (C, nbl, rho, rho); ``halo`` = (top, bot, west, east) with
    top/bot (C, nbl, k, rho+2k) and west/east (C, nbl, rho, k);
    ``existence`` (nbl, 8) int32 {0,1} Moore-neighbor existence of the
    local blocks (padding blocks: all zero). Returns (C, nbl, rho, rho).
    """
    return _window_call(layout, workload, k, state, halo, existence,
                        resolve_interpret(interpret))


def stencil_step_mxu_k_local(layout: BlockLayout, states: jnp.ndarray,
                             halo, existence: jnp.ndarray,
                             workload: StencilWorkload, *, k: int,
                             p: Optional[int] = None,
                             interpret: Optional[bool] = None
                             ) -> jnp.ndarray:
    """Shard-local v5: ``k`` MXU macro-tile substeps of B simulations over
    local blocks, one (B, macro-tile tiles) grid.

    states (B, C, nbl, rho, rho); ``halo`` pieces carry matching (B, C)
    leading axes; ``existence`` (nbl, 8) as in the v4 local entry. The
    local blocks are lane-packed with ``macro_tiles_for(nbl, k)`` — each
    shard gets its own macro-tile geometry, sharing the kernel body,
    window mask and MXU operators with the single-device v5 path. ``p``
    overrides the per-shard packing (None = lane heuristic).
    """
    p = layout.macro_tiles_for(states.shape[2], k, p=p)[0]
    return _mxu_call(layout, workload, k, p, states, halo, existence,
                     resolve_interpret(interpret))


# ======================================================================
# 3D kernel family — defined in kernels/squeeze_stencil3d.py (the same
# v4/v5 designs over BlockLayout3D), re-exported here so the stencil
# kernel surface stays importable from one module.
# ======================================================================
from repro.kernels.squeeze_stencil3d import (  # noqa: E402,F401
    stencil3d_step_fused_k, stencil3d_step_mxu_k)


# ======================================================================
# legacy game-of-life entry points (kept for the original call sites)
# ======================================================================
def life_step_blocks(layout: BlockLayout, state: jnp.ndarray, *,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """One GoL step; state (n_blocks, rho, rho) uint8 -> same."""
    return stencil_step_blocks(layout, state, LIFE, interpret=interpret)


def life_step_strips(layout: BlockLayout, state: jnp.ndarray, *,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """One GoL step, v2 (strip halos); state (n_blocks, rho, rho) uint8."""
    return stencil_step_strips(layout, state, LIFE, interpret=interpret)


def life_step_fused(layout: BlockLayout, state: jnp.ndarray, *,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """One GoL step, v3 (in-kernel strip reads)."""
    return stencil_step_fused(layout, state, LIFE, interpret=interpret)
