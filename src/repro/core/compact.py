"""Compact-domain layout helpers and block-level Squeeze (paper Section 3.5).

Cell-level: the compact state is a dense ``(rows, cols)`` array,
``rows = k^floor(r/2)``, ``cols = k^ceil(r/2)``; entry ``[cy, cx]`` is the
fractal cell whose compact coordinate is ``(cx, cy)``.

Block-level: with ``rho = s**m`` the fractal is handled as a level-``r_b``
fractal of blocks (``r_b = r - m``); each block stores a rho x rho *expanded*
micro-fractal tile (identical occupancy ``micro_mask`` in every block, by
self-similarity). Block state is ``(n_blocks, rho, rho)`` with block id
``by * cols_b + bx``. Cross-block neighbor access goes through a static
block-neighbor table built with one lambda + 8 nu evaluations per block —
the paper's maps hoisted to block granularity (see DESIGN.md Section 2).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import maps
from repro.core.fractals import NBBFractal
#: Moore neighborhood directions (dx, dy), y growing downward — defined in
#: the dependency-free workloads layer, re-exported here for the engines.
from repro.workloads.base import MOORE_DIRS  # noqa: F401

Array = jnp.ndarray


#: padded bytes of the depth-k windows one chunk of a halo-reading block
#: update may span (``BlockLayout.map_chunks``): a TPU lays small
#: trailing dims out in (8, 128) tiles, so the working set of a chunk is
#: sized in those padded bytes, not in cells
CHUNK_WINDOW_BYTES = 128 * 1024 * 1024


def window_chunk(rho: int, k: int, n_channels: int = 1,
                 multiple: int = 1) -> int:
    """Blocks per ``map_chunks`` chunk for depth-``k`` windows: as many
    as fit ``CHUNK_WINDOW_BYTES`` of (8, 128)-padded 32-bit window
    planes, rounded down to a multiple of ``multiple``."""
    w = rho + 2 * k
    per = n_channels * (-(-w // 8) * 8) * (-(-w // 128) * 128) * 4
    return max(multiple, CHUNK_WINDOW_BYTES // per // multiple * multiple)


def compact_meshgrid(frac: NBBFractal, r: int) -> Tuple[Array, Array]:
    """(cx, cy) int32 arrays of shape (rows, cols) covering D_c^2."""
    rows, cols = frac.compact_dims(r)
    cy, cx = jnp.meshgrid(jnp.arange(rows, dtype=jnp.int32),
                          jnp.arange(cols, dtype=jnp.int32), indexing="ij")
    return cx, cy


def compact_to_expanded(frac: NBBFractal, r: int, state_c: Array) -> Array:
    """Scatter a compact state into its (n, n) expanded embedding (holes 0).

    Trailing two axes are spatial; leading (channel) axes pass through.
    """
    n = frac.side(r)
    cx, cy = compact_meshgrid(frac, r)
    ex, ey = maps.lambda_map(frac, r, cx, cy)
    out = jnp.zeros(state_c.shape[:-2] + (n, n), dtype=state_c.dtype)
    return out.at[..., ey, ex].set(state_c)


def expanded_to_compact(frac: NBBFractal, r: int, state_e: Array) -> Array:
    """Gather an expanded state into compact form (fractal cells only)."""
    cx, cy = compact_meshgrid(frac, r)
    ex, ey = maps.lambda_map(frac, r, cx, cy)
    return state_e[..., ey, ex]


# ======================================================================
# block-level Squeeze
# ======================================================================
@dataclasses.dataclass(frozen=True)
class BlockLayout:
    """Static geometry of a block-level Squeeze decomposition."""

    frac: NBBFractal
    r: int
    m: int  # rho = s**m

    def __post_init__(self):
        if not (0 <= self.m <= self.r):
            raise ValueError(f"need 0 <= m <= r, got m={self.m}, r={self.r}")

    def materialize(self) -> "BlockLayout":
        """Build all static geometry eagerly. Engines call this at
        construction (outside jit): a lazy first touch inside a traced
        step() would try to np.asarray() tracers. Kept out of
        __post_init__ so analytic uses (memory_bytes etc.) stay O(1)."""
        _ = self.micro_mask, self.block_coords
        _ = self.block_origin_expanded, self.neighbor_table
        _ = self.dev_micro_mask, self.dev_block_origin_expanded
        _ = self.dev_neighbor_table
        return self

    @property
    def rho(self) -> int:
        return self.frac.s ** self.m

    @property
    def r_b(self) -> int:
        return self.r - self.m

    @property
    def block_dims(self) -> Tuple[int, int]:
        """(rows_b, cols_b) of the compact block domain."""
        return self.frac.compact_dims(self.r_b)

    @property
    def n_blocks(self) -> int:
        return self.frac.volume(self.r_b)

    @property
    def ghost(self) -> int:
        """Sentinel block id used for out-of-fractal neighbors."""
        return self.n_blocks

    @functools.cached_property
    def micro_mask(self) -> np.ndarray:
        """(rho, rho) uint8 occupancy of the level-m micro-fractal, [y, x]."""
        return self.frac.mask(self.m)

    @functools.cached_property
    def block_coords(self) -> Tuple[np.ndarray, np.ndarray]:
        """Flat (n_blocks,) compact block coordinates (bx, by), id-ordered."""
        rows_b, cols_b = self.block_dims
        by, bx = np.meshgrid(np.arange(rows_b, dtype=np.int32),
                             np.arange(cols_b, dtype=np.int32), indexing="ij")
        return bx.reshape(-1), by.reshape(-1)

    @functools.cached_property
    def block_origin_expanded(self) -> np.ndarray:
        """(n_blocks, 2) int32 cell-level expanded origin (x, y) per block."""
        bx, by = self.block_coords
        ex, ey = maps.lambda_map(self.frac, self.r_b,
                                 jnp.asarray(bx), jnp.asarray(by))
        return np.stack([np.asarray(ex), np.asarray(ey)], axis=1) * self.rho

    def _map_offsets_to_table(self, offsets) -> np.ndarray:
        """(n_blocks, len(offsets)) int32 compact block id per block offset,
        built with the paper's maps at block granularity: one lambda per
        block, one nu per (block, offset); out-of-fractal blocks get the
        ``ghost`` sentinel (a zero block is appended before gathers)."""
        frac, r_b = self.frac, self.r_b
        bx, by = (jnp.asarray(a) for a in self.block_coords)
        ex, ey = maps.lambda_map(frac, r_b, bx, by)
        _, cols_b = self.block_dims
        table = np.empty((self.n_blocks, len(offsets)), dtype=np.int32)
        for d, (dx, dy) in enumerate(offsets):
            nx, ny = ex + dx, ey + dy
            valid = maps.is_fractal(frac, r_b, nx, ny)
            cx, cy = maps.nu_map(frac, r_b,
                                 jnp.clip(nx, 0, frac.side(r_b) - 1),
                                 jnp.clip(ny, 0, frac.side(r_b) - 1))
            ids = jnp.where(valid, cy * cols_b + cx, self.ghost)
            table[:, d] = np.asarray(ids, dtype=np.int32)
        return table

    @functools.cached_property
    def neighbor_table(self) -> np.ndarray:
        """(n_blocks, 8) int32 compact block id per Moore direction."""
        return self._map_offsets_to_table(MOORE_DIRS)

    @functools.cached_property
    def existence_table(self) -> np.ndarray:
        """(n_blocks, 8) int32 {0,1}: 1 where the Moore neighbor block is a
        real fractal block, 0 where ``neighbor_table`` holds the ghost
        sentinel (the distributed engine's per-shard kernel operand; the
        single-device paths derive it from the table on the device)."""
        return (self.neighbor_table != self.ghost).astype(np.int32)

    # --------------------------------------------- device-side cached tables
    # One upload per layout, shared by every kernel variant and every trace:
    # jnp.asarray inside each jitted entry point would re-stage the host
    # table per entry point per trace. Cached in __dict__, so dataclass
    # hashing/equality (fields only) are untouched, and the buffers die
    # with the layout (the runner's LRU can still evict). Builds run under
    # ensure_compile_time_eval so a lazy first touch inside an outer jit
    # trace still caches a *concrete* device array, never a tracer.
    @staticmethod
    def _to_device(host: np.ndarray) -> Array:
        with jax.ensure_compile_time_eval():
            return jax.device_put(host)

    @functools.cached_property
    def dev_neighbor_table(self) -> Array:
        """Device-side ``neighbor_table`` (one shared upload)."""
        return self._to_device(self.neighbor_table)

    @functools.cached_property
    def dev_micro_mask(self) -> Array:
        """Device-side ``micro_mask`` (one shared upload)."""
        return self._to_device(self.micro_mask)

    @functools.cached_property
    def dev_block_origin_expanded(self) -> Array:
        """Device-side ``block_origin_expanded`` (one shared upload)."""
        return self._to_device(self.block_origin_expanded)

    def dev_offset_table(self, k: int) -> Array:
        """Device-side ``offset_table(k)`` (one shared upload per block
        radius; radius 1 is ``dev_neighbor_table`` itself)."""
        if self.halo_block_radius(k) == 1:
            return self.dev_neighbor_table
        return self._memo(("dev_offset_table", self.halo_block_radius(k)),
                          lambda: self._to_device(self.offset_table(k)))

    # ------------------------------------------------------- depth-k halos
    def halo_block_radius(self, k: int) -> int:
        """Neighborhood radius in *blocks* covering a depth-``k`` cell halo
        (1 while k <= rho; grows for deeper fusion than one block ring)."""
        if k < 1:
            raise ValueError(f"halo depth must be >= 1, got {k}")
        return math.ceil(k / self.rho)

    def halo_offsets(self, k: int) -> Tuple[Tuple[int, int], ...]:
        """Block offsets (bdx, bdy) whose tiles intersect the depth-``k``
        halo window, raster-ordered; equals ``MOORE_DIRS`` when k <= rho."""
        kb = self.halo_block_radius(k)
        return tuple((dx, dy)
                     for dy in range(-kb, kb + 1)
                     for dx in range(-kb, kb + 1)
                     if (dx, dy) != (0, 0))

    @functools.cached_property
    def _halo_cache(self) -> dict:
        """Per-instance memo for the depth-k tables/masks. Deliberately not
        ``functools.lru_cache`` on the methods: that would key on ``self``
        in a class-level cache and pin every layout (and its (n_blocks,
        rho+2k, rho+2k) halo masks) process-wide forever — defeating the
        runner's LRU eviction. This dict dies with the layout."""
        return {}

    def _memo(self, key, build):
        cache = self._halo_cache
        if key not in cache:
            cache[key] = build()
        return cache[key]

    def offset_table(self, k: int) -> np.ndarray:
        """(n_blocks, len(halo_offsets(k))) int32 compact block id per
        offset, ghost sentinel for out-of-fractal blocks.

        The generalization of ``neighbor_table`` to arbitrary block
        distance: each entry is one lambda + one nu evaluation *per offset*
        directly against the maps, never a composition of unit-step tables
        — composing through a ghost would mis-drop real blocks that sit
        beyond a hole, so every depth is resolved exactly (out-of-fractal
        reads stay zero at every depth, nothing else does).
        """
        return self._memo(("offset_table", self.halo_block_radius(k)),
                          lambda: self._build_offset_table(k))

    def _build_offset_table(self, k: int) -> np.ndarray:
        if self.halo_block_radius(k) == 1:
            return self.neighbor_table  # identical construction + ordering
        return self._map_offsets_to_table(self.halo_offsets(k))

    def _halo_region(self, k: int, bdx: int, bdy: int):
        """Static window/source slices for one block offset: the overlap of
        the neighbor tile at (bdx, bdy) with the (rho+2k)^2 halo window.
        Returns ((dy0, dy1, dx0, dx1) in the window,
                 (sy0, sy1, sx0, sx1) in the neighbor tile)."""
        rho = self.rho
        w = rho + 2 * k
        x0, y0 = k + bdx * rho, k + bdy * rho
        dx0, dx1 = max(x0, 0), min(x0 + rho, w)
        dy0, dy1 = max(y0, 0), min(y0 + rho, w)
        return (dy0, dy1, dx0, dx1), (dy0 - y0, dy1 - y0, dx0 - x0, dx1 - x0)

    def window_mask(self, k: int) -> np.ndarray:
        """(rho+2k, rho+2k) uint8: periodic extension of ``micro_mask`` over
        the depth-``k`` window. By self-similarity every *existing* neighbor
        block carries exactly ``micro_mask``, so this is the halo occupancy
        before ghost gating."""
        def build():
            idx = np.arange(-k, self.rho + k) % self.rho
            return self.micro_mask[np.ix_(idx, idx)]
        return self._memo(("window_mask", k), build)

    def halo_mask(self, k: int) -> np.ndarray:
        """(n_blocks, rho+2k, rho+2k) uint8 occupancy of each block's
        depth-``k`` window: the periodic ``window_mask`` with the regions of
        out-of-fractal (ghost) neighbor blocks zeroed per block. Fused-k
        substeps multiply by a shrinking crop of this mask so hole *and*
        ghost cells stay zero at every substep (the k-substep mask
        discipline; see DESIGN.md Section 2)."""
        return self._memo(("halo_mask", k), lambda: self._build_halo_mask(k))

    def _build_halo_mask(self, k: int) -> np.ndarray:
        w = self.rho + 2 * k
        table = self.offset_table(k)
        full = np.broadcast_to(self.window_mask(k),
                               (self.n_blocks, w, w)).copy()
        for oi, (bdx, bdy) in enumerate(self.halo_offsets(k)):
            (dy0, dy1, dx0, dx1), _ = self._halo_region(k, bdx, bdy)
            full[table[:, oi] == self.ghost, dy0:dy1, dx0:dx1] = 0
        return full

    # -------------------------------------------- macro-tile strip geometry
    def macro_tiles(self, k: int, lanes: int = 128,
                    p: Optional[int] = None) -> Tuple[int, int, int]:
        """Lane-packing geometry of the v5 MXU kernel: ``(P, n_macro,
        nb_pad)`` where ``P`` compact blocks (each a depth-``k`` padded
        ``(rho+2k)``-wide slot) are packed side by side along the minor
        (lane) axis of one macro-tile, chosen so ``P * (rho+2k)`` fills
        the ``lanes``-wide vector registers, and ``n_macro = ceil(n_blocks
        / P)`` macro-tiles cover the compact block domain. After the
        ceiling split, ``P`` is rebalanced down to ``ceil(n_blocks /
        n_macro)`` so padding slots (dead lanes) are minimized. ``nb_pad =
        n_macro * P >= n_blocks``; slots past ``n_blocks`` are zero-filled
        ghosts whose outputs are sliced off.

        ``p`` overrides the lane heuristic with an explicit packing (the
        autotuner sweeps it; clamped to [1, n_blocks], no rebalance — the
        caller asked for exactly this packing)."""
        return self.macro_tiles_for(self.n_blocks, k, lanes, p)

    def macro_tiles_for(self, nb: int, k: int, lanes: int = 128,
                        p: Optional[int] = None) -> Tuple[int, int, int]:
        """``macro_tiles`` for an arbitrary block count ``nb`` — the
        distributed engine packs each shard's *local* blocks (nb_padded /
        n_shards of them) into their own macro-tiles, so the lane-packing
        geometry must be computable per shard, not only for the full
        compact domain. ``p`` overrides the lane heuristic (see
        ``macro_tiles``)."""
        if k < 1:
            raise ValueError(f"halo depth must be >= 1, got {k}")
        if p is not None:
            if p < 1:
                raise ValueError(f"macro-tile packing must be >= 1, "
                                 f"got {p}")
            p = min(p, nb)
            n_macro = -(-nb // p)
            return p, n_macro, n_macro * p
        w = self.rho + 2 * k
        p = max(1, min(lanes // w, nb))
        n_macro = -(-nb // p)
        p = -(-nb // n_macro)  # rebalance: same tile count, fewer dead slots
        return p, n_macro, n_macro * p

    # ------------------------------------------- depth-k exchange strips
    # The distributed halo exchange (core/distributed.py) ships *edge
    # bands*, never whole blocks: per block the top/bottom k rows and the
    # west/east k columns (transposed so all four stack to (4, k, rho)).
    # Corner k x k pieces are sub-slices of the top/bottom bands, so the
    # bands alone reconstruct a full depth-k Moore halo. Valid for
    # k <= rho (one block ring — the same bound as the fused kernels);
    # the consuming table is ``offset_table(k)``, whose radius-1 case is
    # exactly ``neighbor_table`` (ghosts exact past holes at every depth).
    def pack_edge_strips(self, state: Array, k: int) -> Array:
        """(L, nb, rho, rho) -> (L, nb, 4, k, rho) edge bands:
        row 0 = top k rows, row 1 = bottom k rows, row 2 = west k cols
        (transposed), row 3 = east k cols (transposed)."""
        rho = self.rho
        if not (1 <= k <= rho):
            raise ValueError(f"need 1 <= k <= rho={rho}, got k={k}")
        top = state[:, :, :k, :]
        bot = state[:, :, rho - k:, :]
        west = state[:, :, :, :k].swapaxes(-1, -2)
        east = state[:, :, :, rho - k:].swapaxes(-1, -2)
        return jnp.stack([top, bot, west, east], axis=2)

    def halo_from_strips_k(self, strips: Array, table: Array, k: int):
        """Assemble depth-``k`` halo pieces from packed edge strips.

        ``strips``: (L, ns, 4, k, rho) — ``pack_edge_strips`` output over
        any superset of blocks (ns >= nb; the distributed engine appends a
        zero ghost entry at ns-1). ``table``: (nb_sel, 8) row indices into
        ``strips`` per Moore direction, ghosts already remapped to the
        zero entry. Returns ``(top, bot, west, east)`` shaped exactly like
        the fused kernels' ``_gather_halo_k`` output — top/bot
        (L, nb_sel, k, rho+2k) full-width rows including the diagonal
        k x k corners, west/east (L, nb_sel, rho, k) center columns — so
        every depth-k consumer (XLA window assembly, v4/v5 kernels) is
        shared between the single-device and distributed paths."""
        rho = self.rho

        def band(d, row):  # (L, nb_sel, k, rho)
            return strips[:, table[:, d], row]

        # MOORE_DIRS order: NW 0, N 1, NE 2, W 3, E 4, SW 5, S 6, SE 7
        top = jnp.concatenate(
            [band(0, 1)[..., rho - k:], band(1, 1),
             band(2, 1)[..., :k]], axis=-1)
        bot = jnp.concatenate(
            [band(5, 0)[..., rho - k:], band(6, 0),
             band(7, 0)[..., :k]], axis=-1)
        west = band(3, 3).swapaxes(-1, -2)   # W neighbor's east cols
        east = band(4, 2).swapaxes(-1, -2)   # E neighbor's west cols
        return top, bot, west, east

    # ------------------------------------------ locality-aware sharding
    def strip_decomposition(self, n_shards: int) -> "StripDecomposition":
        """Locality-aware block->shard assignment for the neighbor-only
        point-to-point halo exchange (one shared build per shard count;
        see :class:`StripDecomposition`)."""
        return self._memo(("strip_decomposition", n_shards),
                          lambda: StripDecomposition(self, n_shards))

    # ------------------------------------------------------------ conversions
    def to_expanded(self, state_b: Array) -> Array:
        """Block state (C?, n_blocks, rho, rho) -> (C?, n, n) expanded
        embedding (leading channel axes pass through)."""
        n = self.frac.side(self.r)
        org = self.dev_block_origin_expanded  # (n_blocks, 2)
        rho = self.rho
        iy, ix = jnp.meshgrid(jnp.arange(rho), jnp.arange(rho), indexing="ij")
        # absolute cell coords per (block, i, j)
        ax = org[:, 0, None, None] + ix[None]
        ay = org[:, 1, None, None] + iy[None]
        out = jnp.zeros(state_b.shape[:-3] + (n, n), dtype=state_b.dtype)
        return out.at[..., ay, ax].set(state_b)

    def from_expanded(self, state_e: Array) -> Array:
        """(C?, n, n) expanded embedding -> block state (C?, n_blocks,
        rho, rho)."""
        org = self.dev_block_origin_expanded
        rho = self.rho
        iy, ix = jnp.meshgrid(jnp.arange(rho), jnp.arange(rho), indexing="ij")
        ax = org[:, 0, None, None] + ix[None]
        ay = org[:, 1, None, None] + iy[None]
        mask = self.dev_micro_mask
        return state_e[..., ay, ax] * mask.astype(state_e.dtype)

    def pad_with_halo(self, state_b: Array, table=None) -> Array:
        """Assemble (n_blocks, rho+2, rho+2) tiles with Moore halos (the
        depth-1 ``pad_with_halo_k``); ghost neighbors read as 0."""
        return self.pad_with_halo_k(state_b, 1, table)

    def _window_grid(self, k: int, piece):
        """Assemble a (..., rho+2k, rho+2k) window by concatenation from
        ``piece(oi, window_rect, source_rect)`` — one call per block
        offset of the depth-``k`` halo (``oi`` None for the center
        tile). The offsets' overlaps tile the window in a (2kb+1)^2
        grid, row band by row band."""
        kb = self.halo_block_radius(k)
        col = {o: i for i, o in enumerate(self.halo_offsets(k))}
        rows = []
        for bdy in range(-kb, kb + 1):
            rows.append(jnp.concatenate(
                [piece(col.get((bdx, bdy)),
                       *self._halo_region(k, bdx, bdy))
                 for bdx in range(-kb, kb + 1)], axis=-1))
        return jnp.concatenate(rows, axis=-2)

    def halo_window_k(self, center: Array, nbr, k: int) -> Array:
        """(..., n, rho+2k, rho+2k) depth-``k`` windows of ``center``
        (..., n, rho, rho): ``nbr(oi)`` gives the (..., n, rho, rho)
        neighbor blocks at ``halo_offsets(k)[oi]`` (ghosts all zero);
        only each neighbor's overlap with the window is kept."""
        def piece(oi, dst, src):
            del dst
            sy0, sy1, sx0, sx1 = src
            blk = center if oi is None else nbr(oi)
            return blk[..., sy0:sy1, sx0:sx1]

        return self._window_grid(k, piece)

    @staticmethod
    def block_rows(flat: Array, idx: Array, n_blocks: int,
                   rho: int) -> Array:
        """Gather blocks ``idx`` (n,) from ``flat`` (..., n_blocks,
        rho*rho), the lane-dense view of a block state: ids past the
        layout (the ghost sentinel, padding rows) read as zero blocks.
        Returns (..., n, rho, rho)."""
        rows = jnp.take(flat, jnp.minimum(idx, n_blocks - 1), axis=-2)
        rows = rows * (idx < n_blocks)[:, None].astype(rows.dtype)
        return rows.reshape(rows.shape[:-1] + (rho, rho))

    def map_chunks(self, state: Array, table: Array, chunk: int, fn):
        """Apply a halo-reading block update chunk by chunk.

        ``state`` (..., n_blocks, rho, rho); ``table`` (n_blocks, T)
        neighbor ids per block (ghost = ``self.ghost``); ``fn(center,
        nbr, rows)`` maps one chunk — ``center`` (..., c, rho, rho),
        ``nbr(j)`` the blocks named by column ``j`` of the chunk's table
        rows ``rows`` (c, T) — to its (..., c, rho, rho) update.

        The chunks run in a ``fori_loop`` over a lane-dense (...,
        n_blocks, rho*rho) view, so every temporary with small trailing
        dims (halo strips, windows, which a TPU pads to (8, 128) tiles)
        is chunk-sized: the working set stays bounded however many
        blocks the layout has. One chunk when ``chunk >= n_blocks``."""
        nb, rho = self.n_blocks, self.rho
        lead = state.shape[:-3]
        flat = state.reshape(lead + (nb, rho * rho))
        if chunk >= nb:
            return fn(state, lambda j: self.block_rows(
                flat, table[:, j], nb, rho), table)
        n_chunks = -(-nb // chunk)
        table = jnp.concatenate(
            [table, jnp.full((n_chunks * chunk - nb, table.shape[1]),
                             self.ghost, table.dtype)], axis=0)

        def body(c, out):
            base = c * chunk
            rows = jax.lax.dynamic_slice_in_dim(table, base, chunk, 0)
            ids = base + jnp.arange(chunk, dtype=table.dtype)
            res = fn(self.block_rows(flat, ids, nb, rho),
                     lambda j: self.block_rows(flat, rows[:, j], nb, rho),
                     rows)
            return jax.lax.dynamic_update_slice_in_dim(
                out, res.reshape(lead + (chunk, rho * rho)), base,
                axis=out.ndim - 2)

        out = jnp.zeros(lead + (n_chunks * chunk, rho * rho), state.dtype)
        out = jax.lax.fori_loop(0, n_chunks, body, out)
        return out[..., :nb, :].reshape(state.shape)

    def pad_with_halo_k(self, state_b: Array, k: int, table=None) -> Array:
        """Assemble (n_blocks, rho+2k, rho+2k) tiles with depth-``k`` halos.

        For each block offset in ``halo_offsets(k)`` the neighbor tile
        is gathered through the offset table and its overlap with the
        window kept; ghost ids read as zero, which keeps out-of-fractal
        reads zero at every depth. ``table`` is ``offset_table(k)`` as a
        traced operand (default: the layout's device copy).
        """
        if k < 1:
            raise ValueError(f"halo depth must be >= 1, got {k}")
        if table is None:
            table = self.dev_offset_table(k)
        nb, rho = self.n_blocks, self.rho
        flat = state_b.reshape(state_b.shape[:-3] + (nb, rho * rho))
        return self.halo_window_k(
            state_b, lambda oi: self.block_rows(flat, table[:, oi], nb, rho),
            k)

    def halo_gate(self, k: int, table) -> Array:
        """(n, rho+2k, rho+2k) uint8 occupancy of each block's depth-``k``
        window — ``halo_mask(k)`` rebuilt in-trace from the offset table
        (``table`` (n, len(halo_offsets(k))), ghost = ``self.ghost``):
        the periodic ``window_mask`` with each ghost neighbor's region
        zeroed. Built on the device, so no (n_blocks, w, w) table is
        ever uploaded or baked into a compiled program."""
        exist = (table != self.ghost).astype(jnp.uint8)
        n = table.shape[0]

        def piece(oi, dst, src):
            dy0, dy1, dx0, dx1 = dst
            shape = (n, dy1 - dy0, dx1 - dx0)
            if oi is None:
                return jnp.ones(shape, jnp.uint8)
            return jnp.broadcast_to(exist[:, oi, None, None], shape)

        return self._window_grid(k, piece) * jnp.asarray(self.window_mask(k))

    def init_state(self, workload, seed) -> Array:
        """Seeded initial block state (C?, n_blocks, rho, rho), drawn on
        the device in block space from ``workloads.base.cell_bits`` at
        every cell's expanded coordinates — the same field the expanded
        baselines draw over the (n, n) embedding, with holes zeroed."""
        return _init_blocks(self, workload, jnp.asarray(seed, jnp.int32),
                            self.dev_block_origin_expanded)

    def memory_bytes(self, dtype_size: int = 1) -> int:
        """Squeeze block-level state bytes (paper Table 2's nu column)."""
        return self.n_blocks * self.rho * self.rho * dtype_size


@functools.partial(jax.jit, static_argnums=(0, 1))
def _init_blocks(layout: "BlockLayout", workload, seed, org) -> Array:
    from repro.workloads.base import cell_bits
    rho = layout.rho
    iy, ix = jnp.meshgrid(jnp.arange(rho, dtype=jnp.int32),
                          jnp.arange(rho, dtype=jnp.int32), indexing="ij")
    bits = cell_bits(seed, org[:, 0, None, None] + ix[None],
                     org[:, 1, None, None] + iy[None])
    field = workload.init(bits)
    return field * jnp.asarray(layout.micro_mask).astype(field.dtype)


def _balanced_contiguous_partition(counts: np.ndarray,
                                   n_groups: int) -> list:
    """Split ``counts`` into ``n_groups`` CONTIGUOUS NONEMPTY groups
    minimizing the maximum group sum (binary search on the capacity +
    greedy feasibility; len(counts) >= n_groups required). Returns the
    half-open index ranges [(a0, b0), ...]."""
    n = len(counts)
    if n < n_groups:
        raise ValueError(f"cannot split {n} rows into {n_groups} "
                         "nonempty groups")

    def bounds_for(cap):
        """Greedy fill under ``cap``, always leaving enough rows for the
        remaining groups to stay nonempty; None when infeasible."""
        out, start, acc = [], 0, 0
        g = n_groups
        for i, c in enumerate(counts):
            must_cut = (n - i) == (g - 1)  # later groups need the rest
            if i > start and (acc + c > cap or must_cut):
                out.append((start, i))
                start, acc, g = i, 0, g - 1
            acc += c
            if acc > cap and i > start:
                return None
        out.append((start, n))
        return out if len(out) == n_groups and acc <= cap else None

    lo, hi = int(counts.max()), int(counts.sum())
    while lo < hi:
        mid = (lo + hi) // 2
        if bounds_for(mid) is None:
            lo = mid + 1
        else:
            hi = mid
    bounds = bounds_for(lo)
    assert bounds is not None
    return bounds


@dataclasses.dataclass(frozen=True)
class StripDecomposition:
    """Locality-aware block->shard assignment: expanded-space row strips.

    Sharding the compact block domain in compact (digit-interleaved)
    id order scatters spatially adjacent blocks across shards, which is
    why the all-gather exchange was needed. This decomposition instead
    orders blocks by their EXPANDED-space block row (``ey`` of
    ``block_origin_expanded`` — one lambda evaluation per block, holes
    handled exactly because only occupied rows exist) and assigns each
    shard a contiguous strip of whole rows, balanced by block count
    (``_balanced_contiguous_partition``). Rows are never split, so a
    block's Moore neighbors (expanded rows ``ey`` +- 1) always live on
    the SAME shard or one of its two strip neighbors — the static
    guarantee that makes a neighbor-only ``ppermute`` exchange exact.

    ``valid`` is False when the mesh degenerates (fewer occupied rows
    than shards: some shard would own no row and the +-1-shard guarantee
    breaks) — the distributed engine then falls back to the all-gather
    exchange.

    Native (engine) state layout: shard ``s`` owns native rows
    ``[s*nb_local, (s+1)*nb_local)``; within a shard, real blocks come
    first (row-major expanded order), then dead padding slots up to
    ``nb_local`` (the max strip load). ``perm[i]`` is the compact block
    id of native row ``i`` (-1 for dead slots).

    Routing tables (all static, built once per (layout, n_shards)):

    * ``send_prev_idx`` / ``send_next_idx`` — (n_shards, ms_prev/next)
      local indices of the blocks whose edge bands the prev/next strip
      neighbor actually needs (padded with the ``nb_local`` zero-strip
      sentinel; clamped to >= 1 slot so the ppermute operands are never
      zero-sized);
    * ``table`` — (n_shards, nb_local, 8) per-shard Moore halo table in
      COMBINED strip coordinates: [0, nbl) local strips, nbl the zero
      ghost row, [nbl+1, nbl+1+ms_next) strips received from the prev
      neighbor (its send_next buffer), then ms_prev slots received
      from the next neighbor (its send_prev buffer);
    * ``interior_idx`` / ``boundary_idx`` — (n_shards, max_interior/
      boundary) local indices partitioning each shard's slots into
      blocks whose depth-k halo is fully shard-local (interior: compute
      overlaps the in-flight exchange) and blocks that must wait for a
      neighbor strip (boundary), padded with the same sentinel.
    """

    layout: BlockLayout
    n_shards: int

    def __post_init__(self):
        if self.n_shards < 1:
            raise ValueError(f"need n_shards >= 1, got {self.n_shards}")
        self._build()

    def _set(self, **kw):
        for name, val in kw.items():
            object.__setattr__(self, name, val)

    # ------------------------------------------------------------- build
    def _build(self) -> None:
        layout = self.layout
        nb, rho = layout.n_blocks, layout.rho
        org = layout.block_origin_expanded
        ex, ey = org[:, 0] // rho, org[:, 1] // rho
        order = np.lexsort((ex, ey)).astype(np.int32)
        rows, counts = np.unique(ey, return_counts=True)
        if len(rows) < self.n_shards:
            self._set(valid=False, nb_local=0, nb_padded=0, perm=None,
                      shard_of=None, local_of=None)
            return
        bounds = _balanced_contiguous_partition(counts, self.n_shards)
        # vectorized over blocks: the builds stay O(nb log nb) numpy at
        # paper scale (nb = 3^13 at Sierpinski r=17, m=4)
        row_shard = np.empty(len(rows), np.int32)
        for sh, (a, b) in enumerate(bounds):
            row_shard[a:b] = sh
        shard_of = row_shard[np.searchsorted(rows, ey)].astype(np.int32)
        nbl = int(max(counts[a:b].sum() for a, b in bounds))
        # row-major within each strip: rank of each block in its shard
        ordered_shards = shard_of[order]
        starts = np.searchsorted(ordered_shards,
                                 np.arange(self.n_shards))
        local_of = np.empty(nb, np.int32)
        local_of[order] = (np.arange(nb) - starts[ordered_shards]
                           ).astype(np.int32)
        perm = np.full(self.n_shards * nbl, -1, np.int32)
        perm[shard_of * nbl + local_of] = np.arange(nb, dtype=np.int32)
        self._set(valid=True, nb_local=nbl,
                  nb_padded=self.n_shards * nbl, perm=perm,
                  shard_of=shard_of, local_of=local_of)
        self._build_routing()

    def _build_routing(self) -> None:
        layout, nbl, ns = self.layout, self.nb_local, self.n_shards
        table_g = layout.neighbor_table  # (nb, 8) compact block ids
        ghost = layout.ghost
        shard_of, local_of = self.shard_of, self.local_of
        g, d = np.nonzero(table_g != ghost)  # every real (block, dir)
        ng = table_g[g, d]
        s, so = shard_of[g], shard_of[ng]
        delta = so - s
        bad = np.flatnonzero(np.abs(delta) > 1)
        if bad.size:  # the row-strip invariant
            i = bad[0]
            raise AssertionError(
                f"strip decomposition broke: blocks {g[i]}->{ng[i]} "
                f"span shards {s[i]}->{so[i]}")
        # ng's strip travels from its shard so back to s, i.e. shard s+1
        # sends to its PREV neighbor and shard s-1 to its NEXT one
        lo = local_of[ng]

        def needed(sign):
            sel = delta == sign
            return [np.unique(lo[sel & (so == sh)]) for sh in range(ns)]

        send_prev, send_next = needed(1), needed(-1)
        # >= 1 slot: the ppermute operands must never be zero-sized
        ms_prev = max(1, max(len(x) for x in send_prev))
        ms_next = max(1, max(len(x) for x in send_next))

        def pad(lists, width):
            out = np.full((ns, width), nbl, np.int32)  # zero-strip row
            for sh, lst in enumerate(lists):
                out[sh, :len(lst)] = lst
            return out

        def slot(lists, shards, ids):
            """Position of each local id in its shard's send list."""
            out = np.zeros(len(ids), np.int64)
            for sh in range(ns):
                sel = shards == sh
                out[sel] = np.searchsorted(lists[sh], ids[sel])
            return out

        # per-shard halo table in combined strip coordinates
        table = np.full((ns, nbl, 8), nbl, np.int32)
        li = local_of[g]
        same, from_prev = delta == 0, delta == -1
        from_next = delta == 1
        table[s[same], li[same], d[same]] = lo[same]
        # recv-from-prev slab = prev shard's send_next buffer (width
        # ms_next); recv-from-next slab = next shard's send_prev buffer
        table[s[from_prev], li[from_prev], d[from_prev]] = (
            nbl + 1 + slot(send_next, so[from_prev], lo[from_prev]))
        table[s[from_next], li[from_next], d[from_next]] = (
            nbl + 1 + ms_next + slot(send_prev, so[from_next],
                                     lo[from_next]))
        remote = np.zeros((ns, nbl), bool)
        remote[s[~same], li[~same]] = True

        # interior/boundary partition of every local slot (dead padding
        # slots are interior: they compute to zero without any strip)
        interior = [np.flatnonzero(~remote[sh]) for sh in range(ns)]
        boundary = [np.flatnonzero(remote[sh]) for sh in range(ns)]
        mi = max(1, max(len(x) for x in interior))
        mb = max(1, max(len(x) for x in boundary))
        self._set(
            ms_prev=ms_prev, ms_next=ms_next,
            send_prev_idx=pad(send_prev, ms_prev),
            send_next_idx=pad(send_next, ms_next),
            table=table,
            interior_idx=pad(interior, mi),
            boundary_idx=pad(boundary, mb),
            n_interior=np.array([len(x) for x in interior], np.int32),
            n_boundary=np.array([len(x) for x in boundary], np.int32),
            real_sends=sum(1 for sh in range(ns - 1)
                           if len(send_next[sh])) +
            sum(1 for sh in range(1, ns) if len(send_prev[sh])),
        )

    # ------------------------------------------------------- exchange ops
    def pack_edge_strips_for(self, strips_z: Array, neighbor: str,
                             shard: int = 0) -> Array:
        """Gather the send buffer for one strip neighbor out of this
        shard's zero-row-appended local strips (``strips_z``:
        (L, nb_local+1, 4, k, rho)). ``neighbor``: 'prev' | 'next'.
        Inside shard_map the per-shard routing row arrives as a sharded
        operand; this host-facing form (used by the tests' exchange
        simulation) selects it by ``shard``."""
        idx = (self.send_prev_idx if neighbor == "prev"
               else self.send_next_idx)[shard]
        return strips_z[:, idx]

    def halo_from_neighbor_strips_k(self, combined: Array, table: Array,
                                    k: int):
        """Assemble depth-``k`` halo pieces from the COMBINED per-shard
        strip array (local strips + zero row + received neighbor slabs,
        in the ``table`` coordinate convention) — the neighbor-routed
        counterpart of :meth:`BlockLayout.halo_from_strips_k`, sharing
        its band layout with every depth-k consumer."""
        return self.layout.halo_from_strips_k(combined, table, k)

    # ------------------------------------------------------- accounting
    def slot_bytes(self, k: int, itemsize: int) -> int:
        """Bytes of one strip slot (all four depth-``k`` edge bands of
        one block): 4 * k * rho cells."""
        return 4 * k * self.layout.rho * itemsize

    def wire_bytes_per_exchange(self, k: int, itemsize: int,
                                batch: int = 1) -> int:
        """Total bytes moved over the interconnect by one depth-``k``
        p2p exchange: both ppermutes ship their (clamped) buffers
        between every adjacent shard pair."""
        slots = (self.ms_prev + self.ms_next) * (self.n_shards - 1)
        return batch * slots * self.slot_bytes(k, itemsize)

    def wire_bytes_per_device_per_exchange(self, k: int, itemsize: int,
                                           batch: int = 1) -> int:
        """Bytes RECEIVED by one (interior) shard per exchange — the
        per-device wire pressure, independent of the shard count (the
        flat curve the scaling gate asserts)."""
        return (batch * (self.ms_prev + self.ms_next)
                * self.slot_bytes(k, itemsize))
