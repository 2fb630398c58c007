"""Block-level Squeeze in three dimensions: the 2D ``BlockLayout``
machinery (core/compact.py) ported to 3D NBB fractals over the
lambda3/nu3 maps — the geometry half of completing the paper's §5
"extend to 3D" claim at full performance.

With ``rho = s**m`` the 3D fractal is handled as a level-``r_b`` fractal
of blocks (``r_b = r - m``); each block stores a rho^3 *expanded*
micro-fractal cube (identical occupancy ``micro_mask`` in every block,
by self-similarity). Block state is ``(n_blocks, rho, rho, rho)``
indexed ``[b, z, y, x]`` with block id ``(bz * ny + by) * nx + bx`` over
the compact block box ``(nx, ny, nz) = compact_dims(r_b)``. Cross-block
neighbor access goes through static tables built with one lambda3 per
block and one nu3 per (block, offset) — the paper's maps hoisted to
block granularity, exactly as in 2D (DESIGN.md Sections 2 and 5).

Depth-``k`` halo geometry (offset tables exact past holes, periodic
window masks, per-block halo masks, ``pad_with_halo_k``) mirrors the 2D
layout method-for-method so the fused engines and kernels can share one
substep discipline across dimensions.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fractals3d as f3
#: 26-direction 3D Moore neighborhood, raster-ordered — defined in the
#: dependency-free workloads layer, re-exported here for the engines.
from repro.workloads.base import MOORE3_DIRS  # noqa: F401

Array = jnp.ndarray


def halo_regions3(rho: int, k: int):
    """The 26 (zs, ys, xs) window slices of the depth-k halo frame, in
    MOORE3_DIRS order. Shared by the fused 3D kernels to gate the
    periodic window mask by per-block neighbor existence."""
    w = rho + 2 * k
    sl = {-1: slice(0, k), 0: slice(k, k + rho), 1: slice(k + rho, w)}
    return tuple((sl[dz], sl[dy], sl[dx]) for (dx, dy, dz) in MOORE3_DIRS)


@dataclasses.dataclass(frozen=True)
class BlockLayout3D:
    """Static geometry of a 3D block-level Squeeze decomposition."""

    frac: f3.NBBFractal3D
    r: int
    m: int  # rho = s**m

    def __post_init__(self):
        if not (0 <= self.m <= self.r):
            raise ValueError(f"need 0 <= m <= r, got m={self.m}, r={self.r}")

    def materialize(self) -> "BlockLayout3D":
        """Build all static geometry eagerly (same contract as the 2D
        layout: engines call this at construction, outside any trace)."""
        _ = self.micro_mask, self.block_coords
        _ = self.block_origin_expanded, self.neighbor_table
        _ = self.dev_micro_mask, self.dev_block_origin_expanded
        _ = self.dev_neighbor_table
        return self

    def materialize_halo(self, k: int) -> "BlockLayout3D":
        """Build the depth-``k`` halo geometry eagerly (fused-k entry
        points call this outside any trace)."""
        self.materialize()
        _ = self.existence_table, self.dev_existence_table
        _ = self.offset_table(k), self.window_mask(k), self.halo_mask(k)
        _ = self.dev_offset_table(k), self.dev_window_mask(k)
        _ = self.dev_halo_mask(k)
        return self

    @property
    def rho(self) -> int:
        return self.frac.s ** self.m

    @property
    def r_b(self) -> int:
        return self.r - self.m

    @property
    def block_dims(self) -> Tuple[int, int, int]:
        """(nx, ny, nz) of the compact block box."""
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
        """(rho, rho, rho) uint8 occupancy of the level-m micro-fractal,
        indexed [z, y, x]."""
        return self.frac.mask(self.m)

    @functools.cached_property
    def block_coords(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Flat (n_blocks,) compact block coordinates (bx, by, bz),
        id-ordered (z-major raster)."""
        nx, ny, nz = self.block_dims
        bz, by, bx = np.meshgrid(np.arange(nz, dtype=np.int32),
                                 np.arange(ny, dtype=np.int32),
                                 np.arange(nx, dtype=np.int32),
                                 indexing="ij")
        return bx.reshape(-1), by.reshape(-1), bz.reshape(-1)

    @functools.cached_property
    def block_origin_expanded(self) -> np.ndarray:
        """(n_blocks, 3) int32 cell-level expanded origin (x, y, z)."""
        bx, by, bz = self.block_coords
        ex, ey, ez = f3.lambda3_map(self.frac, self.r_b, jnp.asarray(bx),
                                    jnp.asarray(by), jnp.asarray(bz))
        return np.stack([np.asarray(ex), np.asarray(ey),
                         np.asarray(ez)], axis=1) * self.rho

    def _map_offsets_to_table(self, offsets) -> np.ndarray:
        """(n_blocks, len(offsets)) int32 compact block id per offset:
        one lambda3 per block, one nu3 per (block, offset);
        out-of-fractal blocks get the ``ghost`` sentinel."""
        frac, r_b = self.frac, self.r_b
        bx, by, bz = (jnp.asarray(a) for a in self.block_coords)
        ex, ey, ez = f3.lambda3_map(frac, r_b, bx, by, bz)
        nx, ny, _ = self.block_dims
        side = frac.side(r_b) - 1
        table = np.empty((self.n_blocks, len(offsets)), dtype=np.int32)
        for d, (dx, dy, dz) in enumerate(offsets):
            qx, qy, qz = ex + dx, ey + dy, ez + dz
            valid = f3.is_fractal3(frac, r_b, qx, qy, qz)
            cx, cy, cz = f3.nu3_map(frac, r_b,
                                    jnp.clip(qx, 0, side),
                                    jnp.clip(qy, 0, side),
                                    jnp.clip(qz, 0, side))
            ids = jnp.where(valid, (cz * ny + cy) * nx + cx, self.ghost)
            table[:, d] = np.asarray(ids, dtype=np.int32)
        return table

    @functools.cached_property
    def neighbor_table(self) -> np.ndarray:
        """(n_blocks, 26) int32 compact block id per Moore direction."""
        return self._map_offsets_to_table(MOORE3_DIRS)

    @functools.cached_property
    def existence_table(self) -> np.ndarray:
        """(n_blocks, 26) int32 {0,1}: 1 where the Moore neighbor block
        is a real fractal block (the fused 3D kernels' per-block rows,
        gating the periodic window mask per substep)."""
        return (self.neighbor_table != self.ghost).astype(np.int32)

    # --------------------------------------------- device-side cached tables
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
    def dev_existence_table(self) -> Array:
        """Device-side ``existence_table`` (one shared upload)."""
        return self._to_device(self.existence_table)

    @functools.cached_property
    def dev_block_origin_expanded(self) -> Array:
        """Device-side ``block_origin_expanded`` (one shared upload)."""
        return self._to_device(self.block_origin_expanded)

    def dev_offset_table(self, k: int) -> Array:
        """Device-side ``offset_table(k)`` (one upload per depth)."""
        return self._memo(("dev_offset_table", self.halo_block_radius(k)),
                          lambda: self._to_device(self.offset_table(k)))

    def dev_window_mask(self, k: int) -> Array:
        """Device-side int32 ``window_mask(k)`` (upload per depth)."""
        return self._memo(
            ("dev_window_mask", k),
            lambda: self._to_device(self.window_mask(k).astype(np.int32)))

    def dev_halo_mask(self, k: int) -> Array:
        """Device-side ``halo_mask(k)`` (one upload per depth)."""
        return self._memo(("dev_halo_mask", k),
                          lambda: self._to_device(self.halo_mask(k)))

    # ------------------------------------------------------- depth-k halos
    def halo_block_radius(self, k: int) -> int:
        """Neighborhood radius in *blocks* covering a depth-``k`` cell
        halo (1 while k <= rho)."""
        if k < 1:
            raise ValueError(f"halo depth must be >= 1, got {k}")
        return math.ceil(k / self.rho)

    def halo_offsets(self, k: int) -> Tuple[Tuple[int, int, int], ...]:
        """Block offsets (bdx, bdy, bdz) whose cubes intersect the
        depth-``k`` halo window, raster-ordered; equals ``MOORE3_DIRS``
        when k <= rho."""
        kb = self.halo_block_radius(k)
        return tuple((dx, dy, dz)
                     for dz in range(-kb, kb + 1)
                     for dy in range(-kb, kb + 1)
                     for dx in range(-kb, kb + 1)
                     if (dx, dy, dz) != (0, 0, 0))

    @functools.cached_property
    def _halo_cache(self) -> dict:
        """Per-instance memo for the depth-k tables/masks (not an
        lru_cache on the methods — that would pin every layout and its
        (n_blocks, (rho+2k)^3) masks process-wide; see the 2D layout)."""
        return {}

    def _memo(self, key, build):
        cache = self._halo_cache
        if key not in cache:
            cache[key] = build()
        return cache[key]

    def offset_table(self, k: int) -> np.ndarray:
        """(n_blocks, len(halo_offsets(k))) int32 compact block id per
        offset, ghost sentinel for out-of-fractal blocks. Every entry is
        one lambda3 + one nu3 directly against the maps — never a
        composition of unit-step tables, so ghosts stay exact past holes
        at every depth (the 2D offset_table argument, in 3D)."""
        return self._memo(("offset_table", self.halo_block_radius(k)),
                          lambda: self._build_offset_table(k))

    def _build_offset_table(self, k: int) -> np.ndarray:
        if self.halo_block_radius(k) == 1:
            return self.neighbor_table  # identical construction + ordering
        return self._map_offsets_to_table(self.halo_offsets(k))

    def _halo_region(self, k: int, bdx: int, bdy: int, bdz: int):
        """Static window/source slices for one block offset: the overlap
        of the neighbor cube at (bdx, bdy, bdz) with the (rho+2k)^3 halo
        window. Returns ((z0, z1, y0, y1, x0, x1) in the window,
        the matching source bounds in the neighbor cube)."""
        rho = self.rho
        w = rho + 2 * k

        def axis(bd):
            o = k + bd * rho
            lo, hi = max(o, 0), min(o + rho, w)
            return lo, hi, lo - o, hi - o

        xz = [axis(bd) for bd in (bdz, bdy, bdx)]
        dst = tuple(v for lo, hi, _, _ in xz for v in (lo, hi))
        src = tuple(v for _, _, lo, hi in xz for v in (lo, hi))
        return dst, src

    def window_mask(self, k: int) -> np.ndarray:
        """(rho+2k,)^3 uint8: periodic extension of ``micro_mask`` over
        the depth-``k`` window (every *existing* neighbor block carries
        exactly ``micro_mask``, by self-similarity)."""
        def build():
            idx = np.arange(-k, self.rho + k) % self.rho
            return self.micro_mask[np.ix_(idx, idx, idx)]
        return self._memo(("window_mask", k), build)

    def halo_mask(self, k: int) -> np.ndarray:
        """(n_blocks, rho+2k, rho+2k, rho+2k) uint8 occupancy of each
        block's depth-``k`` window: the periodic ``window_mask`` with the
        regions of out-of-fractal (ghost) neighbors zeroed per block —
        the k-substep mask discipline's operand, as in 2D."""
        return self._memo(("halo_mask", k), lambda: self._build_halo_mask(k))

    def _build_halo_mask(self, k: int) -> np.ndarray:
        w = self.rho + 2 * k
        table = self.offset_table(k)
        full = np.broadcast_to(self.window_mask(k),
                               (self.n_blocks, w, w, w)).copy()
        for oi, (bdx, bdy, bdz) in enumerate(self.halo_offsets(k)):
            (z0, z1, y0, y1, x0, x1), _ = \
                self._halo_region(k, bdx, bdy, bdz)
            full[table[:, oi] == self.ghost, z0:z1, y0:y1, x0:x1] = 0
        return full

    # -------------------------------------------- macro-tile strip geometry
    def macro_tiles(self, k: int, lanes: int = 128,
                    p: Optional[int] = None) -> Tuple[int, int, int]:
        """Lane-packing geometry of the 3D MXU kernel: ``(P, n_macro,
        nb_pad)`` with ``P`` blocks packed side by side along the minor
        (x/lane) axis of one macro-tile so ``P * (rho+2k)`` fills the
        vector registers — the same math as the 2D ``macro_tiles``,
        applied to z-slab matrices of shape (rho+2k, P*(rho+2k)).
        ``p`` overrides the lane heuristic (autotuner sweep; clamped to
        [1, n_blocks], no rebalance)."""
        return self.macro_tiles_for(self.n_blocks, k, lanes, p)

    def macro_tiles_for(self, nb: int, k: int, lanes: int = 128,
                        p: Optional[int] = None) -> Tuple[int, int, int]:
        """``macro_tiles`` for an arbitrary block count ``nb``."""
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

    def existence_padded(self, k: int,
                         p: Optional[int] = None) -> np.ndarray:
        """(nb_pad, 26) int32 ``existence_table`` zero-padded to the
        macro slot count (padding slots stay ghost-gated to zero).
        ``p`` is the macro-tile packing override."""
        def build():
            _, _, nb_pad = self.macro_tiles(k, p=p)
            pad = np.zeros((nb_pad - self.n_blocks, 26), np.int32)
            return np.concatenate([self.existence_table, pad], axis=0)
        return self._memo(("existence_padded", k, p), build)

    def dev_existence_padded(self, k: int,
                             p: Optional[int] = None) -> Array:
        """Device-side ``existence_padded(k)`` (upload per depth and
        packing)."""
        return self._memo(
            ("dev_existence_padded", k, p),
            lambda: self._to_device(self.existence_padded(k, p)))

    # ------------------------------------------------------------ conversions
    def to_expanded(self, state_b: Array) -> Array:
        """Block state (C?, n_blocks, rho, rho, rho) -> (C?, n, n, n)
        expanded embedding (leading channel axes pass through)."""
        n = self.frac.side(self.r)
        org = self.dev_block_origin_expanded  # (n_blocks, 3)
        rho = self.rho
        iz, iy, ix = jnp.meshgrid(jnp.arange(rho), jnp.arange(rho),
                                  jnp.arange(rho), indexing="ij")
        ax = org[:, 0, None, None, None] + ix[None]
        ay = org[:, 1, None, None, None] + iy[None]
        az = org[:, 2, None, None, None] + iz[None]
        out = jnp.zeros(state_b.shape[:-4] + (n, n, n), dtype=state_b.dtype)
        return out.at[..., az, ay, ax].set(state_b)

    def from_expanded(self, state_e: Array) -> Array:
        """(C?, n, n, n) expanded embedding -> block state (C?, n_blocks,
        rho, rho, rho)."""
        org = self.dev_block_origin_expanded
        rho = self.rho
        iz, iy, ix = jnp.meshgrid(jnp.arange(rho), jnp.arange(rho),
                                  jnp.arange(rho), indexing="ij")
        ax = org[:, 0, None, None, None] + ix[None]
        ay = org[:, 1, None, None, None] + iy[None]
        az = org[:, 2, None, None, None] + iz[None]
        mask = self.dev_micro_mask
        return state_e[..., az, ay, ax] * mask.astype(state_e.dtype)

    def pad_with_halo_k(self, state_b: Array, k: int) -> Array:
        """Assemble (n_blocks, (rho+2k)^3) windows with depth-``k``
        halos: for each block offset only the overlap slab of the
        neighbor cube is sliced *before* the gather (HBM traffic stays
        ~surface * k, not offsets * rho^3); ghost ids index an appended
        zero slab, keeping out-of-fractal reads zero at every depth."""
        if k < 1:
            raise ValueError(f"halo depth must be >= 1, got {k}")
        rho, nb = self.rho, self.n_blocks
        w = rho + 2 * k
        table = self.dev_offset_table(k)
        out = jnp.zeros((nb, w, w, w), state_b.dtype)
        out = out.at[:, k:k + rho, k:k + rho, k:k + rho].set(state_b)
        for oi, (bdx, bdy, bdz) in enumerate(self.halo_offsets(k)):
            (z0, z1, y0, y1, x0, x1), (sz0, sz1, sy0, sy1, sx0, sx1) = \
                self._halo_region(k, bdx, bdy, bdz)
            strip = state_b[:, sz0:sz1, sy0:sy1, sx0:sx1]
            strip = jnp.concatenate(
                [strip, jnp.zeros((1,) + strip.shape[1:], state_b.dtype)],
                axis=0)
            out = out.at[:, z0:z1, y0:y1, x0:x1].set(
                jnp.take(strip, table[:, oi], axis=0))
        return out

    def memory_bytes(self, dtype_size: int = 1) -> int:
        """Squeeze 3D block-level state bytes."""
        return self.n_blocks * self.rho ** 3 * dtype_size
