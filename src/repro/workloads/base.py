"""The ``StencilWorkload`` abstraction: what the engines simulate.

The paper frames Squeeze as a general scheme for data-parallel computation
on a fractal with neighborhood access; the game of life of Section 4 is
one instance. A workload bundles everything rule-specific so that the
engines (BB, lambda, Squeeze cell/block/3D, the multi-device engine in
core/distributed.py) and the Pallas kernels stay rule-agnostic.

  * ``dtype`` / ``agg_dtype``  — cell state and accumulation dtypes;
  * ``n_channels``             — 1 (scalar field) or C (e.g. Gray-Scott's
                                 (U, V) pair). Multi-channel states carry a
                                 leading channel axis: (C, *spatial).
  * ``weight(offset)``         — per-direction neighbor weight, dimension
                                 agnostic ((dx, dy) or (dx, dy, dz)); a 0
                                 weight means the direction is never read;
  * ``apply(center, agg, mask)`` — the update rule, given the weighted
                                 neighbor aggregate. ``mask`` is the {0,1}
                                 occupancy (holes/boundary), or None when
                                 the caller's domain has no holes (cell
                                 engine: every compact cell is real);
  * ``init(bits)``             — the initial-state distribution over the
                                 *unmasked* spatial domain (engines mask),
                                 drawn from one uint32 ``cell_bits`` word
                                 per cell.

Out-of-fractal and hole neighbors always contribute 0 to the aggregate —
dead cells for CA rules, Dirichlet-0 boundaries for the PDE rules — which
is exactly the paper's adaptation of life to the fractal (Section 4).

See DESIGN.md Section 3 for how this composes with the engines and the
batched runner.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax.numpy as jnp
import numpy as np

Array = jnp.ndarray

#: Moore neighborhood directions (dx, dy), y growing downward. Defined here
#: (the dependency-free layer); ``core.compact`` re-exports it, so the
#: workloads package imports nothing from ``core`` (no import cycle).
MOORE_DIRS: Tuple[Tuple[int, int], ...] = (
    (-1, -1), (0, -1), (1, -1),
    (-1, 0), (1, 0),
    (-1, 1), (0, 1), (1, 1),
)

#: 3D Moore neighborhood (dx, dy, dz), raster-ordered (dz slowest, dx
#: fastest) so the 26 directions line up with the 3D halo regions the
#: same way MOORE_DIRS lines up with the 2D ones.
MOORE3_DIRS: Tuple[Tuple[int, int, int], ...] = tuple(
    (dx, dy, dz)
    for dz in (-1, 0, 1) for dy in (-1, 0, 1) for dx in (-1, 0, 1)
    if (dx, dy, dz) != (0, 0, 0))


class StencilWorkload:
    """Base class; concrete workloads are frozen dataclasses (hashable, so
    a workload can be a jit static argument and an engine-cache key)."""

    name: str = "abstract"
    #: number of state channels; multi-channel states are (C, *spatial)
    n_channels: int = 1
    #: spatial dimensionality the rule is defined for (None = any)
    ndim = None
    #: cell state dtype
    dtype = jnp.uint8
    #: accumulation dtype for the neighbor aggregate
    agg_dtype = jnp.int32

    # ------------------------------------------------------------- rule spec
    def weight(self, offset) -> float:
        """Weight of the neighbor at ``offset`` (any dimensionality)."""
        return 1

    def apply(self, center: Array, agg: Array, mask) -> Array:
        """Update rule: next state from (center, weighted neighbor aggregate).

        ``center``/``agg`` have a leading channel axis iff n_channels > 1.
        Implementations must zero holes when ``mask`` is given.
        """
        raise NotImplementedError

    def init(self, bits: Array) -> Array:
        """Initial state from one uint32 random word per cell (``bits``
        has the spatial shape; the result is (C, *shape) if C > 1)."""
        raise NotImplementedError

    # ------------------------------------------------------------ conveniences
    @property
    def weights2d(self):
        """Weights over the 2D Moore directions, MOORE_DIRS order."""
        return tuple(self.weight(d) for d in MOORE_DIRS)

    @property
    def weights3x3(self) -> np.ndarray:
        """The 2D neighbor weights as a 3x3 float64 matrix indexed
        ``[dy+1, dx+1]`` (center weight 0: the aggregate never includes the
        cell itself — rules read it through ``center``)."""
        w = np.zeros((3, 3), np.float64)
        for dx, dy in MOORE_DIRS:
            w[dy + 1, dx + 1] = self.weight((dx, dy))
        return w

    @property
    def weights3d(self):
        """Weights over the 26 3D Moore directions, MOORE3_DIRS order."""
        return tuple(self.weight(d) for d in MOORE3_DIRS)

    @property
    def weights3x3x3(self) -> np.ndarray:
        """The 3D neighbor weights as a (3, 3, 3) float64 tensor indexed
        ``[dz+1, dy+1, dx+1]`` (center weight 0, as in ``weights3x3``)."""
        w = np.zeros((3, 3, 3), np.float64)
        for dx, dy, dz in MOORE3_DIRS:
            w[dz + 1, dy + 1, dx + 1] = self.weight((dx, dy, dz))
        return w

    @functools.cached_property
    def weight_factors3(self) -> Tuple[Tuple, Tuple, Tuple]:
        """Per-z-plane rank-1 decompositions of ``weights3x3x3``: a
        3-tuple (dz = -1, 0, +1), each entry the ``svd_rank1_terms`` of
        that plane's 3x3 xy weight matrix (empty for all-zero planes).
        This is the z-slab MXU formulation: the 26-neighbor aggregate of
        slab ``z`` is ``sum_dz sum_t R_t(dz) @ X[z+dz] @ C_t(dz)^T`` —
        each z-plane of the weight tensor is an independent 2D banded
        contraction applied to the neighboring slab (see DESIGN.md
        Section 5). Exactness is verified per plane at build time."""
        return tuple(
            svd_rank1_terms(plane) if plane.any() else ()
            for plane in self.weights3x3x3)

    @functools.cached_property
    def weight_factors(self) -> Tuple[Tuple[Tuple[float, ...],
                                            Tuple[float, ...]], ...]:
        """Rank-1 decomposition of ``weights3x3``: <= 3 ``(row, col)``
        pairs with ``sum_i outer(row_i, col_i) == weights3x3`` exactly (to
        float64 SVD precision; verified at build time). This is what turns
        the Moore aggregation into banded matmul contractions
        ``R_i @ X @ C_i^T`` on the MXU (see ``svd_rank1_terms`` and
        DESIGN.md Section 2.2). Cached on the frozen dataclass instance;
        hashability/equality (jit static args, runner cache keys) are
        untouched — dataclass hashing reads fields, not ``__dict__``."""
        return svd_rank1_terms(self.weights3x3)

    def tile_rule(self, center: Array, padded: Array, mask,
                  agg_dtype=None) -> Array:
        """One update on a halo-padded tile: ``center`` (C?, h, w), ``padded``
        (C?, h+2, w+2). This is the traced function the Pallas kernels call
        in place of the old hard-coded life rule. ``agg_dtype`` overrides
        the workload's accumulation dtype (the kernels accumulate on
        32-bit lanes)."""
        agg = weighted_moore_agg(padded, self.weights2d,
                                 agg_dtype or self.agg_dtype)
        return self.apply(center, agg, mask)

    def tile_rule_k(self, padded: Array, halo_mask, k: int,
                    ndim: int = 2, agg_dtype=None) -> Array:
        """``k`` fused updates on a depth-``k`` padded tile (temporal
        blocking). ``padded`` is (C?, h+2k, w+2k); each substep updates the
        current window's interior and the window shrinks by one ring, so
        after ``k`` substeps the (C?, h, w) core has advanced ``k`` exact
        steps. ``halo_mask`` is the {0,1} occupancy of the *whole* window
        (trailing (h+2k, w+2k) axes; leading axes broadcast) or None; it is
        re-applied at every substep on a matching shrinking crop — halo
        cells belong to neighbor tiles whose holes/ghosts must stay zero
        mid-flight, not just at the final write.

        ``ndim=3`` runs the same discipline on a (C?, d+2k, h+2k, w+2k)
        volume over the 26-direction aggregate (the 3D block engines).
        ``agg_dtype`` overrides the accumulation dtype as in
        ``tile_rule``."""
        crop = (Ellipsis,) + (slice(1, -1),) * ndim
        agg_of = weighted_moore_agg if ndim == 2 else weighted_moore_agg3
        weights = self.weights2d if ndim == 2 else self.weights3d
        agg_dtype = agg_dtype or self.agg_dtype
        cur = padded
        for _ in range(k):
            center = cur[crop]
            agg = agg_of(cur, weights, agg_dtype)
            if halo_mask is not None:
                halo_mask = halo_mask[crop]
            cur = self.apply(center, agg, halo_mask)
        return cur

    def masked(self, state: Array, mask) -> Array:
        return state if mask is None else state * mask.astype(state.dtype)


def _mix32(x: Array) -> Array:
    """The lowbias32 integer finalizer (uint32 -> uint32, bijective)."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def cell_bits(seed: int, *coords: Array) -> Array:
    """One uint32 random word per cell, a pure function of ``seed`` and
    the cell's EXPANDED coordinates (x, y[, z]).

    Every engine draws its initial state from these words at its own
    cells' coordinates, so the expanded baselines and the compact
    engines start from the same field without any of them materializing
    another's layout: a block state at r=17 is seeded on the device in
    block space, never through the 2^34-cell embedding."""
    h = _mix32(jnp.asarray(seed).astype(jnp.uint32)
               ^ jnp.uint32(0x9E3779B9))
    for c in coords:
        h = _mix32(h ^ jnp.asarray(c).astype(jnp.uint32))
    return h


def uniform_from_bits(bits: Array) -> Array:
    """float32 in [0, 1) from the top 24 bits of a uint32 word."""
    return (bits >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)


#: which pieces of a Moore halo a single radius-1 update actually reads:
#: edge strips (rows N/S, cols W/E) and the four corner cells.
HaloNeeds = Tuple[bool, ...]


def halo_needs(weights) -> "HaloNeeds":
    """(need_n, need_s, need_w, need_e, need_nw, need_ne, need_sw, need_se)
    for one radius-1 Moore update with the given ``weights2d``.

    A corner halo cell is read only by the matching diagonal shift, so a
    zero diagonal weight makes that gather dead; an edge strip is read by
    its orthogonal shift *and* both adjacent diagonal shifts, so it is dead
    only when all three weights are zero (HeatDiffusion: 4 orthogonal
    strips gathered, 4 corner gathers skipped). Single-step (k=1) kernels
    only — a fused k>=2 substep chain propagates corner values inward even
    under orthogonal-only weights.
    """
    w = dict(zip(MOORE_DIRS, weights))
    need_nw, need_ne = w[(-1, -1)] != 0, w[(1, -1)] != 0
    need_sw, need_se = w[(-1, 1)] != 0, w[(1, 1)] != 0
    need_n = need_nw or need_ne or w[(0, -1)] != 0
    need_s = need_sw or need_se or w[(0, 1)] != 0
    need_w = need_nw or need_sw or w[(-1, 0)] != 0
    need_e = need_ne or need_se or w[(1, 0)] != 0
    return (need_n, need_s, need_w, need_e,
            need_nw, need_ne, need_sw, need_se)


def svd_rank1_terms(weights3x3: np.ndarray, tol: float = 1e-9):
    """Decompose a 3x3 weight matrix into <= 3 rank-1 ``(row, col)`` terms
    by SVD: ``W = sum_i outer(row_i, col_i)`` with ``sqrt(sigma_i)`` folded
    into each factor (keeps both factors O(1), which matters once they are
    cast to the kernel's float32 operands).

    Singular values below ``tol * sigma_max`` are truncated — every
    shipped workload is exactly rank 2 (Life's ones-minus-center, Heat's
    5-point cross, Gray-Scott's 9-point Laplacian all have two equal
    rows), so truncation only drops numerical noise. Reconstruction is
    verified here: a workload whose weights the decomposition cannot
    represent exactly fails loudly at build time, not with silently wrong
    aggregates.
    """
    w = np.asarray(weights3x3, np.float64)
    if w.shape != (3, 3):
        raise ValueError(f"need a 3x3 weight matrix, got {w.shape}")
    u, s, vh = np.linalg.svd(w)
    keep = s > (tol * s[0] if s[0] > 0 else tol)
    terms = tuple(
        (tuple(float(x) for x in u[:, i] * np.sqrt(s[i])),
         tuple(float(x) for x in vh[i, :] * np.sqrt(s[i])))
        for i in range(3) if keep[i])
    recon = np.zeros((3, 3), np.float64)
    for row, col in terms:
        recon += np.outer(row, col)
    if not np.allclose(recon, w, rtol=0, atol=1e-12):
        raise ValueError(
            f"rank-1 SVD terms do not reconstruct the weight matrix "
            f"exactly (max err {np.abs(recon - w).max():.3e})")
    return terms


def banded_operators(terms, window: int, dtype=np.float32):
    """Build the banded contraction matrices for the rank-1 terms over a
    ``window x window`` tile: ``R`` (T, window, window) with
    ``R[t, y, y+dy] = row_t[dy+1]`` and ``C`` (T, window, window) with
    ``C[t, x, x+dx] = col_t[dx+1]``, so that ``R[t] @ X @ C[t].T`` sums
    ``row_t[dy+1] * col_t[dx+1] * X[y+dy, x+dx]`` over the 3x3 offsets.
    Border rows/cols get truncated bands (their outputs fall outside the
    shrinking live window of the fused substeps and are never read).
    """
    tm = np.zeros((len(terms), window, window), dtype)
    cm = np.zeros((len(terms), window, window), dtype)
    for t, (row, col) in enumerate(terms):
        for y in range(window):
            for d in (-1, 0, 1):
                if 0 <= y + d < window:
                    tm[t, y, y + d] = row[d + 1]
                    cm[t, y, y + d] = col[d + 1]
    return tm, cm


def check_workload_ndim(workload: "StencilWorkload", ndim: int):
    """Raise if a workload is bound to an engine of the wrong spatial
    dimensionality (e.g. the 2D heat instance on a 3D engine, whose
    Laplacian degree and stability bound would silently be wrong)."""
    if workload.ndim is not None and workload.ndim != ndim:
        raise ValueError(
            f"workload {workload.name!r} is {workload.ndim}D-only; "
            f"engine is {ndim}D")


def weighted_gather_agg(dirs, weights, gather, shape, agg_dtype) -> Array:
    """Weighted neighbor aggregate from a per-direction ``gather(offset)``
    callback (the gather/scatter engines' counterpart of
    ``weighted_moore_agg``). Zero-weight directions are never gathered;
    unit weights skip the multiply (keeps integer CA aggregates exact)."""
    agg = jnp.zeros(shape, agg_dtype)
    for d, wt in zip(dirs, weights):
        if wt == 0:
            continue
        val = gather(d).astype(agg_dtype)
        agg = agg + (val if wt == 1 else val * jnp.asarray(wt, agg_dtype))
    return agg


def _moore_split(weights):
    """(w_diag, w_orth) when the Moore weights are uniform per ring (all
    four diagonal weights equal, all four orthogonal weights equal) —
    every shipped workload — else None. Such a set separates as
    ``w_diag * ones3x3(minus center) + (w_orth - w_diag) * cross``, and
    the ones part factors into row/col partial sums: 6 shift-adds instead
    of 8 full-window gathers (fewer ops per substep — the lever that
    makes temporal fusion pay: the per-launch halo/exchange cost is
    amortized over k CHEAP substeps)."""
    w = dict(zip(MOORE_DIRS, weights))
    diag = {w[(-1, -1)], w[(1, -1)], w[(-1, 1)], w[(1, 1)]}
    orth = {w[(0, -1)], w[(-1, 0)], w[(1, 0)], w[(0, 1)]}
    if len(diag) == 1 and len(orth) == 1:
        return diag.pop(), orth.pop()
    return None


def _scaled(x: Array, wt, agg_dtype) -> Array:
    return x if wt == 1 else x * jnp.asarray(wt, agg_dtype)


def weighted_moore_agg(padded: Array, weights, agg_dtype) -> Array:
    """Weighted 8-neighbor aggregate from a (+1)-padded array.

    ``padded`` is (..., H+2, W+2); returns (..., H, W). Slicing runs on the
    trailing two axes, so channel/block leading axes broadcast through.
    Zero-weight directions are never read; unit weights skip the multiply
    (keeps integer CA aggregates exact).

    Ring-uniform weight sets (all shipped workloads) take a separable
    fast path: the ones3x3 component is built from row partial sums
    (R = x_up + x + x_down, then R_left + R + R_right minus the center),
    plus a 4-term cross correction when the rings differ — e.g. Life runs
    in 6 integer shift-adds instead of 8, bit-exact (pure adds, no
    weight multiplies).
    """
    h, w = padded.shape[-2] - 2, padded.shape[-1] - 2
    split = _moore_split(weights)
    if split is not None and split[0] != 0:
        wd, wo = split
        x = padded.astype(agg_dtype)
        # rows spans the padded width so the horizontal pass can shift it
        rows = (x[..., 0:h, :] + x[..., 1:h + 1, :] + x[..., 2:h + 2, :])
        sum9 = rows[..., 0:w] + rows[..., 1:w + 1] + rows[..., 2:w + 2]
        agg = _scaled(sum9 - x[..., 1:h + 1, 1:w + 1], wd, agg_dtype)
        if wo != wd:
            cross = (x[..., 0:h, 1:w + 1] + x[..., 1:h + 1, 0:w]
                     + x[..., 1:h + 1, 2:w + 2] + x[..., 2:h + 2, 1:w + 1])
            agg = agg + _scaled(cross, wo - wd, agg_dtype)
        return agg
    agg = jnp.zeros(padded.shape[:-2] + (h, w), agg_dtype)
    for (dx, dy), wt in zip(MOORE_DIRS, weights):
        if wt == 0:
            continue
        sl = padded[..., 1 + dy:h + 1 + dy, 1 + dx:w + 1 + dx]
        sl = sl.astype(agg_dtype)
        agg = agg + _scaled(sl, wt, agg_dtype)
    return agg


def weighted_moore_agg3(padded: Array, weights, agg_dtype) -> Array:
    """Weighted 26-neighbor aggregate from a (+1)-padded 3D array.

    ``padded`` is (..., D+2, H+2, W+2); returns (..., D, H, W) — the 3D
    counterpart of ``weighted_moore_agg``, slicing the trailing three axes
    so leading channel/block axes broadcast through.

    A uniform 26-weight set (LIFE3D) takes the separable fast path: the
    27-cell box sum is built from three axis passes (9 shift-adds instead
    of 26 gathers) and the center is subtracted — pure adds, bit-exact
    for integer CA aggregates. Every other set (e.g. HEAT3D's 6-point
    orthogonal Laplacian) falls back to the zero-skipping gather loop.
    """
    d = padded.shape[-3] - 2
    h = padded.shape[-2] - 2
    w = padded.shape[-1] - 2
    uniq = set(weights)
    if len(uniq) == 1 and 0 not in uniq:
        wt = uniq.pop()
        x = padded.astype(agg_dtype)
        # three separable passes: z, then y (spanning padded x so the
        # final pass can shift it), then x; minus the center
        slabs = x[..., 0:d, :, :] + x[..., 1:d + 1, :, :] \
            + x[..., 2:d + 2, :, :]
        rows = slabs[..., 0:h, :] + slabs[..., 1:h + 1, :] \
            + slabs[..., 2:h + 2, :]
        sum27 = rows[..., 0:w] + rows[..., 1:w + 1] + rows[..., 2:w + 2]
        return _scaled(sum27 - x[..., 1:d + 1, 1:h + 1, 1:w + 1], wt,
                       agg_dtype)
    agg = jnp.zeros(padded.shape[:-3] + (d, h, w), agg_dtype)
    for (dx, dy, dz), wt in zip(MOORE3_DIRS, weights):
        if wt == 0:
            continue
        sl = padded[..., 1 + dz:d + 1 + dz, 1 + dy:h + 1 + dy,
                    1 + dx:w + 1 + dx]
        agg = agg + _scaled(sl.astype(agg_dtype), wt, agg_dtype)
    return agg
