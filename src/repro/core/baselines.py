"""Baseline fractal engines the paper compares against (Section 4):

  * ``BBEngine``      — the classic expanded bounding-box approach: both the
                        parallel grid and the memory are the full n x n
                        embedding (paper's approach 1).
  * ``LambdaEngine``  — Navarro et al. [7]: compact *grid* (one unit of work
                        per fractal cell, placed by lambda) but still
                        *expanded memory* (paper's approach 2). Solves P1,
                        not P2.

Both are parameterized by a ``StencilWorkload`` (default: the paper's
game-of-life adaptation): only fractal cells carry state or are counted as
neighbors (holes and out-of-bounds read 0 — dead for CA rules, Dirichlet-0
for the PDE rules), and the workload's update rule is applied on fractal
cells only. Multi-channel workloads carry a leading channel axis:
state (C, n, n).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import maps
from repro.core.compact import MOORE_DIRS
from repro.core.fractals import NBBFractal
from repro.workloads.base import (StencilWorkload, cell_bits,
                                  check_workload_ndim, weighted_gather_agg,
                                  weighted_moore_agg)
from repro.workloads.rules import LIFE, life_rule  # noqa: F401 (re-export)

Array = jnp.ndarray


def _moore_counts(padded: Array) -> Array:
    """Sum of the 8 Moore neighbors from a (+1)-padded array (trailing two
    axes are spatial; leading channel/block axes broadcast through)."""
    return weighted_moore_agg(padded, (1,) * 8, jnp.int32)


def _pad_spatial(state: Array) -> Array:
    """Zero-pad the trailing two (spatial) axes by 1."""
    pad = [(0, 0)] * (state.ndim - 2) + [(1, 1), (1, 1)]
    return jnp.pad(state, pad)


def _expanded_coords(n: int):
    """(ex, ey) int32 (n, n) iota grids of the expanded embedding."""
    ey, ex = jnp.meshgrid(jnp.arange(n, dtype=jnp.int32),
                          jnp.arange(n, dtype=jnp.int32), indexing="ij")
    return ex, ey


def expanded_mask(frac: NBBFractal, r: int) -> Array:
    """(n, n) uint8 occupancy of the level-``r`` embedding, computed on
    the device from the cell coordinates (a cell is a hole iff one of
    its base-s digit pairs is a hole slot) — never a host table, so a
    2^28-cell reference neither builds nor embeds a 268 MB constant."""
    ex, ey = _expanded_coords(frac.side(r))
    holes = [(x, y) for y in range(frac.s) for x in range(frac.s)
             if frac.h_nu[y, x] < 0]
    member = ex >= 0
    for mu in range(r):
        scale = frac.s ** mu
        tx, ty = (ex // scale) % frac.s, (ey // scale) % frac.s
        for hx, hy in holes:
            member = member & ~((tx == hx) & (ty == hy))
    return member.astype(jnp.uint8)


@partial(jax.jit, static_argnums=(0, 1, 2))
def _init_expanded(frac: NBBFractal, r: int, workload: StencilWorkload,
                   seed) -> Array:
    """Seeded initial field over the (n, n) embedding, holes 0."""
    field = workload.init(cell_bits(seed, *_expanded_coords(frac.side(r))))
    return field * expanded_mask(frac, r).astype(field.dtype)


@dataclasses.dataclass(frozen=True)
class BBEngine:
    """Expanded grid + expanded memory (the classic approach)."""

    frac: NBBFractal
    r: int
    workload: StencilWorkload = LIFE

    def __post_init__(self):
        check_workload_ndim(self.workload, 2)

    def init_random(self, seed: int) -> Array:
        return _init_expanded(self.frac, self.r, self.workload,
                              jnp.asarray(seed, jnp.int32))

    @partial(jax.jit, static_argnums=0)
    def step(self, state: Array) -> Array:
        wl = self.workload
        mask = expanded_mask(self.frac, self.r)
        padded = _pad_spatial(state)
        # integer counts in 32 bits: on a v5e, 8-bit shifted slices of the
        # whole (n, n) plane ran 12x slower (2^28 cells, 64 Life steps:
        # 97.6 s against 8.2 s)
        agg_dtype = jnp.promote_types(wl.agg_dtype, jnp.int32) \
            if jnp.issubdtype(wl.agg_dtype, jnp.integer) else wl.agg_dtype
        agg = weighted_moore_agg(padded, wl.weights2d, agg_dtype)
        return wl.apply(state, agg, mask)

    @partial(jax.jit, static_argnums=0)
    def _run(self, state: Array, steps) -> Array:
        return jax.lax.fori_loop(0, steps, lambda _, s: self.step(s), state)

    def run(self, state: Array, steps: int) -> Array:
        return self._run(state, jnp.asarray(steps, jnp.int32))

    def memory_bytes(self, dtype_size: int = 1) -> int:
        n = self.frac.side(self.r)
        return self.workload.n_channels * n * n * dtype_size


@dataclasses.dataclass(frozen=True)
class LambdaEngine:
    """Compact grid (via lambda), expanded memory — Navarro et al. [7].

    Work is enumerated over the k^r compact coordinates; each one lambda-maps
    to its expanded cell, reads its Moore neighborhood from expanded memory,
    and writes the updated cell back to expanded memory.
    """

    frac: NBBFractal
    r: int
    workload: StencilWorkload = LIFE

    def __post_init__(self):
        check_workload_ndim(self.workload, 2)

    def init_random(self, seed: int) -> Array:
        return BBEngine(self.frac, self.r, self.workload).init_random(seed)

    @partial(jax.jit, static_argnums=0)
    def step(self, state: Array) -> Array:
        frac, r, wl = self.frac, self.r, self.workload
        rows, cols = frac.compact_dims(r)
        cy, cx = jnp.meshgrid(jnp.arange(rows, dtype=jnp.int32),
                              jnp.arange(cols, dtype=jnp.int32), indexing="ij")
        ex, ey = maps.lambda_map(frac, r, cx, cy)
        padded = _pad_spatial(state)
        agg = weighted_gather_agg(
            MOORE_DIRS, wl.weights2d,
            lambda d: padded[..., ey + 1 + d[1], ex + 1 + d[0]],
            state.shape[:-2] + ex.shape, wl.agg_dtype)
        center = state[..., ey, ex]
        # every enumerated cell is a fractal cell: no mask needed
        nxt_vals = wl.apply(center, agg, None)
        # scatter back into (a fresh copy of) expanded memory
        nxt = jnp.zeros_like(state)
        return nxt.at[..., ey, ex].set(nxt_vals.astype(state.dtype))

    def run(self, state: Array, steps: int) -> Array:
        return jax.lax.fori_loop(0, steps, lambda _, s: self.step(s), state)

    def memory_bytes(self, dtype_size: int = 1) -> int:
        n = self.frac.side(self.r)
        return self.workload.n_channels * n * n * dtype_size
