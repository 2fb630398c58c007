"""3D compact stencil engines: the paper's case study lifted to 3D NBB
fractals (Menger sponge etc.) using the lambda3/nu3 maps — completing
the §5 "extend to 3D" future work at full performance parity with the
2D stack:

  * ``BB3DEngine``          — expanded bounding-volume baseline, O(n^3).
  * ``Squeeze3DEngine``     — paper-faithful per-cell compact engine
                              (one lambda3 per cell, one nu3 +
                              membership per neighbor), O(k^r) memory.
  * ``Squeeze3DBlockEngine``  — block-level Squeeze over
                              ``BlockLayout3D``: static 26-direction
                              block tables turn the step into
                              halo-gather + dense in-cube stencil, with
                              ``step_k`` depth-k temporal fusion (any
                              k >= 1; k > rho spans multiple block
                              rings through the offset tables).
  * ``Squeeze3DPallasEngine`` — the block engine with its step fused
                              into one of the 3D Pallas kernels
                              (kernels/squeeze_stencil3d.py): variant
                              'fused' (v4-style depth-k window in VMEM)
                              or 'mxu' (v5-style z-slab banded matmuls
                              on lane-packed macro-tiles). k <= rho.

All engines are parameterized by a single-channel ``StencilWorkload``
over the 26-cell Moore neighborhood; the defaults are 3D life B6/S5-7
(``LIFE3D``) and the 6-neighbor Jacobi heat workload (``HEAT3D``).
Holes and out-of-bounds never contribute, exactly like the 2D
adaptation in §4. Every ``run`` goes through the cached-jit machinery
of core/stencil.py: the step count is a *traced* loop bound (changing
it does not retrace) and ``donate=True`` donates the state buffer to
XLA for zero-copy steady-state stepping.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import fractals3d as f3
from repro.core.compact3d import BlockLayout3D
from repro.core.stencil import _CachedRun, _FusedStepping
from repro.workloads.base import (MOORE3_DIRS, StencilWorkload, cell_bits,
                                  check_workload_ndim, weighted_gather_agg,
                                  weighted_moore_agg3)
from repro.workloads.rules import LIFE3D

Array = jnp.ndarray


def life3_rule(alive: Array, neighbors: Array) -> Array:
    """3D life B6/S5-7 (kept as a function for the original call sites)."""
    born = (neighbors == 6)
    survive = (alive > 0) & (neighbors >= 5) & (neighbors <= 7)
    return (born | survive).astype(jnp.uint8)


def _check_workload(workload: StencilWorkload):
    if workload.n_channels != 1:
        raise ValueError("3D engines support single-channel workloads only")
    check_workload_ndim(workload, 3)


@dataclasses.dataclass(frozen=True)
class BB3DEngine(_CachedRun):
    """Expanded bounding-volume baseline: O(n^3) memory."""

    frac: f3.NBBFractal3D
    r: int
    workload: StencilWorkload = LIFE3D

    def __post_init__(self):
        _check_workload(self.workload)

    def init_random(self, seed: int) -> Array:
        n = self.frac.side(self.r)
        mask = jnp.asarray(self.frac.mask(self.r))
        ez, ey, ex = jnp.meshgrid(*(jnp.arange(n, dtype=jnp.int32),) * 3,
                                  indexing="ij")
        field = self.workload.init(cell_bits(seed, ex, ey, ez))
        return field * mask.astype(field.dtype)

    @partial(jax.jit, static_argnums=0)
    def step(self, state: Array) -> Array:
        wl = self.workload
        mask = jnp.asarray(self.frac.mask(self.r))
        padded = jnp.pad(state, 1)
        agg = weighted_moore_agg3(padded, wl.weights3d, wl.agg_dtype)
        return wl.apply(state, agg, mask).astype(state.dtype)

    def _run_impl(self, state: Array, steps, ops) -> Array:
        return jax.lax.fori_loop(0, steps, lambda _, s: self.step(s), state)

    def run(self, state: Array, steps, donate: bool = False) -> Array:
        return self._dispatch_run(state, steps, donate)

    def memory_bytes(self) -> int:
        return self.frac.side(self.r) ** 3


@dataclasses.dataclass(frozen=True)
class Squeeze3DEngine(_CachedRun):
    """Compact 3D engine: O(k^r) memory via lambda3/nu3 per neighbor."""

    frac: f3.NBBFractal3D
    r: int
    workload: StencilWorkload = LIFE3D

    def __post_init__(self):
        _check_workload(self.workload)

    def _compact_grid(self):
        nx, ny, nz = self.frac.compact_dims(self.r)
        cz, cy, cx = jnp.meshgrid(jnp.arange(nz, dtype=jnp.int32),
                                  jnp.arange(ny, dtype=jnp.int32),
                                  jnp.arange(nx, dtype=jnp.int32),
                                  indexing="ij")
        return cx, cy, cz

    def init_random(self, seed: int) -> Array:
        expanded = BB3DEngine(self.frac, self.r,
                              self.workload).init_random(seed)
        cx, cy, cz = self._compact_grid()
        ex, ey, ez = f3.lambda3_map(self.frac, self.r, cx, cy, cz)
        return expanded[ez, ey, ex]

    def to_expanded(self, state: Array) -> Array:
        n = self.frac.side(self.r)
        cx, cy, cz = self._compact_grid()
        ex, ey, ez = f3.lambda3_map(self.frac, self.r, cx, cy, cz)
        out = jnp.zeros((n, n, n), state.dtype)
        return out.at[ez, ey, ex].set(state)

    @partial(jax.jit, static_argnums=0)
    def step(self, state: Array) -> Array:
        frac, r, wl = self.frac, self.r, self.workload
        cx, cy, cz = self._compact_grid()
        ex, ey, ez = f3.lambda3_map(frac, r, cx, cy, cz)

        def gather(d):
            nx_, ny_, nz_ = ex + d[0], ey + d[1], ez + d[2]
            valid = f3.is_fractal3(frac, r, nx_, ny_, nz_)
            bx, by, bz = f3.nu3_map(frac, r, nx_, ny_, nz_)
            return jnp.where(valid, state[bz, by, bx],
                             jnp.zeros((), state.dtype))

        agg = weighted_gather_agg(MOORE3_DIRS, wl.weights3d, gather,
                                  state.shape, wl.agg_dtype)
        return wl.apply(state, agg, None).astype(state.dtype)

    def _run_impl(self, state: Array, steps, ops) -> Array:
        return jax.lax.fori_loop(0, steps, lambda _, s: self.step(s), state)

    def run(self, state: Array, steps, donate: bool = False) -> Array:
        """``steps`` steps in one cached jit whose loop bound is a
        *traced* scalar — changing the step count does not recompile
        (the old bare ``fori_loop`` baked the Python int into the
        trace, so every distinct count paid a full retrace; same fix as
        ``SqueezeCellEngine.run``). ``donate=True`` donates the input
        state buffer to XLA — zero-copy steady-state stepping; the
        caller must not reuse ``state`` afterwards."""
        return self._dispatch_run(state, steps, donate)

    def memory_bytes(self) -> int:
        return self.frac.volume(self.r)


@dataclasses.dataclass(frozen=True)
class Squeeze3DBlockEngine(_FusedStepping):
    """3D block-level Squeeze with static 26-direction neighbor tables.

    ``fusion_k`` sets the temporal-fusion depth used by ``run`` (None =
    the shared ``default_fusion_k`` heuristic on rho). The XLA
    ``step_k`` path supports any k >= 1 — depths beyond rho span
    multiple block rings through the depth-k offset tables.
    """

    layout: BlockLayout3D
    workload: StencilWorkload = LIFE3D
    fusion_k: Optional[int] = None

    def __post_init__(self):
        _check_workload(self.workload)
        if self.fusion_k is not None and self.fusion_k < 1:
            raise ValueError(f"fusion_k must be >= 1, got {self.fusion_k}")
        self.layout.materialize()

    @property
    def frac(self) -> f3.NBBFractal3D:
        return self.layout.frac

    @property
    def r(self) -> int:
        return self.layout.r

    def init_random(self, seed: int) -> Array:
        expanded = BB3DEngine(self.frac, self.r,
                              self.workload).init_random(seed)
        return self.layout.from_expanded(expanded)

    def to_expanded(self, state: Array) -> Array:
        return self.layout.to_expanded(state)

    def step(self, state: Array, ops=None) -> Array:
        return self._step(state)

    @partial(jax.jit, static_argnums=0)
    def _step(self, state: Array) -> Array:
        wl = self.workload
        self.layout.materialize_halo(1)
        padded = self.layout.pad_with_halo_k(state, 1)
        agg = weighted_moore_agg3(padded, wl.weights3d, wl.agg_dtype)
        mask = self.layout.dev_micro_mask  # broadcasts over n_blocks
        return wl.apply(state, agg, mask).astype(state.dtype)

    # ------------------------------------------------------ temporal fusion
    def _materialize_fused(self, k: int) -> None:
        self.layout.materialize_halo(k)
        self.layout.materialize_halo(1)  # the remainder path's step()

    def step_k(self, state: Array, k: int, ops=None) -> Array:
        """Advance ``k`` exact steps in one fused computation: one
        depth-k halo assembly, then k in-register substeps on the
        shrinking window (XLA path; any k >= 1, including k > rho)."""
        self.layout.materialize_halo(k)  # host tables outside the trace
        self.layout.materialize_halo(1)
        return self._step_k(state, k)

    @partial(jax.jit, static_argnums=(0, 2))
    def _step_k(self, state: Array, k: int) -> Array:
        wl = self.workload
        padded = self.layout.pad_with_halo_k(state, k)
        hmask = self.layout.dev_halo_mask(k)  # (nb, (rho+2k)^3)
        return wl.tile_rule_k(padded, hmask, k, ndim=3).astype(state.dtype)

    def memory_bytes(self, dtype_size: int = 1) -> int:
        return self.layout.memory_bytes(dtype_size)


@dataclasses.dataclass(frozen=True)
class Squeeze3DPallasEngine(_FusedStepping):
    """3D block-level Squeeze with the step fused into a Pallas kernel.

    ``variant`` selects the kernel of kernels/squeeze_stencil3d.py:
    'fused' (depth-k window assembled in VMEM, k substeps, one write)
    or 'mxu' (z-slab banded matmul aggregation on lane-packed
    macro-tiles). State layout and conversions are identical to
    ``Squeeze3DBlockEngine``; ``fusion_k`` must stay <= rho (the
    kernels' one-block-ring limit).
    """

    layout: BlockLayout3D
    workload: StencilWorkload = LIFE3D
    variant: str = "fused"
    fusion_k: Optional[int] = None
    #: MXU macro-tile packing override ('mxu' variant only, None = lane
    #: heuristic)
    macro_p: Optional[int] = None

    def __post_init__(self):
        if self.variant not in ("fused", "mxu"):
            raise ValueError(f"unknown 3D Pallas variant {self.variant!r}")
        _check_workload(self.workload)
        if self.fusion_k is not None and not (
                1 <= self.fusion_k <= self.layout.rho):
            raise ValueError(
                f"pallas fusion_k must be in [1, rho={self.layout.rho}], "
                f"got {self.fusion_k}")
        if self.macro_p is not None and self.variant != "mxu":
            raise ValueError(
                "macro_p only applies to the 'mxu' variant, got "
                f"variant={self.variant!r}")
        self.layout.materialize()

    @property
    def frac(self) -> f3.NBBFractal3D:
        return self.layout.frac

    @property
    def r(self) -> int:
        return self.layout.r

    def init_random(self, seed: int) -> Array:
        return Squeeze3DBlockEngine(self.layout,
                                    self.workload).init_random(seed)

    def to_expanded(self, state: Array) -> Array:
        return self.layout.to_expanded(state)

    def step(self, state: Array, ops=None) -> Array:
        return self.step_k(state, 1)

    # ------------------------------------------------------ temporal fusion
    def _materialize_fused(self, k: int) -> None:
        # only what the fused kernels read — not the XLA path's
        # per-block halo_mask/offset_table host build
        for kk in {1, k}:  # k and the remainder path's single step
            _ = self.layout.dev_existence_table
            _ = self.layout.dev_window_mask(kk)
            if self.variant == "mxu":
                p = self.layout.macro_tiles(kk, p=self.macro_p)[0]
                _ = self.layout.dev_existence_padded(kk, p=p)

    def step_k(self, state: Array, k: int, ops=None) -> Array:
        """Advance ``k`` exact steps in one fused kernel launch
        (k <= rho)."""
        from repro.kernels import squeeze_stencil3d as k3
        if self.variant == "mxu":
            return k3.stencil3d_step_mxu_k(self.layout, state,
                                           self.workload, k=k,
                                           p=self.macro_p)
        return k3.stencil3d_step_fused_k(self.layout, state, self.workload,
                                         k=k)

    def memory_bytes(self, dtype_size: int = 1) -> int:
        return self.layout.memory_bytes(dtype_size)
