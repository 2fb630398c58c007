"""Squeeze engines: stencil simulation entirely in compact space (paper
Sections 3.2-3.5).

  * ``SqueezeCellEngine``  — the paper-faithful per-cell scheme: one lambda
    per cell, one (fused) nu + membership test per neighbor, gathers from
    the compact state. Memory = k^r cells.
  * ``SqueezeBlockEngine`` — block-level Squeeze (Section 3.5): maps run at
    block granularity; each block is a rho x rho expanded micro-fractal.
    The static block-neighbor table (built once with the maps; see
    DESIGN.md Section 2 for the TPU-native restructure) turns the step
    into halo-gather + dense in-tile stencil.
  * ``SqueezePallasEngine`` — the block engine with its step fused into
    one of the Pallas kernels (kernels/squeeze_stencil.py).

Every engine is parameterized by a ``StencilWorkload`` (default: the
paper's game of life); multi-channel workloads carry a leading channel
axis (cell state (C, rows, cols); block state (C, n_blocks, rho, rho)).
All engines produce states convertible to the same expanded embedding as
the baselines (tests assert step-for-step equivalence).

Temporal fusion: the block engines additionally expose ``step_k`` (k
exact steps per launch via depth-k halos; DESIGN.md Section 2) and their
``run(state, steps)`` tiles the step count into ceil(steps/k) fused
launches plus a single-step remainder, with ``k`` chosen by the static
``default_fusion_k`` heuristic unless the engine's ``fusion_k`` field
overrides it. ``run(..., donate=True)`` donates the stepped state buffer
to XLA (zero-copy steady-state stepping).
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import maps
from repro.core.baselines import (BBEngine, _moore_counts,  # noqa: F401
                                  life_rule)
from repro.core.compact import (BlockLayout, MOORE_DIRS, compact_meshgrid,
                                compact_to_expanded, window_chunk)
from repro.core.fractals import NBBFractal
from repro.workloads.base import (StencilWorkload, cell_bits,
                                  check_workload_ndim, weighted_gather_agg,
                                  weighted_moore_agg)
from repro.workloads.rules import LIFE

Array = jnp.ndarray


def default_fusion_k(rho: int) -> int:
    """Static temporal-fusion depth heuristic for a rho x rho block tile.

    The fused working window is (rho+2k)^2, so deeper fusion trades
    redundant halo-ring compute for ~k-fold amortization of dispatch,
    table gathers and center HBM traffic. Small tiles can't afford a deep
    ring (rho < 2 -> no fusion); big tiles amortize a ring of 3 easily.
    Always <= rho, so the heuristic depth is valid for the Pallas fused-k
    kernel as well as the XLA path. Explicit ``fusion_k`` on the engines
    (or ``k=`` on the runner) overrides this.
    """
    if rho < 2:
        return 1
    return 3 if rho >= 8 else 2


class _CachedRun:
    """Cached-jit run machinery: hosts define ``_run_impl(state, steps,
    ops)`` with a *traced* steps scalar, and their ``run`` dispatches
    through ``_dispatch_run`` — one plain and one ``donate_argnums``
    compilation per engine value, neither retracing when the step count
    changes.

    ``operands()`` is the pytree of device tables the traced step reads
    (the block-neighbor table, ...). It is passed into every compiled
    program as an argument: a table closed over by ``jit`` would be
    baked into the executable as a constant (51 MB of neighbor table at
    r=17), so the steps take it as ``ops`` instead.

    The ``engine.trace`` counter increments only while jax traces the
    body (cached dispatches skip it), so the telemetry registry turns
    "changing the step count must not retrace" into an assertable
    invariant (see tests/test_obs.py; counts appear only if telemetry
    was enabled at trace time)."""

    def operands(self):
        """Device tables the traced steps read (none by default)."""
        return {}

    @partial(jax.jit, static_argnums=0)
    def _run(self, state: Array, steps, ops) -> Array:
        obs.inc("engine.trace", engine=type(self).__name__, fn="run")
        return self._run_impl(state, steps, ops)

    @partial(jax.jit, static_argnums=0, donate_argnums=1)
    def _run_donated(self, state: Array, steps, ops) -> Array:
        obs.inc("engine.trace", engine=type(self).__name__,
                fn="run_donated")
        return self._run_impl(state, steps, ops)

    def _dispatch_run(self, state: Array, steps, donate: bool) -> Array:
        fn = self._run_donated if donate else self._run
        return fn(state, jnp.asarray(steps, jnp.int32), self.operands())


class _FusedStepping(_CachedRun):
    """Temporal-fusion run machinery shared by the block engines.

    Hosts require a ``layout``, a ``fusion_k`` field, ``step(state,
    ops=None)`` and ``step_k(state, k, ops=None)``; hosts whose k-step
    body reads host-built geometry outside ``operands()`` override
    ``_materialize_fused(k)`` to build it outside any trace.
    """

    @property
    def effective_fusion_k(self) -> int:
        if self.fusion_k is not None:
            return self.fusion_k
        return default_fusion_k(self.layout.rho)

    def _materialize_fused(self, k: int) -> None:
        pass

    def _run_impl(self, state: Array, steps, ops) -> Array:
        k = self.effective_fusion_k
        if k <= 1:
            return jax.lax.fori_loop(0, steps,
                                     lambda _, s: self.step(s, ops), state)
        state = jax.lax.fori_loop(0, steps // k,
                                  lambda _, s: self.step_k(s, k, ops),
                                  state)
        return jax.lax.fori_loop(0, steps % k,
                                 lambda _, s: self.step(s, ops), state)

    def run(self, state: Array, steps, donate: bool = False) -> Array:
        """``steps`` steps, tiled into floor(steps/k) fused k-step launches
        plus a steps%k single-step remainder (``steps`` stays a dynamic
        loop bound: changing it does not retrace). ``donate=True`` donates
        the input state buffer to XLA — zero-copy steady-state stepping;
        the caller must not reuse ``state`` afterwards.

        With telemetry enabled, each call counts its fused launches,
        remainder single steps and donation usage on the registry
        (``engine.fused_launches`` / ``engine.single_steps`` /
        ``engine.donated_runs``, labeled by engine class + Pallas
        variant)."""
        k = self.effective_fusion_k
        if k > 1:                 # the k<=1 path never touches halo tables
            self._materialize_fused(k)
        if obs.enabled():
            n = int(steps)
            lbl = dict(engine=type(self).__name__,
                       variant=getattr(self, "variant", ""))
            obs.inc("engine.runs", **lbl)
            obs.inc("engine.steps", n, **lbl)
            if k > 1:
                obs.inc("engine.fused_launches", n // k, **lbl)
                obs.inc("engine.single_steps", n % k, **lbl)
            else:
                obs.inc("engine.single_steps", n, **lbl)
            if donate:
                obs.inc("engine.donated_runs", **lbl)
        return self._dispatch_run(state, steps, donate)


@dataclasses.dataclass(frozen=True)
class SqueezeCellEngine(_CachedRun):
    """Paper-faithful compact-space engine (thread-level Squeeze)."""

    frac: NBBFractal
    r: int
    workload: StencilWorkload = LIFE

    def __post_init__(self):
        check_workload_ndim(self.workload, 2)

    def init_random(self, seed: int) -> Array:
        cx, cy = compact_meshgrid(self.frac, self.r)
        ex, ey = maps.lambda_map(self.frac, self.r, cx, cy)
        return self.workload.init(cell_bits(seed, ex, ey))

    def to_expanded(self, state: Array) -> Array:
        return compact_to_expanded(self.frac, self.r, state)

    @partial(jax.jit, static_argnums=0)
    def step(self, state: Array) -> Array:
        frac, r, wl = self.frac, self.r, self.workload
        cx, cy = compact_meshgrid(frac, r)
        # 1 lambda per cell: where am I in (virtual) expanded space?
        ex, ey = maps.lambda_map(frac, r, cx, cy)

        def gather(d):
            # 1 nu (+ membership, fused — same digit pass) per neighbor
            nx, ny, valid = maps.nu_with_membership(
                frac, r, ex + d[0], ey + d[1])
            return jnp.where(valid, state[..., ny, nx],
                             jnp.zeros((), state.dtype))

        agg = weighted_gather_agg(MOORE_DIRS, wl.weights2d, gather,
                                  state.shape[:-2] + ex.shape, wl.agg_dtype)
        # every compact cell is a fractal cell: no mask
        return wl.apply(state, agg, None).astype(state.dtype)

    def _run_impl(self, state: Array, steps, ops) -> Array:
        return jax.lax.fori_loop(0, steps, lambda _, s: self.step(s), state)

    def run(self, state: Array, steps, donate: bool = False) -> Array:
        """``steps`` steps in one cached jit whose loop bound is a *traced*
        scalar — changing the step count does not recompile (the old
        bare ``fori_loop`` baked the Python int into the trace, so every
        distinct count paid a full retrace). ``donate=True`` donates the
        input state buffer to XLA (zero-copy steady-state stepping; the
        caller must not reuse ``state`` afterwards) — same signature as
        the block engines' ``run``."""
        return self._dispatch_run(state, steps, donate)

    def memory_bytes(self, dtype_size: int = 1) -> int:
        rows, cols = self.frac.compact_dims(self.r)
        return self.workload.n_channels * rows * cols * dtype_size


@dataclasses.dataclass(frozen=True)
class SqueezeBlockEngine(_FusedStepping):
    """Block-level Squeeze (paper Section 3.5) with a static neighbor table.

    ``fusion_k`` sets the temporal-fusion depth used by ``run`` (None =
    the ``default_fusion_k`` heuristic). The XLA ``step_k`` path supports
    any k >= 1 — depths beyond rho span multiple block rings through the
    depth-k offset tables.
    """

    layout: BlockLayout
    workload: StencilWorkload = LIFE
    fusion_k: Optional[int] = None

    def __post_init__(self):
        check_workload_ndim(self.workload, 2)
        if self.fusion_k is not None and self.fusion_k < 1:
            raise ValueError(f"fusion_k must be >= 1, got {self.fusion_k}")
        self.layout.materialize()

    @property
    def frac(self) -> NBBFractal:
        return self.layout.frac

    @property
    def r(self) -> int:
        return self.layout.r

    def init_random(self, seed: int) -> Array:
        return self.layout.init_state(self.workload, seed)

    def to_expanded(self, state: Array) -> Array:
        return self.layout.to_expanded(state)

    def operands(self):
        """The neighbor table (and, for fusion deeper than one block
        ring, the depth-k offset table) as traced step operands."""
        ops = {"table": self.layout.dev_neighbor_table}
        k = self.effective_fusion_k
        if self.layout.halo_block_radius(k) > 1:
            ops["table_k"] = self.layout.dev_offset_table(k)
        return ops

    def _table_k(self, k: int, ops) -> Array:
        if ops is None:
            return self.layout.dev_offset_table(k)
        if self.layout.halo_block_radius(k) == 1:
            return ops["table"]
        if "table_k" in ops and ops["table_k"].shape[1] == len(
                self.layout.halo_offsets(k)):
            return ops["table_k"]
        return self.layout.dev_offset_table(k)

    def step(self, state: Array, ops=None) -> Array:
        return self._step(state, self._table_k(1, ops))

    @partial(jax.jit, static_argnums=0)
    def _step(self, state: Array, table: Array) -> Array:
        wl, layout = self.workload, self.layout
        mask = jnp.asarray(layout.micro_mask)

        def update(center, nbr, rows):
            padded = layout.halo_window_k(center, nbr, 1)
            agg = weighted_moore_agg(padded, wl.weights2d, wl.agg_dtype)
            return wl.apply(center, agg, mask).astype(center.dtype)

        return layout.map_chunks(state, table, self._chunk(1), update)

    def _chunk(self, k: int) -> int:
        return window_chunk(self.layout.rho, k, self.workload.n_channels)

    # ------------------------------------------------------ temporal fusion
    def step_k(self, state: Array, k: int, ops=None) -> Array:
        """Advance ``k`` exact steps in one fused computation: one depth-k
        halo assembly, then k in-register substeps on the shrinking window
        (XLA path; any k >= 1, including k > rho). The per-block window
        occupancy is rebuilt from the offset table on the device
        (``BlockLayout.halo_gate``); blocks are updated in bounded chunks
        (``BlockLayout.map_chunks``)."""
        return self._step_k(state, k, self._table_k(k, ops))

    @partial(jax.jit, static_argnums=(0, 2))
    def _step_k(self, state: Array, k: int, table: Array) -> Array:
        wl, layout = self.workload, self.layout

        def update(center, nbr, rows):
            padded = layout.halo_window_k(center, nbr, k)
            hmask = layout.halo_gate(k, rows)  # (c, rho+2k, rho+2k)
            return wl.tile_rule_k(padded, hmask, k).astype(center.dtype)

        return layout.map_chunks(state, table, self._chunk(k), update)

    def memory_bytes(self, dtype_size: int = 1) -> int:
        return self.workload.n_channels * self.layout.memory_bytes(dtype_size)


@dataclasses.dataclass(frozen=True)
class SqueezePallasEngine(_FusedStepping):
    """Block-level Squeeze with the step fused into a Pallas kernel.

    ``variant`` selects the halo strategy of kernels/squeeze_stencil.py:
    'blocks' (v1, paper-shaped), 'strips' (v2, pre-gathered strip halos),
    'fused' (the v4 depth-k kernel, one substep for single steps) or
    'mxu' (v5, stencil-as-matmul on lane-packed macro-tiles). State
    layout and conversions are identical to ``SqueezeBlockEngine``.
    ``run`` steps through the temporal-fusion kernel (v4
    ``stencil_step_fused_k``, or the v5 k-substep variant for 'mxu')
    whenever the effective fusion depth is > 1; ``fusion_k`` overrides
    the heuristic but must stay <= rho (the kernels' one-block-ring
    limit).

    The 'mxu' variant additionally supports *native batching*
    (``step_batched`` / ``step_k_batched``): B independent simulations
    advance through ONE kernel dispatch over a (B, macro-tile) grid
    instead of a vmap of per-simulation pallas_calls — the
    ``BatchedRunner`` routes through it when ``supports_native_batch``.
    """

    layout: BlockLayout
    workload: StencilWorkload = LIFE
    variant: str = "strips"
    fusion_k: Optional[int] = None
    #: MXU macro-tile packing override (blocks per macro-tile; 'mxu'
    #: variant only, None = lane heuristic)
    macro_p: Optional[int] = None

    def __post_init__(self):
        if self.variant not in ("blocks", "strips", "fused", "mxu"):
            raise ValueError(f"unknown Pallas variant {self.variant!r}")
        check_workload_ndim(self.workload, 2)
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
    def frac(self) -> NBBFractal:
        return self.layout.frac

    @property
    def r(self) -> int:
        return self.layout.r

    def init_random(self, seed: int) -> Array:
        return self.layout.init_state(self.workload, seed)

    def to_expanded(self, state: Array) -> Array:
        return self.layout.to_expanded(state)

    def operands(self):
        """The neighbor table, a traced operand of every kernel launch."""
        return {"table": self.layout.dev_neighbor_table}

    @staticmethod
    def _table(ops):
        return None if ops is None else ops["table"]

    def step(self, state: Array, ops=None) -> Array:
        from repro.kernels import ops as kops
        table = self._table(ops)
        if self.variant == "mxu":
            return kops.stencil_step_mxu(self.layout, state, self.workload,
                                         p=self.macro_p, table=table)
        if self.variant == "fused":
            return kops.stencil_step_fused_k(self.layout, state,
                                             self.workload, k=1,
                                             table=table)
        fn = {"blocks": kops.stencil_step_blocks,
              "strips": kops.stencil_step_strips}[self.variant]
        return fn(self.layout, state, self.workload, table=table)

    # ------------------------------------------------------- native batching
    @property
    def supports_native_batch(self) -> bool:
        """True when B simulations step through one (B, macro-tile)
        kernel grid rather than a vmap of per-simulation pallas_calls."""
        return self.variant == "mxu"

    def step_batched(self, states: Array, ops=None) -> Array:
        """One step of B independent simulations in one kernel dispatch;
        states (B, C?, n_blocks, rho, rho) -> same ('mxu' variant only)."""
        return self.step_k_batched(states, 1, ops)

    def step_k_batched(self, states: Array, k: int, ops=None) -> Array:
        """``k`` exact steps of B independent simulations in one kernel
        dispatch over the (B, macro-tile) grid ('mxu' variant only)."""
        if not self.supports_native_batch:
            raise ValueError(
                f"native batching needs variant='mxu', got {self.variant!r} "
                "(use jax.vmap over step/step_k instead)")
        from repro.kernels import ops as kops
        return kops.stencil_step_mxu_batched(self.layout, states,
                                             self.workload, k=k,
                                             p=self.macro_p,
                                             table=self._table(ops))

    # ------------------------------------------------------ temporal fusion
    def step_k(self, state: Array, k: int, ops=None) -> Array:
        """Advance ``k`` exact steps in one fused kernel launch (k <= rho):
        the v5 macro-tile kernel for 'mxu', the v4 kernel otherwise."""
        from repro.kernels import ops as kops
        table = self._table(ops)
        if self.variant == "mxu":
            return kops.stencil_step_mxu_k(self.layout, state, self.workload,
                                           k=k, p=self.macro_p, table=table)
        return kops.stencil_step_fused_k(self.layout, state, self.workload,
                                         k=k, table=table)

    def memory_bytes(self, dtype_size: int = 1) -> int:
        return self.workload.n_channels * self.layout.memory_bytes(dtype_size)


#: distributed engine kinds -> shard-local compute backend
_DIST_KINDS = {"dist-block": "jnp", "dist-fused": "fused",
               "dist-mxu": "mxu"}

#: sentinel: "normalize against the active default tuning table"
_UNSET_TABLE = object()


def make_engine(kind, frac=None, r: Optional[int] = None, m: int = 0,
                workload: Optional[StencilWorkload] = None,
                fusion_k: Optional[int] = None, mesh=None,
                axis: str = "data", exchange: str = "auto",
                macro_p: Optional[int] = None, table=_UNSET_TABLE):
    """Engine factory. Primary form: ``make_engine(spec)`` with an
    :class:`repro.tuning.spec.EngineSpec` — the canonical configuration
    identity. The spec is ``normalize()``d first (alias rewrite, knob
    zeroing, and tunable-knob resolution: explicit argument > tuning-
    table hit > static heuristic — see DESIGN.md Section 11), so the
    engine's kind/fusion depth/macro-tile packing/exchange mode are the
    resolved values. Registry fractals/workloads and the mesh are
    reconstructed from the spec; pass ``frac=``/``workload=``/``mesh=``
    objects to supply custom ones (they must match the spec's
    identity). ``table=None`` pins normalization to the static
    heuristics (no tuning-table consult).

    Legacy form: ``make_engine(kind, frac, r, m=..., ...)`` with a kind
    string and a fractal object still works — it constructs the spec
    internally and emits a ``DeprecationWarning``.

    kind: 'bb' | 'lambda' | 'cell' | 'block' | 'pallas-blocks' |
          'pallas-strips' | 'pallas-fused' | 'pallas-mxu' |
          'dist-block' | 'dist-fused' | 'dist-mxu' |
          'bb3d' | 'cell3d' | 'block3d' | 'pallas-3d' | 'pallas-3d-mxu'
          ('pallas' = 'pallas-strips', 'pallas-3d' = the fused 3D
          kernel).
    ``m`` (block level, rho = s**m) and ``fusion_k`` (temporal-fusion
    depth for ``run``; None = table-then-heuristic) only apply to the
    block/pallas/dist kinds — the expanded-space and cell engines have
    no block tiles to fuse over. ``macro_p`` overrides the MXU
    macro-tile packing (lane-packed blocks per macro-tile; MXU kinds
    only, None = table-then-lane-heuristic). 'pallas-mxu' is the v5
    stencil-as-matmul kernel: the Moore aggregation runs as rank-1
    banded MXU contractions on lane-packed multi-block macro-tiles with
    a *native* batch grid (``step_batched``) — see DESIGN.md Section
    2.2.

    The 'dist-*' kinds are the multi-device engine of
    ``core/distributed.py``: the compact block domain sharded over
    ``mesh``'s ``axis`` (default: all devices on one "data" axis) with a
    k-fused strip halo exchange (one exchange per k steps; ``exchange``
    picks 'p2p' neighbor-only ppermute with interior/boundary compute
    overlap, the 'gather' all-gather fallback, or 'auto' = p2p whenever
    the strip decomposition is valid) and the named shard-local compute
    backend — 'dist-block' is the XLA window path, 'dist-fused' the v4
    fused-depth kernel, 'dist-mxu' the v5 MXU macro-tile kernel. See
    DESIGN.md Sections 4 and 10.

    The '*3d' kinds take an ``NBBFractal3D`` and a 3D single-channel
    workload (LIFE3D, HEAT3D): 'bb3d'/'cell3d' are the expanded and
    per-cell compact engines, 'block3d' the 3D block engine over
    ``BlockLayout3D`` (XLA path, any fusion depth), 'pallas-3d' the
    fused depth-k 3D kernel and 'pallas-3d-mxu' the z-slab MXU
    stencil-as-matmul kernel (both k <= rho). See DESIGN.md Section 5.

    With telemetry enabled, every build counts ``engine.builds`` and
    sets the ``engine.memory_bytes`` gauge (compact-state footprint at
    the workload dtype), both labeled by the *normalized* kind (so
    'pallas' callers and runner users agree on the label).
    """
    from repro.tuning.spec import EngineSpec
    if isinstance(kind, EngineSpec):
        spec = kind
    else:
        warnings.warn(
            "make_engine(kind, frac, r, ...) with a kind string is "
            "deprecated; build an EngineSpec and call make_engine(spec) "
            "(see DESIGN.md Section 11)",
            DeprecationWarning, stacklevel=2)
        if frac is None or r is None:
            raise TypeError(
                "legacy make_engine(kind, frac, r, ...) needs a fractal "
                "object and r")
        spec = EngineSpec.from_args(kind, frac, r, m, workload, fusion_k,
                                    macro_p, mesh, axis, exchange)
    norm = spec.normalize() if table is _UNSET_TABLE \
        else spec.normalize(table=table)
    frac_obj = frac if frac is not None else norm.build_frac()
    workload_obj = workload if workload is not None \
        else norm.build_workload()
    mesh_obj = mesh if mesh is not None else norm.build_mesh()
    engine = _make_engine(norm, frac_obj, workload_obj, mesh_obj)
    if obs.enabled():
        obs.inc("engine.builds", kind=norm.kind)
        if hasattr(engine, "memory_bytes"):
            try:
                itemsize = jnp.dtype(workload_obj.dtype).itemsize
                obs.set_gauge("engine.memory_bytes",
                              engine.memory_bytes(dtype_size=itemsize),
                              kind=norm.kind)
            except TypeError:  # engines with a fixed internal dtype
                obs.set_gauge("engine.memory_bytes",
                              engine.memory_bytes(), kind=norm.kind)
    return engine


def _make_engine(spec, frac, workload, mesh):
    """Dispatch a *normalized* EngineSpec plus the resolved fractal/
    workload/mesh objects to the engine classes."""
    from repro.core.baselines import LambdaEngine
    kind, r, m = spec.kind, spec.r, spec.m
    fusion_k, macro_p = spec.fusion_k, spec.macro_p
    if kind in ("bb3d", "cell3d", "block3d") or kind.startswith("pallas-3d"):
        from repro.core import stencil3d as s3
        from repro.core.compact3d import BlockLayout3D
        if kind == "bb3d":
            return s3.BB3DEngine(frac, r, workload)
        if kind == "cell3d":
            return s3.Squeeze3DEngine(frac, r, workload)
        if kind == "block3d":
            return s3.Squeeze3DBlockEngine(BlockLayout3D(frac, r, m),
                                           workload, fusion_k=fusion_k)
        variant = kind[len("pallas-3d"):].lstrip("-") or "fused"
        return s3.Squeeze3DPallasEngine(BlockLayout3D(frac, r, m),
                                        workload, variant=variant,
                                        fusion_k=fusion_k,
                                        macro_p=macro_p)
    if kind == "bb":
        return BBEngine(frac, r, workload)
    if kind == "lambda":
        return LambdaEngine(frac, r, workload)
    if kind == "cell":
        return SqueezeCellEngine(frac, r, workload)
    if kind == "block":
        return SqueezeBlockEngine(BlockLayout(frac, r, m), workload,
                                  fusion_k=fusion_k)
    if kind in _DIST_KINDS:
        from repro.core.distributed import make_distributed_engine
        return make_distributed_engine(
            BlockLayout(frac, r, m), mesh=mesh, axis=spec.axis,
            workload=workload, compute=_DIST_KINDS[kind],
            fusion_k=fusion_k, exchange=spec.exchange,
            macro_p=macro_p)
    if kind.startswith("pallas-"):
        return SqueezePallasEngine(BlockLayout(frac, r, m), workload,
                                   variant=kind[len("pallas-"):],
                                   fusion_k=fusion_k, macro_p=macro_p)
    raise ValueError(f"unknown engine kind {kind!r}")
