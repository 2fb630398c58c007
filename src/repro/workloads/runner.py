"""Batched multi-fractal simulation runtime.

Serving many concurrent fractal simulations means many independent initial
states over a small set of static configurations. The configuration
identity is :class:`repro.tuning.spec.EngineSpec` — the same object that
keys the serving buckets and the autotuner's tables — and this module
provides the building block:

  * one compiled step per static configuration, vmapped over a leading
    batch axis of independent states (B simulations advance in one XLA
    call); the 'pallas-mxu' kind instead dispatches ONE kernel over a
    native (B, n_macro_tiles) grid (``supports_native_batch`` on the
    engine), sharing the block tables across the batch — the vmap path
    stays as the fallback for every other kind;
  * fused multi-step serving: ``run`` tiles the step count into
    floor(steps/k) vmapped k-step launches (temporal fusion over the
    engines' depth-k halos) plus a single-step remainder; the fusion
    depth is part of the cache key (None resolves through the tuning
    table, then the static heuristic — ``EngineSpec.normalize()`` — so
    the default and an equal explicit depth share one entry);
  * zero-copy steady-state stepping: ``run(..., donate=True)`` routes
    through a ``donate_argnums`` jit so XLA reuses the incoming batch
    buffer for the output (the caller must not touch the input after);
  * an LRU cache of those compiled engines keyed by the NORMALIZED spec,
    so a serving process pays tracing/compilation once per
    configuration, not once per request;
  * multi-device placement: with a ``mesh``, regular kinds shard the
    BATCH axis (whole simulations spread across devices — many small
    fractals) while the 'dist-*' kinds shard the BLOCK axis (one fractal
    too large per device, k-fused strip halo exchange — see
    core/distributed.py and DESIGN.md Section 4); the mesh shape and
    fusion depth are part of the cache key;
  * trace/build counters (``RunnerStats``) so reuse is *testable* — the
    suite asserts >= 8 concurrent simulations share one compiled engine.

Every public method accepts either an ``EngineSpec`` first —
``run(spec, states, steps)`` — or the legacy argument list
``run(kind, frac, r, states, steps, ...)``; both flow through the one
normalization path (``EngineSpec.normalize()``), so a spec call and the
equivalent legacy call share one compiled entry. Custom (non-registry)
fractals are identified by their position mask; custom workloads are
identified by ``workload.name`` and must be passed as objects through
the legacy form (give them unique names — the cache cannot distinguish
two different workloads sharing one name).

The runner is dimension-agnostic: the 3D kinds ('bb3d' | 'cell3d' |
'block3d' | 'pallas-3d' | 'pallas-3d-mxu') dispatch states with 3D
spatial trailing axes — (B, nx, ny, nz) cell states, (B, n_blocks, rho,
rho, rho) block states — through the same vmapped-step/fused-run/LRU
machinery; 'block3d' and 'pallas-3d*' are block kinds, so the fusion
depth participates in their cache key exactly as in 2D.

See DESIGN.md Sections 3 and 11.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict
from typing import TYPE_CHECKING, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.tuning.spec import EngineSpec, is_dist_kind
from repro.workloads.base import StencilWorkload
from repro.workloads.rules import LIFE

if TYPE_CHECKING:  # annotation-only; keeps runtime free of core imports
    from repro.core.fractals import NBBFractal

Array = jnp.ndarray

#: the cache identity of one simulation family: a *normalized*
#: EngineSpec — the same object serving buckets and tuning tables key on
Key = EngineSpec


def _is_dist(kind: str) -> bool:
    """Multi-device engine kinds (block-axis sharding over a mesh)."""
    return is_dist_kind(kind)


@dataclasses.dataclass
class RunnerStats:
    """Legacy per-runner counters (kept: cheap, always on, asserted by
    the reuse tests). The labeled equivalents land on the telemetry
    registry when ``SQUEEZE_TELEMETRY`` is enabled: ``runner.cache.{hit,
    miss,evict}``, ``runner.build`` / ``runner.trace`` (per-key compile
    counts), ``runner.runs`` + ``runner.run.seconds`` latency
    histograms, ``runner.batch_size`` / ``runner.steps`` histograms —
    see DESIGN.md Section 7."""

    builds: int = 0    # engines constructed (LRU misses)
    traces: int = 0    # jax traces of the batched step (recompilations)
    evictions: int = 0


@dataclasses.dataclass
class _Entry:
    engine: object
    batched_step: callable
    batched_run: callable
    batched_run_donated: callable
    #: the engine's device tables, passed to every compiled call as an
    #: argument (never closed over: jit would bake them in as constants)
    ops: object = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class _Resolved:
    """One normalized configuration plus the objects ``_build`` needs
    (the spec alone cannot carry custom fractal/workload objects or a
    live mesh)."""

    spec: EngineSpec          # normalized: THE cache key
    frac: object
    workload: StencilWorkload
    mesh: object              # live Mesh or None


class BatchedRunner:
    """LRU cache of compiled batched engines keyed by normalized
    EngineSpec.

    Thread-safe: the serving layer (``repro.serving``) drives one runner
    from many worker threads, including abandoned hang threads that may
    race a fresh retry. Cache lookups/inserts/evictions hold an RLock;
    a cold build runs *outside* the lock behind a per-key build event,
    so (a) concurrent misses on the same key build the engine exactly
    once (the losers wait, then take the cache hit) and (b) a
    multi-second trace never blocks warm hits on other keys.
    """

    def __init__(self, capacity: int = 16):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self.stats = RunnerStats()
        self._cache: "OrderedDict[Key, _Entry]" = OrderedDict()
        self._lock = threading.RLock()
        self._building: Dict[Key, threading.Event] = {}

    # ------------------------------------------------------------- cache
    def _resolve(self, kind, frac=None, r: Optional[int] = None,
                 m: int = 0, workload: Optional[StencilWorkload] = None,
                 k: Optional[int] = None, mesh=None, axis: str = "data",
                 exchange: str = "auto") -> _Resolved:
        """THE normalization path: spec or legacy args in, normalized
        spec + build objects out. ``EngineSpec.normalize()`` does the
        alias rewrite, the non-block/non-dist knob zeroing, and the
        explicit > table > heuristic knob resolution; an explicit
        ``k < 1`` raises here, before any cache traffic."""
        if isinstance(kind, EngineSpec):
            # frac/workload/mesh act as OBJECT overrides here (custom
            # workloads are registry-invisible; the serving layer passes
            # the request's own objects) — identity still comes from the
            # spec alone
            norm = kind.normalize()
            return _Resolved(
                spec=norm,
                frac=frac if frac is not None else norm.build_frac(),
                workload=(workload if workload is not None
                          else norm.build_workload()),
                mesh=mesh if mesh is not None else norm.build_mesh())
        workload = LIFE if workload is None else workload
        spec = EngineSpec.from_args(kind, frac, r, m, workload,
                                    fusion_k=k, mesh=mesh, axis=axis,
                                    exchange=exchange)
        norm = spec.normalize()
        return _Resolved(spec=norm, frac=frac, workload=workload,
                         mesh=mesh)

    def _get(self, res: _Resolved) -> _Entry:
        key = res.spec
        while True:
            with self._lock:
                entry = self._cache.get(key)
                if entry is not None:
                    self._cache.move_to_end(key)
                    obs.inc("runner.cache.hit", kind=key.kind)
                    return entry
                ev = self._building.get(key)
                if ev is None:
                    # we build; racing threads wait on the event, then
                    # re-check the cache (or, if we failed/were evicted
                    # already, one of them becomes the next builder)
                    self._building[key] = threading.Event()
                    break
            ev.wait()
        try:
            entry = self._build(res)
            return self._insert(key, entry)
        finally:
            with self._lock:
                self._building.pop(key).set()

    def _build(self, res: _Resolved) -> _Entry:
        """Construct + wrap the engine for ``res`` (no lock held: engine
        construction and jax tracing can take seconds)."""
        spec = res.spec
        kind, k, workload = spec.kind, spec.fusion_k, res.workload
        obs.inc("runner.cache.miss", kind=kind)
        obs.inc("runner.build", kind=kind, workload=spec.workload, k=k)
        from repro.core.stencil import make_engine
        # the spec is already normalized — build with the table DISABLED
        # so make_engine does not re-consult it (one consult, and one
        # engine.tune.* outcome, per runner call; none per build)
        engine = make_engine(spec, frac=res.frac, workload=workload,
                             mesh=res.mesh, table=None)
        if _is_dist(kind):
            # the distributed engine owns its jit cache, its fused-launch
            # tiling (exactly ceil(steps/k) collectives) and its exchange
            # accounting — the runner must not wrap it in another jit, or
            # the Python-side collective counters would only run at trace
            # time. Its step/run handle (B, C?, nb_padded, rho, rho)
            # natively (one batched strip all-gather per launch).
            return _Entry(engine,
                          lambda states, ops: engine.step_batched(states),
                          lambda states, steps, ops: engine.run(
                              states, int(steps)),
                          lambda states, steps, ops: engine.run(
                              states, int(steps), donate=True))
        fused = spec.is_block and k > 1
        stats = self.stats
        # the v5 'mxu' engine advances the whole batch through ONE kernel
        # dispatch over a (B, n_macro_tiles) grid — the block tables are
        # shared across the batch instead of re-staged per simulation by a
        # vmap of pallas_call; every other kind keeps the vmap path
        native = getattr(engine, "supports_native_batch", False)

        def trace_tick():
            """Runs only while tracing; cached calls skip it. Mirrored
            onto the registry so retrace regressions are assertable per
            (kind, workload, k) without a runner handle."""
            stats.traces += 1
            obs.inc("runner.trace", kind=kind, workload=spec.workload,
                    k=k)

        def traced_step(state, ops):
            trace_tick()
            # engines without device tables (cell/bb/lambda) take none
            return engine.step(state, ops) if ops else engine.step(state)

        def traced_step_k(state, ops):
            trace_tick()
            return engine.step_k(state, k, ops)

        def traced_batch_step(states, ops):
            trace_tick()
            return engine.step_batched(states, ops)

        def traced_batch_step_k(states, ops):
            trace_tick()
            return engine.step_k_batched(states, k, ops)

        def vmapped(fn):  # batch axis on the states only, shared tables
            return jax.vmap(fn, in_axes=(0, None))

        body = traced_batch_step if native else vmapped(traced_step)
        body_k = (traced_batch_step_k if native
                  else vmapped(traced_step_k))

        def _run(states, steps, ops):
            if fused:
                states = jax.lax.fori_loop(
                    0, steps // k, lambda _, s: body_k(s, ops), states)
                return jax.lax.fori_loop(
                    0, steps % k, lambda _, s: body(s, ops), states)
            return jax.lax.fori_loop(
                0, steps, lambda _, s: body(s, ops), states)

        ops = engine.operands() if hasattr(engine, "operands") else {}
        return _Entry(engine, jax.jit(body), jax.jit(_run),
                      jax.jit(_run, donate_argnums=0), ops)

    def _insert(self, key: Key, entry: _Entry) -> _Entry:
        """Shared cache insert + build accounting + LRU eviction."""
        with self._lock:
            self._cache[key] = entry
            self.stats.builds += 1
            if len(self._cache) > self.capacity:
                self._cache.popitem(last=False)
                self.stats.evictions += 1
                obs.inc("runner.cache.evict")
        return entry

    def is_cached(self, kind, frac=None, r: Optional[int] = None,
                  m: int = 0, workload: Optional[StencilWorkload] = None,
                  k: Optional[int] = None, mesh=None,
                  axis: str = "data", exchange: str = "auto") -> bool:
        """Whether this configuration is a warm cache hit right now
        (no build, no LRU touch) — the serving layer's admission
        control uses this to bound concurrent cold compiles. Accepts a
        spec (``is_cached(spec)``) or legacy args."""
        res = self._resolve(kind, frac, r, m, workload, k, mesh, axis,
                            exchange)
        with self._lock:
            return res.spec in self._cache

    def invalidate(self, kind, frac=None, r: Optional[int] = None,
                   m: int = 0, workload: Optional[StencilWorkload] = None,
                   k: Optional[int] = None, mesh=None,
                   axis: str = "data", exchange: str = "auto") -> bool:
        """Drop one compiled entry (if cached): the serving layer's
        engine-restart path after a watchdog-detected hang — the next
        ``run`` rebuilds from scratch. Returns True if an entry was
        evicted. Accepts a spec or legacy args."""
        res = self._resolve(kind, frac, r, m, workload, k, mesh, axis,
                            exchange)
        with self._lock:
            entry = self._cache.pop(res.spec, None)
            if entry is not None:
                obs.inc("runner.cache.invalidate", kind=res.spec.kind)
            return entry is not None

    def engine_for(self, kind, frac=None, r: Optional[int] = None,
                   m: int = 0, workload: Optional[StencilWorkload] = None,
                   k: Optional[int] = None, mesh=None, axis: str = "data",
                   exchange: str = "auto"):
        """The (cached) underlying single-simulation engine. ``exchange``
        picks the dist-* halo-exchange mode ('auto' | 'p2p' | 'gather';
        ignored — and normalized out of the cache key — for
        single-device kinds). ``step``/``run`` use the 'auto' default,
        which resolves through the tuning table, then to the
        neighbor-only p2p exchange whenever the mesh supports it.
        Accepts a spec (``engine_for(spec)``) or legacy args."""
        return self._get(self._resolve(kind, frac, r, m, workload, k,
                                       mesh, axis, exchange)).engine

    def cache_size(self) -> int:
        return len(self._cache)

    # --------------------------------------------------------- mesh placement
    @staticmethod
    def place_batch(states: Array, mesh, axis: str = "data") -> Array:
        """Shard a batch of independent simulations over ``mesh``'s
        ``axis`` along the BATCH dimension (each device owns whole
        simulations — no halo traffic; the right placement for many small
        fractals). For one fractal too large per device, use the
        'dist-*' kinds instead: they shard the BLOCK axis and exchange
        k-fused halo strips (see DESIGN.md Section 4)."""
        from jax.sharding import NamedSharding, PartitionSpec
        spec = PartitionSpec(axis, *([None] * (states.ndim - 1)))
        return jax.device_put(states, NamedSharding(mesh, spec))

    # ---------------------------------------------------------- batched API
    def init_batch(self, kind, frac=None, r: Optional[int] = None,
                   seeds=None, m: int = 0,
                   workload: Optional[StencilWorkload] = None,
                   mesh=None, axis: str = "data") -> Array:
        """Stack independent initial states: (B, *state_shape). With a
        ``mesh``, 'dist-*' kinds come back sharded over the BLOCK axis
        (one fractal spread across devices); every other kind is sharded
        over the BATCH axis (whole simulations spread across devices).
        Spec form: ``init_batch(spec, seeds, mesh=...)``."""
        if isinstance(kind, EngineSpec) and seeds is None:
            seeds, frac = frac, None  # init_batch(spec, seeds) form
        res = self._resolve(kind, frac, r, m, workload, None, mesh, axis)
        engine = self._get(res).engine
        if _is_dist(res.spec.kind):
            return engine.init_batch(seeds)
        states = jnp.stack([engine.init_random(int(s)) for s in seeds])
        if res.mesh is not None:
            states = self.place_batch(states, res.mesh, axis)
        return states

    def step(self, kind, frac=None, r: Optional[int] = None,
             states: Optional[Array] = None, m: int = 0,
             workload: Optional[StencilWorkload] = None,
             mesh=None, axis: str = "data") -> Array:
        """One step of B independent simulations, one compiled call.
        Spec form: ``step(spec, states)``."""
        if isinstance(kind, EngineSpec) and states is None:
            states, frac = frac, None  # step(spec, states) form
        res = self._resolve(kind, frac, r, m, workload, None, mesh, axis)
        entry = self._get(res)
        return entry.batched_step(states, entry.ops)

    def run(self, kind, frac=None, r: Optional[int] = None,
            states: Optional[Array] = None, steps: Optional[int] = None,
            m: int = 0, workload: Optional[StencilWorkload] = None,
            k: Optional[int] = None, donate: bool = False,
            mesh=None, axis: str = "data") -> Array:
        """``steps`` steps of B independent simulations, tiled into
        floor(steps/k) fused k-step launches plus a steps%k single-step
        remainder (``k=None``: tuning table, then the engine heuristic;
        non-block kinds step singly). ``steps`` is a dynamic fori_loop
        bound: changing it does not retrace (the 'dist-*' kinds instead
        tile in the engine so the collective count is exactly
        ceil(steps/k); their remainder launch compiles once per distinct
        steps%k, bounded by k). ``donate=True`` hands the ``states``
        buffer to XLA for in-place reuse — zero-copy steady-state
        stepping; the caller must not use ``states`` afterwards.
        Spec form: ``run(spec, states, steps, donate=...)``.

        With telemetry enabled, each call records a ``runner.run.seconds``
        wall-time histogram sample (dispatch latency: time to hand the
        work to XLA, not device completion on async backends) plus batch
        size / step-count histograms, all labeled by ``kind``."""
        if isinstance(kind, EngineSpec) and states is None:
            states, steps, frac, r = frac, r, None, None
        t0 = time.perf_counter() if obs.enabled() else None
        res = self._resolve(kind, frac, r, m, workload, k, mesh, axis)
        entry = self._get(res)
        label = res.spec.kind
        fn = entry.batched_run_donated if donate else entry.batched_run
        with obs.span("runner.run", kind=label, steps=int(steps)):
            out = fn(states, jnp.asarray(steps, jnp.int32), entry.ops)
        if t0 is not None:
            obs.observe("runner.run.seconds",
                        time.perf_counter() - t0, kind=label)
            obs.observe("runner.batch_size", int(states.shape[0]),
                        kind=label)
            obs.observe("runner.steps", int(steps), kind=label)
            obs.inc("runner.runs", kind=label)
            if donate:
                obs.inc("runner.donated_runs", kind=label)
        return out

    def compile_run(self, spec: EngineSpec, states, donate: bool = False,
                    frac=None, workload: Optional[StencilWorkload] = None,
                    mesh=None):
        """Compile ``run(spec, states, ...)`` for this batch shape ahead
        of the launch, so the launch itself starts warm; returns the
        compiled executable. None for kinds that compile inside their
        own ``run`` (the 'dist-*' engines own their jit cache and tile
        their launches)."""
        res = self._resolve(spec, frac, None, 0, workload, None, mesh)
        entry = self._get(res)
        fn = entry.batched_run_donated if donate else entry.batched_run
        if not hasattr(fn, "lower"):
            return None
        return fn.lower(jax.ShapeDtypeStruct(states.shape, states.dtype),
                        jax.ShapeDtypeStruct((), jnp.int32),
                        entry.ops).compile()

    def to_expanded(self, kind, frac=None, r: Optional[int] = None,
                    states: Optional[Array] = None, m: int = 0,
                    workload: Optional[StencilWorkload] = None,
                    mesh=None, axis: str = "data") -> Array:
        """Batched conversion to the (B, C?, n, n) expanded embedding.
        Spec form: ``to_expanded(spec, states)``."""
        if isinstance(kind, EngineSpec) and states is None:
            states, frac = frac, None
        res = self._resolve(kind, frac, r, m, workload, None, mesh, axis)
        engine = self._get(res).engine
        if hasattr(engine, "to_expanded"):
            return jax.vmap(engine.to_expanded)(states)
        return states  # BB/lambda states are already expanded


#: process-wide default runner (a serving process wants exactly one cache)
_DEFAULT: Optional[BatchedRunner] = None


def default_runner() -> BatchedRunner:
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = BatchedRunner()
    return _DEFAULT
