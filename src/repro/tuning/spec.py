"""``EngineSpec`` — the canonical engine-configuration identity.

One frozen, hashable, JSON-serializable dataclass names a simulation
configuration everywhere in the stack:

  * ``make_engine(spec)`` builds an engine from it (core/stencil.py);
  * the ``BatchedRunner`` LRU keys compiled entries on
    ``spec.normalize()`` (workloads/runner.py);
  * ``SimRequest.bucket`` batches serving traffic by it
    (serving/types.py);
  * the tuning table (tuning/table.py) persists autotuned winners
    under ``spec.tuning_key()``.

``normalize()`` is the single normalization code path the runner's old
``_resolve_key``/``_resolve_k`` pair and ``make_engine``'s ``'pallas'``
alias rewrite collapsed into: it rewrites kind aliases, zeroes knobs
that do not apply to the kind (fusion depth on non-block kinds,
exchange/mesh on single-device kinds, macro-tile packing on non-MXU
kinds), and resolves the tunable knobs left ``None`` through the
precedence rule

    explicit argument  >  tuning-table hit  >  static heuristic

counting one ``engine.tune.{hit,miss,fallback}`` telemetry outcome per
table consult. Two configurations batch/cache/serve together exactly
when their normalized specs compare equal.

The fractal identity is ``(s, mask-or-name)``: registry fractals
serialize by name, anything else by its slot-position mask — both
reconstructible via ``build_frac()`` without the original object.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Optional, Tuple, Union

#: every engine kind ``make_engine`` accepts, post-alias (the kind
#: registry; tests iterate this)
KINDS: Tuple[str, ...] = (
    "bb", "lambda", "cell", "block",
    "pallas-blocks", "pallas-strips", "pallas-fused", "pallas-mxu",
    "dist-block", "dist-fused", "dist-mxu",
    "bb3d", "cell3d", "block3d", "pallas-3d", "pallas-3d-mxu",
)

#: kind aliases rewritten by ``canonical()`` — shared by ``make_engine``
#: and the runner so both label telemetry with the same kind string
KIND_ALIASES: Dict[str, str] = {"pallas": "pallas-strips"}

#: kinds with block tiles: these fuse over depth-k halos (same prefix
#: rule the runner used)
_BLOCK_PREFIX = ("block", "pallas", "dist")

#: kinds whose kernels lane-pack P blocks per MXU macro-tile
MXU_KINDS = frozenset({"pallas-mxu", "dist-mxu", "pallas-3d-mxu"})

_EXCHANGES = ("auto", "p2p", "gather")

#: sentinel: "consult the active default tuning table"
_DEFAULT_TABLE = object()

FracId = Union[str, Tuple[Tuple[int, ...], ...]]


#: working set of one row on the block path, in multiples of the row's
#: state bytes. Described-v5e compiles of the block path's batched run
#: (one row, donated, k=3; arguments + output + temporaries - aliased,
#: the neighbor table included) take 3.25 x the row's state for Life on
#: the Sierpinski triangle at r=17 and at r=19 (m=4), and 2.63 x for
#: Gray-Scott on the carpet at r=9 (m=2); the service keeps the row's
#: own state resident beside the run, one more
BLOCK_ROW_WORKING_SET = 4.25


def auto_kind(state_bytes: int, bytes_limit: Optional[int],
              n_devices: int) -> str:
    """The engine kind a request of kind ``'auto'`` runs: ``'dist-block'``
    (its domain sharded over every local device) when one row's working
    set on the block path does not fit one device's ``bytes_limit`` and
    there is more than one device, else ``'block'``. ``bytes_limit``
    None (a backend that reports no limit) never shards."""
    if (n_devices > 1 and bytes_limit is not None
            and BLOCK_ROW_WORKING_SET * state_bytes > bytes_limit):
        return "dist-block"
    return "block"


def device_capacity() -> Tuple[Optional[int], int]:
    """(``memory_stats()["bytes_limit"]`` of the first local device, or
    None where the backend reports none; the local device count)."""
    import jax
    devs = jax.local_devices()
    stats = devs[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    return (None if limit is None else int(limit)), len(devs)


def is_block_kind(kind: str) -> bool:
    return kind.startswith(_BLOCK_PREFIX)


def is_dist_kind(kind: str) -> bool:
    return kind.startswith("dist-")


def _frac_identity(frac) -> Tuple[int, FracId]:
    """(s, mask-or-name) of a fractal object: the registry name when the
    object IS that registry entry, else its slot-position mask."""
    s = int(frac.s)
    name = getattr(frac, "name", None)
    positions = tuple(tuple(int(c) for c in p) for p in frac.positions)
    if name is not None:
        from repro.core.fractals import REGISTRY
        from repro.core.fractals3d import REGISTRY3D
        reg = REGISTRY.get(name) or REGISTRY3D.get(name)
        if reg is not None and reg.s == s and tuple(
                tuple(int(c) for c in p) for p in reg.positions
        ) == positions:
            return s, name
    return s, positions


def _mesh_shape(mesh) -> Optional[Tuple[int, ...]]:
    """Bucket a mesh (jax Mesh | shape tuple | None) to its shape."""
    if mesh is None:
        return None
    if isinstance(mesh, (tuple, list)):
        return tuple(int(d) for d in mesh)
    return tuple(int(d) for d in mesh.devices.shape)


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """Canonical engine-configuration identity (see module docstring).

    ``frac`` is a registry fractal name or a slot-position mask (tuple
    of (x, y[, z]) coordinates); ``s`` the fractal's per-level scaling
    factor; ``workload`` a registry workload name. ``fusion_k``,
    ``macro_p`` and ``exchange`` are the tunable knobs (``None`` /
    ``'auto'`` = resolve via table-then-heuristic in ``normalize``);
    ``mesh_shape``/``axis`` bucket the dist-kind device mesh.
    """

    kind: str
    s: int
    frac: FracId
    r: int
    m: int = 0
    workload: str = "life"
    fusion_k: Optional[int] = None
    macro_p: Optional[int] = None
    exchange: str = "auto"
    mesh_shape: Optional[Tuple[int, ...]] = None
    axis: str = "data"

    # ------------------------------------------------------ construction
    @classmethod
    def from_args(cls, kind: str, frac, r: int, m: int = 0,
                  workload=None, fusion_k: Optional[int] = None,
                  macro_p: Optional[int] = None, mesh=None,
                  axis: str = "data",
                  exchange: str = "auto") -> "EngineSpec":
        """Capture the identity of a legacy ``make_engine``/runner
        argument list (fractal/workload/mesh *objects*)."""
        s, ident = _frac_identity(frac)
        wl_name = workload if isinstance(workload, str) else (
            "life" if workload is None else workload.name)
        return cls(kind=kind, s=s, frac=ident, r=int(r), m=int(m),
                   workload=wl_name, fusion_k=fusion_k, macro_p=macro_p,
                   exchange=exchange, mesh_shape=_mesh_shape(mesh),
                   axis=axis)

    # ------------------------------------------------------- predicates
    @property
    def is_block(self) -> bool:
        return is_block_kind(self.kind)

    @property
    def is_dist(self) -> bool:
        return is_dist_kind(self.kind)

    @property
    def is_mxu(self) -> bool:
        return self.kind in MXU_KINDS or (
            KIND_ALIASES.get(self.kind, self.kind) in MXU_KINDS)

    @property
    def rho(self) -> int:
        """Block tile side: s**m (1 for non-block kinds)."""
        return self.s ** self.m if self.is_block else 1

    # ----------------------------------------------------- normalization
    def canonical(self) -> "EngineSpec":
        """Alias-rewritten, knob-zeroed form (validation included):

        * ``'pallas'`` -> ``'pallas-strips'`` for every consumer (the
          runner used to rewrite it while direct ``make_engine`` calls
          did not, so the two disagreed on telemetry kind labels);
        * non-block kinds have nothing to fuse: ``fusion_k`` -> 1,
          ``m`` -> 0 (no block tiles, so equal configurations share one
          slot instead of one per supplied ``m``);
        * ``exchange``/``mesh_shape``/``axis`` are dist-only knobs,
          zeroed elsewhere; ``macro_p`` is MXU-only.
        """
        kind = KIND_ALIASES.get(self.kind, self.kind)
        if kind not in KINDS:
            raise ValueError(
                f"unknown engine kind {self.kind!r}; known: "
                f"{sorted(KINDS + tuple(KIND_ALIASES))}")
        if self.fusion_k is not None and self.fusion_k < 1:
            raise ValueError(
                f"fusion_k must be >= 1, got {self.fusion_k}")
        if self.macro_p is not None and self.macro_p < 1:
            raise ValueError(
                f"macro_p must be >= 1, got {self.macro_p}")
        if self.exchange not in _EXCHANGES:
            raise ValueError(
                f"exchange must be one of {_EXCHANGES}, "
                f"got {self.exchange!r}")
        block = is_block_kind(kind)
        dist = is_dist_kind(kind)
        return dataclasses.replace(
            self,
            kind=kind,
            m=self.m if block else 0,
            fusion_k=self.fusion_k if block else 1,
            macro_p=self.macro_p if kind in MXU_KINDS else None,
            exchange=self.exchange if dist else "auto",
            mesh_shape=self.mesh_shape if dist else None,
            axis=self.axis if dist else "data",
        )

    def normalize(self, table: Any = _DEFAULT_TABLE) -> "EngineSpec":
        """The single configuration identity: ``canonical()`` with every
        tunable knob resolved to a concrete value via

            explicit argument > tuning-table hit > static heuristic.

        ``table``: the default sentinel consults the active table
        (tuning/table.py — shipped ``tables/default.json`` unless
        overridden by ``SQUEEZE_TUNING_TABLE`` or disabled by
        ``SQUEEZE_TUNING=off``); pass an explicit ``TuningTable`` or
        ``None`` (heuristic only, no telemetry) to pin it. One
        ``engine.tune.{hit,miss,fallback}`` counter is recorded per
        table consult. Idempotent: a fully resolved spec passes through
        unchanged without consulting the table.
        """
        spec = self.canonical()
        if not spec.is_block:
            return spec
        k, p, ex = spec.fusion_k, spec.macro_p, spec.exchange
        need_k = k is None
        need_p = p is None and spec.kind in MXU_KINDS
        need_ex = ex == "auto" and spec.is_dist
        if need_k or need_p or need_ex:
            entry = None
            if table is not None:
                from repro.tuning.table import consult
                entry = consult(spec, table if table is not _DEFAULT_TABLE
                                else None)
            if entry is not None:
                if need_k and entry.fusion_k is not None:
                    # the fused kernels cap k at rho (one block ring)
                    k = max(1, min(entry.fusion_k, spec.rho))
                if need_p and entry.macro_p is not None:
                    p = entry.macro_p
                if need_ex and entry.exchange in ("p2p", "gather"):
                    ex = entry.exchange
            if k is None:
                from repro.core.stencil import default_fusion_k
                k = default_fusion_k(spec.rho)
        return dataclasses.replace(spec, fusion_k=k, macro_p=p,
                                   exchange=ex)

    def tuning_key(self) -> str:
        """Stable JSON string keying this configuration in a tuning
        table: the canonical identity *minus* the tunable knobs (which
        are the table's values, not its key), mesh bucketed by shape."""
        c = self.canonical()
        ident = {
            "kind": c.kind, "s": c.s,
            "frac": c.frac if isinstance(c.frac, str)
            else [list(p) for p in c.frac],
            "r": c.r, "m": c.m, "workload": c.workload,
            "mesh_shape": (list(c.mesh_shape)
                           if c.mesh_shape is not None else None),
            "axis": c.axis,
        }
        return json.dumps(ident, sort_keys=True, separators=(",", ":"))

    # ------------------------------------------------- JSON round-trip
    def to_json(self) -> Dict[str, Any]:
        """Plain-JSON dict; ``from_json`` round-trips it exactly."""
        d = dataclasses.asdict(self)
        if not isinstance(self.frac, str):
            d["frac"] = [list(p) for p in self.frac]
        if self.mesh_shape is not None:
            d["mesh_shape"] = list(self.mesh_shape)
        return d

    @classmethod
    def from_json(cls, d: Dict[str, Any]) -> "EngineSpec":
        frac = d["frac"]
        if not isinstance(frac, str):
            frac = tuple(tuple(int(c) for c in p) for p in frac)
        mesh = d.get("mesh_shape")
        return cls(
            kind=d["kind"], s=int(d["s"]), frac=frac, r=int(d["r"]),
            m=int(d.get("m", 0)), workload=d.get("workload", "life"),
            fusion_k=d.get("fusion_k"), macro_p=d.get("macro_p"),
            exchange=d.get("exchange", "auto"),
            mesh_shape=tuple(int(x) for x in mesh)
            if mesh is not None else None,
            axis=d.get("axis", "data"))

    # ------------------------------------------- object reconstruction
    def build_frac(self):
        """The fractal object this spec names (registry lookup for
        name identities, reconstruction for mask identities)."""
        from repro.core.fractals import REGISTRY, NBBFractal
        from repro.core.fractals3d import REGISTRY3D, NBBFractal3D
        if isinstance(self.frac, str):
            frac = REGISTRY.get(self.frac) or REGISTRY3D.get(self.frac)
            if frac is None:
                raise KeyError(
                    f"unknown fractal name {self.frac!r} in EngineSpec "
                    f"(custom fractals serialize by position mask)")
            if frac.s != self.s:
                raise ValueError(
                    f"fractal {self.frac!r} has s={frac.s}, spec says "
                    f"s={self.s}")
            return frac
        ndim = len(self.frac[0])
        name = f"nbb{ndim}d-s{self.s}-k{len(self.frac)}"
        cls = NBBFractal3D if ndim == 3 else NBBFractal
        return cls(name, self.s, self.frac)

    def build_workload(self):
        """The workload object this spec names (registry lookup; pass
        custom workload objects explicitly to ``make_engine``/runner
        calls — they serialize by name only)."""
        from repro.workloads import rules
        registry = dict(rules.WORKLOADS)
        for extra in (rules.LIFE3D, rules.HEAT3D):
            registry.setdefault(extra.name, extra)
        try:
            return registry[self.workload]
        except KeyError:
            raise KeyError(
                f"unknown workload name {self.workload!r} in EngineSpec; "
                f"registry has {sorted(registry)} (pass the workload "
                f"object explicitly for custom workloads)") from None

    def build_mesh(self):
        """A device mesh matching ``mesh_shape``/``axis`` (None when the
        spec has no mesh — dist engines then default to all devices)."""
        if self.mesh_shape is None:
            return None
        import math

        import jax
        from jax.sharding import Mesh

        import numpy as np
        n = math.prod(self.mesh_shape)
        devs = jax.devices()
        if len(devs) < n:
            raise ValueError(
                f"spec wants a {self.mesh_shape} mesh ({n} devices), "
                f"but only {len(devs)} are available")
        names = tuple(f"{self.axis}{i}" if i else self.axis
                      for i in range(len(self.mesh_shape)))
        return Mesh(np.array(devs[:n]).reshape(self.mesh_shape), names)
