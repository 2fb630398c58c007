"""Multi-device compact fractal stencil: shard_map + k-fused strip halo
exchange + shard-local fused kernels.

The compact block domain (the *only* thing in memory — the paper's P2 win)
is sharded along its leading block axis over a mesh axis (default "data").
One fused depth-``k`` launch advances ``k`` exact steps with ONE halo
exchange of edge *strips* (top/bottom ``k`` rows, west/east ``k`` columns
per block — ``BlockLayout.pack_edge_strips``, ~4k/rho of the state; the
state itself is never exchanged).

Two exchange modes (``exchange=``):

``'p2p'`` (the default resolution of ``'auto'``) — neighbor-only
``jax.lax.ppermute`` overlapped with interior compute. Fractal adjacency
is non-local in *compact* (digit-interleaved) id order, but the lambda/nu
maps give a static block<->space correspondence, and
``BlockLayout.strip_decomposition`` uses it to assign each shard a
contiguous strip of expanded-space block rows (holes handled exactly —
only occupied rows exist). Rows are never split, so every cross-shard
Moore neighbor lives on shard +-1 and the whole exchange is two
``ppermute`` shifts of exactly the strips each neighbor needs
(``send_prev_idx`` / ``send_next_idx`` routing tables). Each launch
splits its local blocks into *interior* (depth-k halo fully shard-local;
computed while the permutes are in flight) and *boundary* (needs a
neighbor strip; computed after) — XLA schedules the interior kernels
against the collective from the data dependence alone. Per-device
exchanged bytes are independent of the shard count (each shard talks to
at most two neighbors regardless of mesh size) — the flat scaling curve
gated by ``benchmarks/distributed_bench.py --scaling``.

``'gather'`` — the fallback: ONE ``all_gather`` replicates every shard's
strips over the mesh axis, then each shard assembles halos from the
replicated buffer. Exchanged bytes grow ~linearly with device count, but
the scheme needs no decomposition, so it covers degenerate meshes where
the strip decomposition is invalid (fewer occupied expanded block rows
than shards). ``exchange='auto'`` resolves to p2p whenever the
decomposition is valid and falls back to gather otherwise;
``exchange='p2p'`` raises on a degenerate mesh.

Both modes assemble halos via the static ``offset_table(k)`` machinery
(radius-1 == the exact-past-holes Moore table for k <= rho) and run the
same shard-local fused substeps: the v5 MXU macro-tile kernel
(``compute='mxu'``), the v4 fused-depth kernel (``compute='fused'``), or
the XLA window path (``compute='jnp'``), all parameterized by the
``StencilWorkload`` and all reusing the single-device substep mask
discipline (periodic window mask gated by per-block neighbor existence).

``run(state, steps)`` tiles steps into floor(steps/k) fused launches plus
ONE remainder launch of depth steps % k, so a run performs exactly
ceil(steps/k) halo exchanges — asserted by ``exchange_stats()`` in the
tests (``bytes_permuted``/``neighbor_sends`` on the p2p path,
``bytes_gathered`` on the gather path). ``run(..., donate=True)``
donates the state buffer to XLA (zero-copy steady-state stepping, as the
single-device engines).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.compact import BlockLayout
from repro.workloads.base import StencilWorkload, check_workload_ndim
from repro.workloads.rules import LIFE

Array = jnp.ndarray

#: shard-local compute backends: XLA window path, v4 fused-depth kernel,
#: v5 MXU macro-tile kernel
COMPUTES = ("jnp", "fused", "mxu")

#: halo-exchange modes: neighbor-only ppermute with interior/boundary
#: overlap, strip all-gather fallback, or pick-per-mesh
EXCHANGES = ("auto", "p2p", "gather")


def _pad_blocks(layout: BlockLayout, n_shards: int) -> int:
    """Blocks padded so the leading axis divides the mesh axis size."""
    nb = layout.n_blocks
    return ((nb + n_shards - 1) // n_shards) * n_shards


@dataclasses.dataclass
class ExchangeStats:
    """Halo-exchange accounting of one engine: every fused launch issues
    exactly one exchange — one strip ``all_gather`` on the gather path,
    one pair of neighbor ``ppermute`` shifts on the p2p path (verified
    structurally by the tests, which count collectives in the lowered
    step HLO)."""

    steps: int = 0            # simulated steps advanced
    collectives: int = 0      # halo exchanges issued (one per launch)
    bytes_gathered: int = 0   # replicated strip-buffer bytes (gather)
    bytes_permuted: int = 0   # neighbor strip bytes on the wire (p2p)
    neighbor_sends: int = 0   # directed shard->shard strip sends (p2p)

    @property
    def exchanged_bytes(self) -> int:
        """Mode-independent exchanged volume (one of the two byte
        counters is always zero)."""
        return self.bytes_gathered + self.bytes_permuted

    @property
    def collectives_per_step(self) -> float:
        return self.collectives / max(self.steps, 1)

    @property
    def bytes_per_step(self) -> float:
        return self.exchanged_bytes / max(self.steps, 1)


@dataclasses.dataclass(frozen=True)
class DistributedSqueezeEngine:
    """Block-level Squeeze sharded over one mesh axis, workload-generic
    and fusion-aware.

    State layout: (C?, nb_padded, rho, rho) — or (B, C?, nb_padded, rho,
    rho) batched — sharded over the block axis. On the gather path the
    native order is compact id order plus a dead tail; on the p2p path it
    is the ``StripDecomposition`` permutation (expanded-row strips, dead
    padding at each shard's tail). Dead blocks are permanently zero: the
    neighbor table never points at them and every compute path gates them
    out of the occupancy mask. ``to_dense``/``from_dense`` convert to the
    mesh- and exchange-independent compact order.

    ``compute`` picks the shard-local backend ('jnp' | 'fused' | 'mxu');
    ``fusion_k`` the exchange/fusion depth used by ``run`` (None = the
    single-device ``default_fusion_k`` heuristic, always <= rho);
    ``exchange`` the halo-exchange mode ('auto' | 'p2p' | 'gather' — see
    the module docstring for the semantics and the fallback rule).
    """

    layout: BlockLayout
    mesh: Mesh
    axis: str = "data"
    workload: StencilWorkload = LIFE
    compute: str = "jnp"
    fusion_k: Optional[int] = None
    interpret: Optional[bool] = None  # kernel computes; None = auto-detect
    exchange: str = "auto"
    #: MXU macro-tile packing override ('mxu' compute only; applied to
    #: each shard's local lane-packing geometry, None = lane heuristic)
    macro_p: Optional[int] = None

    def __post_init__(self):
        if self.compute not in COMPUTES:
            raise ValueError(
                f"unknown compute {self.compute!r}; have {COMPUTES}")
        if self.exchange not in EXCHANGES:
            raise ValueError(
                f"unknown exchange {self.exchange!r}; have {EXCHANGES}")
        check_workload_ndim(self.workload, 2)
        if self.fusion_k is not None and not (
                1 <= self.fusion_k <= self.layout.rho):
            raise ValueError(
                f"distributed fusion_k must be in [1, rho="
                f"{self.layout.rho}], got {self.fusion_k} (the strip "
                "exchange covers one block ring)")
        if self.macro_p is not None and self.compute != "mxu":
            raise ValueError(
                "macro_p only applies to the 'mxu' compute, got "
                f"compute={self.compute!r}")
        self.layout.materialize()
        if self.exchange == "p2p" and not self.decomp.valid:
            raise ValueError(
                f"exchange='p2p' needs >= {self.n_shards} occupied "
                "expanded block rows (the strip decomposition is "
                "degenerate on this mesh); use exchange='auto' or "
                "'gather'")
        object.__setattr__(self, "_stats", ExchangeStats())

    # ------------------------------------------------------------ geometry
    @property
    def n_shards(self) -> int:
        return self.mesh.shape[self.axis]

    @functools.cached_property
    def decomp(self):
        """The locality-aware strip decomposition for this mesh size
        (shared across engines via the layout memo; ``.valid`` is False
        on degenerate meshes)."""
        return self.layout.strip_decomposition(self.n_shards)

    @functools.cached_property
    def exchange_mode(self) -> str:
        """The RESOLVED exchange ('p2p' | 'gather'): 'auto' picks p2p
        whenever the strip decomposition is valid."""
        if self.exchange == "gather":
            return "gather"
        if self.exchange == "p2p":
            return "p2p"
        return "p2p" if self.decomp.valid else "gather"

    @property
    def nb_padded(self) -> int:
        if self.exchange_mode == "p2p":
            return self.decomp.nb_padded
        return _pad_blocks(self.layout, self.n_shards)

    @property
    def nb_local(self) -> int:
        return self.nb_padded // self.n_shards

    @property
    def effective_fusion_k(self) -> int:
        if self.fusion_k is not None:
            return self.fusion_k
        from repro.core.stencil import default_fusion_k
        return default_fusion_k(self.layout.rho)

    def state_spec(self, ndim: int) -> P:
        """PartitionSpec sharding the block axis (position ndim-3)."""
        spec = [None] * ndim
        spec[ndim - 3] = self.axis
        return P(*spec)

    def sharding(self, ndim: Optional[int] = None) -> NamedSharding:
        if ndim is None:
            ndim = 3 + (1 if self.workload.n_channels > 1 else 0)
        return NamedSharding(self.mesh, self.state_spec(ndim))

    # ----------------------------------------------------------- accounting
    def strip_bytes(self, k: int, batch: int = 1) -> int:
        """Bytes of the replicated strip buffer produced by one depth-``k``
        halo all-gather (the gather collective's payload)."""
        itemsize = jnp.dtype(self.workload.dtype).itemsize
        return (batch * self.workload.n_channels * self.nb_padded
                * 4 * k * self.layout.rho * itemsize)

    def permute_bytes(self, k: int, batch: int = 1) -> int:
        """Total bytes moved over the wire by one depth-``k`` p2p
        exchange (both ppermute shifts, every adjacent shard pair)."""
        itemsize = jnp.dtype(self.workload.dtype).itemsize
        return self.decomp.wire_bytes_per_exchange(
            k, itemsize, batch * self.workload.n_channels)

    def wire_bytes_per_device(self, k: int, batch: int = 1) -> int:
        """Bytes one shard RECEIVES per depth-``k`` exchange — the
        per-device wire pressure the scaling bench records. Flat in the
        shard count on the p2p path (two neighbors regardless of mesh
        size); grows ~linearly on the gather path (everyone else's
        strips)."""
        itemsize = jnp.dtype(self.workload.dtype).itemsize
        if self.exchange_mode == "p2p":
            return self.decomp.wire_bytes_per_device_per_exchange(
                k, itemsize, batch * self.workload.n_channels)
        return (batch * self.workload.n_channels
                * (self.nb_padded - self.nb_local)
                * 4 * k * self.layout.rho * itemsize)

    def exchange_stats(self) -> ExchangeStats:
        """Snapshot of the halo-exchange counters (exchanges issued,
        simulated steps advanced, bytes gathered/permuted, neighbor
        sends)."""
        return dataclasses.replace(self._stats)

    def reset_exchange_stats(self) -> None:
        st = self._stats
        st.steps = st.collectives = 0
        st.bytes_gathered = st.bytes_permuted = st.neighbor_sends = 0

    def _account(self, k: int, launches: int, batch: int) -> None:
        st = self._stats
        if self.exchange_mode == "p2p":
            gathered = 0
            permuted = launches * self.permute_bytes(k, batch)
            sends = launches * 2 * (self.n_shards - 1)
        else:
            gathered = launches * self.strip_bytes(k, batch)
            permuted = sends = 0
        st.steps += launches * k
        st.collectives += launches
        st.bytes_gathered += gathered
        st.bytes_permuted += permuted
        st.neighbor_sends += sends
        if obs.enabled():
            # the same accounting, unified onto the telemetry registry
            # (labeled by compute backend) so one obs.report() answers
            # "how many exchanges and bytes did this run ship"
            obs.inc("dist.steps", launches * k, compute=self.compute)
            obs.inc("dist.collectives", launches, compute=self.compute)
            obs.inc("dist.bytes_gathered", gathered,
                    compute=self.compute)
            obs.inc("dist.bytes_permuted", permuted,
                    compute=self.compute)
            obs.inc("dist.neighbor_sends", sends, compute=self.compute)
            obs.inc("engine.fused_launches", launches,
                    engine=type(self).__name__, variant=self.compute)

    def memory_bytes(self, dtype_size: Optional[int] = None) -> int:
        """Total (all-shard) Squeeze state bytes, padding blocks included
        (the per-shard footprint is this / n_shards)."""
        if dtype_size is None:
            dtype_size = jnp.dtype(self.workload.dtype).itemsize
        return (self.workload.n_channels * self.nb_padded
                * self.layout.rho ** 2 * dtype_size)

    # ------------------------------------------------------------ state I/O
    @functools.cached_property
    def _native_src(self) -> Optional[np.ndarray]:
        """(nb_padded,) compact block id feeding each native slot, with
        dead slots pointing at the appended zero block — None on the
        gather path, whose native order is compact order + dead tail."""
        if self.exchange_mode != "p2p":
            return None
        d = self.decomp
        return np.where(d.perm >= 0, d.perm,
                        np.int32(self.layout.n_blocks))

    @functools.cached_property
    def _dense_src(self) -> Optional[np.ndarray]:
        """(n_blocks,) native slot of each compact block id (the inverse
        gather of ``_native_src``) — None on the gather path."""
        if self.exchange_mode != "p2p":
            return None
        d = self.decomp
        return (d.shard_of.astype(np.int64) * d.nb_local
                + d.local_of).astype(np.int32)

    def _pad_state(self, dense: Array) -> Array:
        """Compact-order (B?, C?, n_blocks, rho, rho) -> engine-native
        block order (permuted strips on p2p, identity + dead tail on
        gather)."""
        src = self._native_src
        if src is None:
            pad = self.nb_padded - self.layout.n_blocks
            if pad:
                shape = dense.shape[:-3] + (pad,) + dense.shape[-2:]
                dense = jnp.concatenate(
                    [dense, jnp.zeros(shape, dense.dtype)], axis=-3)
            return dense
        zshape = dense.shape[:-3] + (1,) + dense.shape[-2:]
        dense_z = jnp.concatenate(
            [dense, jnp.zeros(zshape, dense.dtype)], axis=-3)
        return dense_z[..., src, :, :]

    def init_random(self, seed: int) -> Array:
        from repro.core.stencil import SqueezeBlockEngine
        dense = SqueezeBlockEngine(self.layout,
                                   self.workload).init_random(seed)
        dense = self._pad_state(dense)
        return jax.device_put(dense, self.sharding(dense.ndim))

    def init_batch(self, seeds) -> Array:
        """Stack independent initial states: (B, C?, nb_padded, rho, rho),
        sharded over the block axis."""
        from repro.core.stencil import SqueezeBlockEngine
        eng = SqueezeBlockEngine(self.layout, self.workload)
        dense = jnp.stack([eng.init_random(int(s)) for s in seeds])
        dense = self._pad_state(dense)
        return jax.device_put(dense, self.sharding(dense.ndim))

    def to_dense(self, state: Array) -> Array:
        """Engine-native -> compact block order (for comparison against
        single-device and for mesh-independent checkpoints)."""
        src = self._dense_src
        if src is None:
            return state[..., : self.layout.n_blocks, :, :]
        return state[..., src, :, :]

    def from_dense(self, dense: Array) -> Array:
        """(B?, C?, n_blocks, rho, rho) unpadded compact state ->
        engine-native padded + sharded state (the inverse of
        :meth:`to_dense`). This is the elastic-restore ingest path: a
        checkpoint saved under ANY mesh/exchange stores the
        mesh-independent dense state, and re-enters here permuted+padded
        for THIS engine's layout and device_put with its sharding."""
        dense = jnp.asarray(dense, jnp.dtype(self.workload.dtype))
        padded = self._pad_state(dense)
        return jax.device_put(padded, self.sharding(padded.ndim))

    def dead_mask(self) -> np.ndarray:
        """(nb_padded, rho, rho) uint8, 1 where a cell must be zero in
        every valid state: fractal holes inside real blocks (the mask
        discipline re-kills them each substep) and every cell of a
        padding block — in ENGINE-NATIVE block order. A nonzero cell
        under this mask is the signature of halo/strip corruption — the
        elastic runner's post-launch integrity check multiplies by it."""
        layout = self.layout
        hole = (1 - layout.micro_mask).astype(np.uint8)
        src = self._native_src
        if src is None:
            dead = np.broadcast_to(
                hole, (layout.n_blocks,) + hole.shape)
            pad = self.nb_padded - layout.n_blocks
            if pad:
                dead = np.concatenate(
                    [dead, np.ones((pad,) + hole.shape, np.uint8)],
                    axis=0)
            return np.ascontiguousarray(dead)
        hole_z = np.concatenate(
            [np.broadcast_to(hole, (layout.n_blocks,) + hole.shape),
             np.ones((1,) + hole.shape, np.uint8)], axis=0)
        return np.ascontiguousarray(hole_z[src])

    def to_expanded(self, state: Array) -> Array:
        """(B?, C?, nb_padded, rho, rho) -> (B?, C?, n, n) expanded."""
        return self.layout.to_expanded(self.to_dense(state))

    # --------------------------------------------------- canonical 5D states
    def _canon(self, state: Array) -> Tuple[Array, bool]:
        """Any public state rank -> ((B, C, nb_padded, rho, rho), batched)."""
        chan = self.workload.n_channels > 1
        base = 4 if chan else 3
        if state.ndim == base:
            return (state[None] if chan else state[None, None]), False
        if state.ndim == base + 1:
            return (state if chan else state[:, None]), True
        raise ValueError(
            f"bad state rank {state.ndim} for workload "
            f"{self.workload.name!r} (expected {base} or {base + 1})")

    def _uncanon(self, s5: Array, batched: bool) -> Array:
        chan = self.workload.n_channels > 1
        if batched:
            return s5 if chan else s5[:, 0]
        return s5[0] if chan else s5[0, 0]

    # ------------------------------------------------------- compiled steps
    @functools.cached_property
    def _cache(self) -> dict:
        """Per-instance memo of device tables and jitted step/run fns."""
        return {}

    def _memo(self, key, build):
        cache = self._cache
        if key not in cache:
            cache[key] = build()
        return cache[key]

    def _shard_operands(self, k: int) -> Tuple[Array, ...]:
        """Per-shard static operands of a depth-``k`` launch, built ONCE
        and device_put sharded over the block axis (a traced step would
        otherwise re-derive them per launch — pure overhead on the
        per-step critical path). Gather mode: (mask, table, existence).
        P2p mode: those three in native strip order, sentinel-extended
        per shard, plus the interior-view table and the per-shard
        routing rows (send_prev, send_next, boundary).

          * halo mask (nb_padded, w, w): ``layout.halo_mask(k)`` (periodic
            window occupancy, ghost regions zeroed) with all-zero rows for
            dead blocks — so the substep mask discipline AND the
            padding-stays-dead guarantee are a single multiply;
          * neighbor table (nb_padded, 8): ``offset_table(k)`` (radius-1 ==
            the exact-past-holes Moore table), ghosts pre-remapped to the
            appended zero-strip row — gather: global strip ids; p2p: the
            decomposition's combined per-shard strip coordinates;
          * existence (nb_padded, 8) int32: the shard-local kernels'
            per-block rows for the in-kernel mask reconstruction.
        """
        def build_gather():
            layout = self.layout
            pad = self.nb_padded - layout.n_blocks
            w = layout.rho + 2 * k
            mask = np.concatenate(
                [layout.halo_mask(k),
                 np.zeros((pad, w, w), np.uint8)], axis=0)
            table = np.concatenate(
                [layout.offset_table(k),
                 np.full((pad, 8), layout.ghost, np.int32)], axis=0)
            table = np.where(table == layout.ghost,
                             np.int32(self.nb_padded), table)
            existence = np.concatenate(
                [layout.existence_table,
                 np.zeros((pad, 8), np.int32)], axis=0)
            row = NamedSharding(self.mesh, P(self.axis, None))
            cube = NamedSharding(self.mesh, P(self.axis, None, None))
            return (jax.device_put(mask, cube),
                    jax.device_put(table, row),
                    jax.device_put(existence, row))

        def build_p2p():
            layout, d = self.layout, self.decomp
            src = self._native_src  # dead slots -> appended zero rows
            ns, nbl = self.n_shards, self.nb_local
            w = layout.rho + 2 * k
            mask = np.concatenate(
                [layout.halo_mask(k),
                 np.zeros((1, w, w), np.uint8)], axis=0)[src]
            existence = np.concatenate(
                [layout.existence_table,
                 np.zeros((1, 8), np.int32)], axis=0)[src]
            table = d.table.reshape(self.nb_padded, 8)

            # pre-extend each shard's rows with the ghost/sentinel row
            # (index nbl): all-dead mask/existence, table pointing at
            # the appended zero strip row — hoists three per-launch
            # concatenations off the traced step's critical path
            def extend(rows, sentinel_row):
                per = rows.reshape((ns, nbl) + rows.shape[1:])
                sen = np.broadcast_to(
                    sentinel_row, (ns, 1) + rows.shape[1:])
                out = np.concatenate([per, sen], axis=1)
                return np.ascontiguousarray(
                    out.reshape((ns * (nbl + 1),) + rows.shape[1:]))

            mask_z = extend(mask, np.zeros((w, w), mask.dtype))
            ex_z = extend(existence, np.zeros(8, existence.dtype))
            table_z = extend(table, np.full(8, nbl, table.dtype))

            # interior-view table: every remote reference (combined slot
            # > nbl) remapped to the ghost zero row.  The full-domain
            # overlap pass reads halos through THIS table, so it depends
            # only on shard-local strips — correct for interior blocks
            # (whose rows the remap never touches), provisional for
            # boundary blocks (patched after the permutes land).
            table_int = np.ascontiguousarray(
                np.minimum(table, np.int32(nbl)))

            row = NamedSharding(self.mesh, P(self.axis, None))
            cube = NamedSharding(self.mesh, P(self.axis, None, None))
            return (jax.device_put(mask_z, cube),
                    jax.device_put(table_z, row),
                    jax.device_put(ex_z, row),
                    jax.device_put(table_int, row),
                    jax.device_put(d.send_prev_idx, row),
                    jax.device_put(d.send_next_idx, row),
                    jax.device_put(d.boundary_idx, row))

        build = build_p2p if self.exchange_mode == "p2p" \
            else build_gather
        return self._memo(("operands", self.exchange_mode, k), build)

    def _materialize(self, k: int) -> None:
        """Build every static host/device table a depth-``k`` traced step
        reads — outside any trace."""
        self.layout.materialize()
        _ = self._shard_operands(k)

    # ---------------------------------------------------- shard-local compute
    def _compute_k(self, states: Array, halo, mask: Array,
                   existence: Array, k: int) -> Array:
        """k fused substeps on one set of blocks (any static count):
        ``states`` (B, C, n_sel, rho, rho), ``halo`` the matching
        depth-k pieces, ``mask``/``existence`` the selected rows of the
        sharded operands. Shared by the gather path (all local blocks at
        once) and the p2p path (full-domain overlap pass + boundary
        patch subset)."""
        layout = self.layout
        rho = layout.rho
        if self.compute == "mxu":
            from repro.kernels.squeeze_stencil import (
                stencil_step_mxu_k_local)
            out = stencil_step_mxu_k_local(
                layout, states, halo, existence, self.workload, k=k,
                p=self.macro_p, interpret=self.interpret)
        elif self.compute == "fused":
            from repro.kernels.squeeze_stencil import (
                stencil_step_fused_k_local)

            def one(s, top, bot, west, east):
                return stencil_step_fused_k_local(
                    layout, s, (top, bot, west, east), existence,
                    self.workload, k=k, interpret=self.interpret)

            out = jax.vmap(one)(states, *halo)
        else:
            return self._jnp_step_k(states, halo, mask, k)
        # the kernels gate halo regions in-kernel but keep the periodic
        # center mask — one multiply by the mask's center re-kills dead
        # blocks (their mask rows are all zero)
        center = mask[:, k:k + rho, k:k + rho]
        return out * center.astype(out.dtype)

    def _local_step_k(self, state_local: Array, mask: Array, table: Array,
                      existence: Array, k: int) -> Array:
        """One fused depth-``k`` gather-mode launch on this shard: pack
        strips, ONE all_gather, assemble halos, run k substeps locally.

        state_local (B, C, nb_local, rho, rho) -> same, k steps later;
        ``mask``/``table``/``existence`` are this shard's rows of the
        ``_shard_operands`` arrays.
        """
        layout, axis = self.layout, self.axis
        rho, nbl = layout.rho, self.nb_local
        b, nc = state_local.shape[0], state_local.shape[1]

        # 1. pack my edge bands ((B, C) folded: strip plumbing is linear
        # per leading axis)
        flat = state_local.reshape(b * nc, nbl, rho, rho)
        strips_local = layout.pack_edge_strips(flat, k)
        # 2. halo exchange: ONE all_gather of strips only
        strips = jax.lax.all_gather(strips_local, axis, axis=1, tiled=True)
        strips = jnp.concatenate(
            [strips,
             jnp.zeros((strips.shape[0], 1) + strips.shape[2:],
                       strips.dtype)], axis=1)  # ghost zero entry (row nbp)
        # 3. assemble my blocks' depth-k halos + shard-local fused compute
        halo = tuple(
            h.reshape((b, nc) + h.shape[1:])
            for h in layout.halo_from_strips_k(strips, table, k))
        return self._compute_k(state_local, halo, mask, existence, k)

    def _local_step_k_p2p(self, state_local: Array, mask: Array,
                          table: Array, existence: Array,
                          table_int: Array, send_prev: Array,
                          send_next: Array, boundary: Array,
                          k: int) -> Array:
        """One fused depth-``k`` p2p launch on this shard: pack strips,
        start the two neighbor ``ppermute`` shifts, run the k substeps
        over the WHOLE local domain from shard-local strips only WHILE
        the permutes are in flight (``table_int`` remaps every remote
        halo reference to the ghost zero row, so the pass has no data
        dependence on the collectives — exact for interior blocks,
        provisional for boundary blocks), then recompute just the
        boundary blocks from the combined local+received strip buffer
        and patch them in (compute-all-then-patch overlap).

        state_local (B, C, nb_local, rho, rho) -> same, k steps later.
        ``mask``/``table``/``existence`` are this shard's rows of the
        native-ordered operands, pre-extended with the ghost/sentinel
        row (index nb_local: all-dead, table pointing at the zero strip
        row); ``table_int`` the (nb_local, 8) interior-view table;
        ``send_prev``/``send_next``/``boundary`` this shard's (1, m)
        routing rows (indices into [0, nb_local])."""
        layout, axis = self.layout, self.axis
        rho, nbl, ns = layout.rho, self.nb_local, self.n_shards
        b, nc = state_local.shape[0], state_local.shape[1]
        sp, sn = send_prev[0], send_next[0]
        bi = boundary[0]

        # 1. pack my edge bands + the shared ghost/sentinel zero row
        flat = state_local.reshape(b * nc, nbl, rho, rho)
        strips = layout.pack_edge_strips(flat, k)
        strips_z = jnp.concatenate(
            [strips,
             jnp.zeros((strips.shape[0], 1) + strips.shape[2:],
                       strips.dtype)], axis=1)
        # 2. halo exchange: two neighbor-only permute shifts carrying
        # ONLY the strips each neighbor needs (dead routing slots ship
        # the zero row)
        fwd = [(i, i + 1) for i in range(ns - 1)]
        bwd = [(i + 1, i) for i in range(ns - 1)]
        recv_prev = jax.lax.ppermute(strips_z[:, sn], axis, fwd)
        recv_next = jax.lax.ppermute(strips_z[:, sp], axis, bwd)

        # 3a. full-domain overlap pass: halos through the interior-view
        # table touch only strips_z, so XLA schedules these kernels
        # concurrently with the in-flight permutes.  Boundary rows come
        # out provisional (their remote neighbors read as dead) and are
        # patched below; interior rows are final.
        halo_full = tuple(
            h.reshape((b, nc) + h.shape[1:])
            for h in layout.halo_from_strips_k(strips_z, table_int, k))
        out = self._compute_k(state_local, halo_full,
                              mask[:nbl], existence[:nbl], k)
        # 3b. boundary fix-up from local + received strips, in the
        # decomposition's combined coordinate convention:
        # [0, nbl) local | nbl ghost | ms_next from prev | ms_prev next
        combined = jnp.concatenate(
            [strips_z, recv_prev, recv_next], axis=1)
        halo_bnd = tuple(
            h.reshape((b, nc) + h.shape[1:])
            for h in layout.halo_from_strips_k(combined, table[bi], k))
        out_bnd = self._compute_k(
            state_local[:, :, jnp.minimum(bi, nbl - 1)], halo_bnd,
            mask[bi], existence[bi], k)
        # sentinel padding entries (index nbl) are out of bounds on the
        # nbl-row axis: the gather above clamps them (their value is
        # irrelevant — the zero mask row kills the output) and the
        # scatter here drops them (default OOB-drop semantics)
        return out.at[:, :, bi].set(out_bnd)

    def _jnp_step_k(self, states: Array, halo, mask: Array,
                    k: int) -> Array:
        """XLA window path: assemble (B, C, n_sel, rho+2k, rho+2k) tiles
        and run the workload's k fused substeps under the precomputed
        sharded halo mask (the same per-block occupancy the single-device
        XLA ``step_k`` reads; dead-block rows are all zero, so the
        k-substep mask discipline and the padding gate are one
        multiply)."""
        layout, wl = self.layout, self.workload
        rho = layout.rho
        w = rho + 2 * k
        top, bot, west, east = halo
        b, nc, nbl = states.shape[:3]
        padded = jnp.zeros((b, nc, nbl, w, w), states.dtype)
        padded = padded.at[..., k:k + rho, k:k + rho].set(states)
        padded = padded.at[..., :k, :].set(top)
        padded = padded.at[..., w - k:, :].set(bot)
        padded = padded.at[..., k:k + rho, :k].set(west)
        padded = padded.at[..., k:k + rho, w - k:].set(east)

        def one(p):  # (C, nbl, w, w) -> (C, nbl, rho, rho)
            if wl.n_channels > 1:
                return wl.tile_rule_k(p, mask, k)
            return wl.tile_rule_k(p[0], mask, k)[None]

        return jax.vmap(one)(padded).astype(states.dtype)

    def _step5_fn(self, k: int, donate: bool = False):
        """Jitted shard_map'd fused step over canonical 5D states plus the
        sharded static operands."""
        def build():
            self._materialize(k)
            spec = self.state_spec(5)
            row = P(self.axis, None)
            if self.exchange_mode == "p2p":
                local = functools.partial(self._local_step_k_p2p, k=k)
                in_specs = (spec, P(self.axis, None, None),
                            row, row, row, row, row, row)
            else:
                local = functools.partial(self._local_step_k, k=k)
                in_specs = (spec, P(self.axis, None, None), row, row)
            # pallas_call has no shard_map replication rule: the kernel
            # computes must disable the (conservative) rep check
            step = jax.shard_map(
                local, mesh=self.mesh, in_specs=in_specs,
                out_specs=spec, check_vma=self.compute == "jnp")
            return jax.jit(step, donate_argnums=0) if donate \
                else jax.jit(step)
        return self._memo(("step5", k, donate), build)

    def _call_step(self, k: int, s5: Array, donate: bool = False) -> Array:
        return self._step5_fn(k, donate)(s5, *self._shard_operands(k))

    def _loop_fn(self, k: int, donate: bool):
        """Jitted fori_loop of fused launches; the launch count is a
        *traced* scalar, so changing ``steps`` does not retrace."""
        def build():
            step = self._step5_fn(k)

            def body(s5, n, *ops):
                return jax.lax.fori_loop(
                    0, n, lambda _, s: step(s, *ops), s5)

            return jax.jit(body, donate_argnums=0) if donate \
                else jax.jit(body)
        return self._memo(("loop", k, donate), build)

    # ------------------------------------------------------------ public API
    def step(self, state: Array) -> Array:
        """One step (one halo exchange)."""
        return self.step_k(state, 1)

    def step_k(self, state: Array, k: int) -> Array:
        """``k`` exact steps in one fused launch: ONE halo exchange of
        depth-``k`` strips, then k shard-local substeps (1 <= k <= rho)."""
        if not (1 <= k <= self.layout.rho):
            raise ValueError(
                f"need 1 <= k <= rho={self.layout.rho}, got k={k}")
        s5, batched = self._canon(state)
        out = self._call_step(k, s5)
        self._account(k, 1, s5.shape[0])
        return self._uncanon(out, batched)

    def step_batched(self, states: Array) -> Array:
        return self.step_k(states, 1)

    def step_k_batched(self, states: Array, k: int) -> Array:
        return self.step_k(states, k)

    @property
    def supports_native_batch(self) -> bool:
        """B simulations advance through one shard_map step whose strip
        exchange is a single batched collective (every compute backend;
        'mxu' additionally runs one (B, n_macro_local) kernel grid)."""
        return True

    def run(self, state: Array, steps: int, donate: bool = False) -> Array:
        """``steps`` steps tiled into floor(steps/k) fused depth-k launches
        plus ONE remainder launch of depth steps % k — exactly
        ceil(steps/k) halo exchanges total. ``donate=True`` donates the
        state buffer to XLA (zero-copy stepping; the caller must not reuse
        ``state`` afterwards)."""
        steps = int(steps)
        if steps < 0:
            raise ValueError(f"steps must be >= 0, got {steps}")
        s5, batched = self._canon(state)
        b = s5.shape[0]
        k = self.effective_fusion_k
        n_fused, rem = divmod(steps, k)
        with obs.span("dist.run", compute=self.compute, steps=steps,
                      k=k, batch=b):
            if n_fused:
                s5 = self._loop_fn(k, donate)(
                    s5, jnp.asarray(n_fused, jnp.int32),
                    *self._shard_operands(k))
                self._account(k, n_fused, b)
            if rem:
                s5 = self._call_step(rem, s5, donate)
                self._account(rem, 1, b)
        if donate:
            obs.inc("engine.donated_runs",
                    engine=type(self).__name__, variant=self.compute)
        return self._uncanon(s5, batched)

    def lowered_step_text(self, state: Array, k: int) -> str:
        """Lowered StableHLO of one fused depth-``k`` launch — the tests
        count its collectives (one all_gather per gather launch; two
        collective_permutes and ZERO all_gathers per p2p launch)."""
        s5, _ = self._canon(state)
        return self._step5_fn(k).lower(
            s5, *self._shard_operands(k)).as_text()


def make_distributed_engine(layout: BlockLayout, mesh: Optional[Mesh] = None,
                            axis: str = "data",
                            workload: StencilWorkload = LIFE,
                            compute: str = "jnp",
                            fusion_k: Optional[int] = None,
                            interpret: Optional[bool] = None,
                            exchange: str = "auto",
                            macro_p: Optional[int] = None
                            ) -> DistributedSqueezeEngine:
    """Engine over ``mesh`` (default: all devices on one "data" axis)."""
    if mesh is None:
        mesh = Mesh(jax.devices(), ("data",))
        axis = "data"
    return DistributedSqueezeEngine(layout, mesh, axis, workload, compute,
                                    fusion_k, interpret, exchange,
                                    macro_p=macro_p)
