"""Multi-device compact fractal stencil: one domain sharded over a mesh,
k-fused launches with one halo exchange each, shard-local updates.

The compact block domain (the *only* thing in memory — the paper's P2 win)
is sharded along its leading block axis over a mesh axis (default "data").
One fused depth-``k`` launch advances ``k`` exact steps with ONE halo
exchange; the state itself is never gathered on one device, and neither
is the seeded initial state (``init_batch`` draws it shard by shard) nor
the finished one (``host_dense`` copies it to the host shard by shard).

Two exchange modes (``exchange=``):

``'p2p'`` (the default resolution of ``'auto'``) — neighbor-only
``jax.lax.ppermute`` overlapped with interior compute. Fractal adjacency
is non-local in *compact* (digit-interleaved) id order, but the lambda/nu
maps give a static block<->space correspondence, and
``BlockLayout.strip_decomposition`` uses it to assign each shard a
contiguous strip of expanded-space block rows (holes handled exactly —
only occupied rows exist). Rows are never split, so every cross-shard
Moore neighbor lives on shard +-1 and the whole exchange is two
``ppermute`` shifts of exactly the rows each neighbor needs
(``send_prev_idx`` / ``send_next_idx``). Each launch first updates every
local block from local data alone while the permutes are in flight (XLA
schedules it against the collectives from the data dependence alone),
then recomputes the shard's first and last expanded rows, which hold
every block with a remote neighbor, from local and received rows. The
XLA compute (``'jnp'``, kind ``'dist-block'``) ships whole blocks and
runs both passes through ``BlockLayout.map_chunks``, so its windows are
chunk-sized at any domain size (Sierpinski r=20 on four v5e chips); the
kernel computes ship depth-k edge strips
(``BlockLayout.pack_edge_strips``). Per-device exchanged bytes are
independent of the shard count — the flat scaling curve gated by
``benchmarks/distributed_bench.py --scaling``.

``'gather'`` — the fallback: ONE ``all_gather`` replicates every shard's
strips over the mesh axis, then each shard assembles halos from the
replicated buffer. Exchanged bytes grow ~linearly with device count, but
the scheme needs no decomposition, so it covers degenerate meshes where
the strip decomposition is invalid (fewer occupied expanded block rows
than shards). ``exchange='auto'`` resolves to p2p whenever the
decomposition is valid and falls back to gather otherwise;
``exchange='p2p'`` raises on a degenerate mesh.

Every launch reads one (n, 9) step table per shard (eight Moore
neighbors and the block itself, ghost-remapped) and rebuilds each
window's occupancy from its rows in-trace (``BlockLayout.halo_gate``):
no (n, w, w) mask exists on the host or the device. The shard-local
substeps are the v5 MXU macro-tile kernel (``compute='mxu'``), the v4
fused-depth kernel (``compute='fused'``) or the XLA window path
(``compute='jnp'``), all parameterized by the ``StencilWorkload``.

``run_program()`` is the whole run as one jitted program with a traced
step count: floor(steps/k) fused launches plus ONE remainder launch of
depth steps % k, so a run performs exactly ceil(steps/k) halo exchanges
— counted on the host from (steps, k) by ``count_run`` and asserted by
``exchange_stats()`` in the tests (``bytes_permuted``/``neighbor_sends``
on the p2p path, ``bytes_gathered`` on the gather path). The serving
runner compiles it ahead like every other kind's run. ``run(...,
donate=True)`` donates a batched state buffer to XLA.
"""
from __future__ import annotations

import dataclasses
import functools
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.compact import HOST_THREADS, BlockLayout, window_chunk
from repro.workloads.base import (StencilWorkload, cell_bits,
                                  check_workload_ndim)
from repro.workloads.rules import LIFE

Array = jnp.ndarray

#: shard-local compute backends: XLA window path, v4 fused-depth kernel,
#: v5 MXU macro-tile kernel
COMPUTES = ("jnp", "fused", "mxu")

#: halo-exchange modes: neighbor-only ppermute with interior/boundary
#: overlap, strip all-gather fallback, or pick-per-mesh
EXCHANGES = ("auto", "p2p", "gather")

#: most ``map_chunks`` chunks one pass over a shard takes. Each chunk is
#: one loop iteration of ~100 device operations, so at ``window_chunk``'s
#: size a shard of the r=20 domain (10.77 M blocks) ran ~1000 of them a
#: pass, ~11 K a request: the step spent its time between small
#: operations, and a profiler recorded ~1 M events a request on each
#: chip. Larger chunks cost little memory, since their windows stay
#: inside the fusions: a described-v5e compile of the r=20 run program
#: takes 9.03 GB a chip at 10922 blocks a chunk, 9.13 GB at 8 x 10922.
#: (Chunks that are no multiple of ``window_chunk``'s took 0.6 GB more
#: there, hence whole multiples.)
MAX_SHARD_CHUNKS = 128


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
        # host tables only, built with the engine's operands: a sharded
        # engine keeps no whole-domain table on any one device
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
            return self.n_shards * self.nb_local
        return _pad_blocks(self.layout, self.n_shards)

    @property
    def nb_local(self) -> int:
        """Slots per shard. On p2p, the decomposition's widest strip
        rounded up to whole ``map_chunks`` chunks, so that a launch's
        chunked pass needs no padded copy of the shard's table or
        state."""
        if self.exchange_mode != "p2p":
            return self.nb_padded // self.n_shards
        nbl = self.decomp.nb_local
        chunk = self._chunk(self.effective_fusion_k)
        return nbl if nbl <= chunk else -(-nbl // chunk) * chunk

    def _widen(self, ids: np.ndarray) -> np.ndarray:
        """Decomposition coordinates -> this engine's: ids at or past the
        decomposition's ``nb_local`` (its ghost row, then the received
        rows) move up by the slots ``nb_local`` adds."""
        dnbl = self.decomp.nb_local
        return np.where(ids >= dnbl, ids + (self.nb_local - dnbl),
                        ids).astype(np.int32)

    def _by_shard(self, x: np.ndarray, fill) -> np.ndarray:
        """(n_shards * d.nb_local, ...) decomposition rows ->
        (nb_padded, ...): each shard's rows padded to ``nb_local``."""
        ns, dnbl = self.n_shards, self.decomp.nb_local
        per = x.reshape((ns, dnbl) + x.shape[1:])
        out = np.full((ns, self.nb_local) + x.shape[1:], fill, x.dtype)
        out[:, :dnbl] = per
        return out.reshape((self.nb_padded,) + x.shape[1:])

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

    def slot_bytes(self, k: int) -> int:
        """Bytes one block puts on the p2p wire per channel: the whole
        block on the XLA compute (it is read through ``map_chunks``),
        the four depth-``k`` edge strips on the kernel computes."""
        itemsize = jnp.dtype(self.workload.dtype).itemsize
        if self.compute == "jnp":
            return self.layout.rho ** 2 * itemsize
        return self.decomp.slot_bytes(k, itemsize)

    def permute_bytes(self, k: int, batch: int = 1) -> int:
        """Total bytes moved over the wire by one depth-``k`` p2p
        exchange (both ppermute shifts, every adjacent shard pair)."""
        d = self.decomp
        return (batch * self.workload.n_channels * (d.ms_prev + d.ms_next)
                * (self.n_shards - 1) * self.slot_bytes(k))

    def wire_bytes_per_device(self, k: int, batch: int = 1) -> int:
        """Bytes one shard RECEIVES per depth-``k`` exchange — the
        per-device wire pressure the scaling bench records. Flat in the
        shard count on the p2p path (two neighbors regardless of mesh
        size); grows ~linearly on the gather path (everyone else's
        strips)."""
        itemsize = jnp.dtype(self.workload.dtype).itemsize
        if self.exchange_mode == "p2p":
            d = self.decomp
            return (batch * self.workload.n_channels
                    * (d.ms_prev + d.ms_next) * self.slot_bytes(k))
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

    # ------------------------------------------------------- static tables
    @functools.cached_property
    def _native_ids(self) -> np.ndarray:
        """(nb_padded,) compact block id of each native slot, -1 for the
        dead padding slots, which come after each shard's real ones."""
        if self.exchange_mode == "p2p":
            return self._by_shard(self.decomp.perm, -1)
        ids = np.full(self.nb_padded, -1, np.int32)
        ids[:self.layout.n_blocks] = np.arange(self.layout.n_blocks,
                                               dtype=np.int32)
        return ids

    @functools.cached_property
    def shard_blocks(self) -> np.ndarray:
        """(n_shards,) real (non-padding) blocks each shard holds."""
        live = (self._native_ids >= 0).reshape(self.n_shards, -1)
        return live.sum(axis=1)

    @property
    def edge_widths(self) -> Tuple[int, int]:
        """(head, tail): the p2p boundary pass recomputes slots [0, head)
        and [t, t + tail) of every shard, ``t`` its ``tail_start``: the
        widest first row of a shard with a prev neighbor and the widest
        last row of one with a next neighbor
        (``StripDecomposition.edge_rows``), which hold every block with
        a neighbor on another shard (at least one slot each)."""
        e = self.decomp.edge_rows
        return (int(max(e[1:, 0], default=1)),
                int(max(e[:-1, 1], default=1)))

    def _tail_starts(self) -> np.ndarray:
        """(n_shards, 1) int32 first slot of each shard's tail range."""
        tail = self.edge_widths[1]
        start = np.clip(self.shard_blocks - tail, 0, self.nb_local - tail)
        return start.astype(np.int32)[:, None]

    def _host_table(self) -> np.ndarray:
        """(nb_padded + n_shards, 9) int32 step table, each shard's
        ``nb_local`` rows followed by one all-ghost sentinel row.
        Columns 0-7 hold the Moore neighbors (``MOORE_DIRS`` order),
        column 8 the block itself, so that a dead padding slot, whose
        column 8 is the ghost, stays zero. p2p: the decomposition's
        combined per-shard coordinates ([0, nb_local) local, nb_local
        the ghost, then the received rows); gather: global native ids,
        ghost ``nb_padded`` (the appended zero strip). Written in place,
        one shard a thread: at r=20 each copy of it is 1.5 GB."""
        ns, nbl = self.n_shards, self.nb_local
        p2p = self.exchange_mode == "p2p"
        ghost = np.int32(nbl if p2p else self.nb_padded)
        live = (self._native_ids >= 0).reshape(ns, nbl)
        table = np.empty((ns, nbl + 1, 9), np.int32)
        layout = self.layout

        def fill(sh):
            t = table[sh]
            t[nbl] = ghost
            nbr = t[:nbl, :8]
            if p2p:  # ``_widen``, in place
                d = self.decomp
                t[d.nb_local:nbl, :8] = ghost
                nbr = nbr[:d.nb_local]
                nbr[...] = d.table[sh]
                np.add(nbr, nbl - d.nb_local, out=nbr,
                       where=nbr >= d.nb_local)
                own = np.arange(nbl, dtype=np.int32)
            else:
                lo = sh * nbl
                n = max(0, min(nbl, layout.n_blocks - lo))
                nbr[n:] = ghost
                real = layout.neighbor_table[lo:lo + n]
                nbr[:n] = np.where(real == layout.ghost, ghost, real)
                own = np.arange(lo, lo + nbl, dtype=np.int32)
            t[:nbl, 8] = np.where(live[sh], own, ghost)

        with ThreadPoolExecutor(ns) as pool:
            list(pool.map(fill, range(ns)))
        return table.reshape(-1, 9)

    @functools.cached_property
    def _tables(self):
        """(operands, origins): the device tables every launch reads,
        sharded over the block axis, and each native slot's expanded
        origin ((nb_padded, 2) int32, -1 on padding slots), which the
        seeded draw reads. Built once, on the host, then placed shard
        by shard: no (nb, w, w) occupancy array exists anywhere (the
        step rebuilds it per chunk from the table rows,
        ``BlockLayout.halo_gate``)."""
        layout = self.layout
        row = NamedSharding(self.mesh, P(self.axis, None))
        with obs.span("dist.build_tables", r=layout.r,
                      shards=self.n_shards, exchange=self.exchange_mode):
            table = self._host_table()
            org = np.concatenate(
                [layout.block_origin_expanded,
                 np.full((1, 2), -1, np.int32)], axis=0)
            ids = self._native_ids
            origins = org[np.where(ids >= 0, ids, layout.n_blocks)]
            ops = (table,)
            if self.exchange_mode == "p2p":
                d = self.decomp
                ops += (self._widen(d.send_prev_idx),
                        self._widen(d.send_next_idx), self._tail_starts())
            with jax.ensure_compile_time_eval():
                ops = tuple(jax.device_put(o, row) for o in ops)
                origins = jax.device_put(origins, row)
        return ops, origins

    def operands(self) -> Tuple[Array, ...]:
        """The sharded device tables every launch takes as arguments:
        the step table (``_host_table``) and, on the p2p path, the
        routing rows (send_prev, send_next) and the tail range's start
        (``edge_widths``)."""
        return self._tables[0]

    # ------------------------------------------------------------ state I/O
    @functools.cached_property
    def _dense_src(self) -> Optional[np.ndarray]:
        """(n_blocks,) native slot of each compact block id — None on the
        gather path, whose native order is compact order + dead tail."""
        if self.exchange_mode != "p2p":
            return None
        d = self.decomp
        return (d.shard_of.astype(np.int64) * self.nb_local
                + d.local_of).astype(np.int32)

    def _pad_state(self, dense: Array) -> Array:
        """Compact-order (B?, C?, n_blocks, rho, rho) -> engine-native
        block order (permuted strips on p2p, identity + dead tail on
        gather)."""
        src = np.where(self._native_ids >= 0, self._native_ids,
                       np.int32(self.layout.n_blocks))
        zshape = dense.shape[:-3] + (1,) + dense.shape[-2:]
        dense_z = jnp.concatenate(
            [dense, jnp.zeros(zshape, dense.dtype)], axis=-3)
        return dense_z[..., src, :, :]

    @functools.cached_property
    def _init_fn(self):
        """Jitted seeded draw of B initial states, (B,) seeds ->
        (B, C?, nb_padded, rho, rho) in native order, computed shard by
        shard from ``workloads.base.cell_bits`` at each slot's expanded
        origin (padding slots zero): the field of
        ``BlockLayout.init_state``, and no device holds all of it."""
        layout, wl = self.layout, self.workload
        rho = layout.rho
        chan = 1 if wl.n_channels > 1 else 0
        mask = layout.micro_mask

        def init(seeds, org):
            iy, ix = jnp.meshgrid(jnp.arange(rho, dtype=jnp.int32),
                                  jnp.arange(rho, dtype=jnp.int32),
                                  indexing="ij")
            live = (org[:, 0] >= 0)[:, None, None] & (
                jnp.asarray(mask)[None] > 0)

            def one(seed):
                bits = cell_bits(seed, org[:, 0, None, None] + ix[None],
                                 org[:, 1, None, None] + iy[None])
                field = wl.init(bits)
                return field * live.astype(field.dtype)

            return jax.vmap(one)(seeds)

        return jax.jit(init, out_shardings=self.sharding(4 + chan))

    def init_batch(self, seeds) -> Array:
        """Independent seeded initial states, (B, C?, nb_padded, rho,
        rho), sharded over the block axis (drawn per shard)."""
        seeds = jnp.asarray(list(seeds), jnp.int32)
        return self._init_fn(seeds, self._tables[1])

    def init_random(self, seed: int) -> Array:
        return self.init_batch(jnp.reshape(jnp.asarray(seed, jnp.int32),
                                           (1,)))[0]

    def to_dense(self, state: Array) -> Array:
        """Engine-native -> compact block order (for comparison against
        single-device and for mesh-independent checkpoints)."""
        src = self._dense_src
        if src is None:
            return state[..., : self.layout.n_blocks, :, :]
        return state[..., src, :, :]

    def host_dense(self, state: Array) -> np.ndarray:
        """Host copy of ``state`` (C?, nb_padded, rho, rho) in compact
        block order, made shard by shard, each in its own thread: the
        shard's addressable data comes to the host in pieces
        (``serving.handoff.host_pieces``), and each piece's real blocks
        are scattered to their compact ids. No device gathers the whole
        state."""
        from repro.serving import handoff
        ids = self._native_ids
        shape = state.shape[:-3] + (self.layout.n_blocks,) + state.shape[-2:]
        out = np.empty(shape, np.dtype(state.dtype))
        words = _row_words(out)

        def land(shard):
            first = shard.index[-3].start or 0
            sh = first // self.nb_local
            live = int(self.shard_blocks[sh])  # a shard's real slots lead
            with obs.span("serve.host_copy_shard", shard=sh):
                for a, b, host in handoff.host_pieces(shard.data, -3):
                    n = min(max(live - a, 0), b - a)
                    # 8-byte words move a 256-byte block much faster than
                    # numpy's per-element u8 scatter, and a slice of the
                    # real slots than a mask of them
                    words[..., ids[first + a:first + a + n], :] = (
                        _row_words(host)[..., :n, :])

        # touch the fresh result's pages first, in parallel: faulted in
        # one at a time by the scatter, they cost ~1 s a GB on a v5e host
        with ThreadPoolExecutor(HOST_THREADS) as pool:
            list(pool.map(np.ndarray.fill,
                          np.array_split(out.reshape(-1), HOST_THREADS),
                          [0] * HOST_THREADS))
        shards = state.addressable_shards
        with ThreadPoolExecutor(len(shards)) as pool:
            list(pool.map(land, shards))
        return out

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
        hole = (1 - self.layout.micro_mask).astype(np.uint8)
        dead = np.broadcast_to(hole, (self.nb_padded,) + hole.shape).copy()
        dead[self._native_ids < 0] = 1
        return dead

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

    # ---------------------------------------------------- shard-local compute
    def _chunk(self, k: int) -> int:
        """Blocks per ``map_chunks`` chunk: the fewest whole multiples of
        ``window_chunk``'s that keep a shard's pass within
        ``MAX_SHARD_CHUNKS`` chunks."""
        base = window_chunk(self.layout.rho, k, self.workload.n_channels)
        return base * max(1, -(-self.decomp.nb_local
                               // (base * MAX_SHARD_CHUNKS)))

    def _block_update(self, k: int, ghost: int):
        """``map_chunks`` update of the XLA path: depth-``k`` windows of
        a chunk, their occupancy rebuilt from the chunk's table rows
        (``halo_gate``; a padding slot's column 8 is the ghost, so its
        window is all dead), then the workload's k fused substeps."""
        layout, wl = self.layout, self.workload

        def update(center, nbr, rows):
            padded = layout.halo_window_k(center, nbr, k)
            return self._substeps(padded, rows, ghost, k).astype(
                center.dtype)

        return update

    def _substeps(self, padded: Array, rows: Array, ghost: int,
                  k: int) -> Array:
        """The workload's k fused substeps on (B, C, n, rho+2k, rho+2k)
        windows, under the occupancy rebuilt from their (n, 9) table
        rows: ghost neighbors' regions dead, and the whole window of a
        padding slot (column 8 the ghost)."""
        layout, wl = self.layout, self.workload
        gate = layout.halo_gate(k, rows[:, :8], ghost) * (
            rows[:, 8] != ghost).astype(jnp.uint8)[:, None, None]

        def one(p):  # (C, n, w, w) -> (C, n, rho, rho)
            if wl.n_channels > 1:
                return wl.tile_rule_k(p, gate, k)
            return wl.tile_rule_k(p[0], gate, k)[None]

        return jax.vmap(one)(padded)

    def _compute_k(self, states: Array, halo, rows: Array, ghost: int,
                   k: int) -> Array:
        """k fused substeps on one set of blocks from strip halos:
        ``states`` (B, C, n_sel, rho, rho), ``halo`` the matching
        depth-k pieces, ``rows`` their (n_sel, 9) step-table rows. The
        kernel computes on both exchanges, and the XLA compute on the
        gather exchange."""
        layout = self.layout
        rho = layout.rho
        existence = (rows[:, :8] != ghost).astype(jnp.int32)
        alive = rows[:, 8] != ghost
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
            return self._jnp_step_k(states, halo, rows, ghost, k)
        # the kernels gate halo regions in-kernel but keep the periodic
        # center mask — one multiply re-kills padding slots
        center = jnp.asarray(layout.micro_mask)[None] * alive[:, None, None]
        return out * center.astype(out.dtype)

    def _local_step_k(self, state_local: Array, table: Array,
                      k: int) -> Array:
        """One fused depth-``k`` gather-mode launch on this shard: pack
        strips, ONE all_gather, assemble halos, run k substeps locally.

        state_local (B, C, nb_local, rho, rho) -> same, k steps later;
        ``table`` is this shard's (nb_local + 1, 9) rows of the step
        table (global strip ids, ghost ``nb_padded``)."""
        layout, axis = self.layout, self.axis
        rho, nbl = layout.rho, self.nb_local
        b, nc = state_local.shape[0], state_local.shape[1]
        rows = table[:nbl]

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
            for h in layout.halo_from_strips_k(strips, rows[:, :8], k))
        return self._compute_k(state_local, halo, rows, self.nb_padded, k)

    def _exchange_p2p(self, to_prev: Array, to_next: Array):
        """The two neighbor-only permute shifts: this shard's rows for
        its prev and its next neighbor out, theirs in. Returns (from
        prev, from next)."""
        ns, axis = self.n_shards, self.axis
        fwd = [(i, i + 1) for i in range(ns - 1)]
        bwd = [(i + 1, i) for i in range(ns - 1)]
        return (jax.lax.ppermute(to_next, axis, fwd),
                jax.lax.ppermute(to_prev, axis, bwd))

    def _boundary_ranges(self, tail_start: Array):
        """(start, width) of the head and tail slot ranges the boundary
        pass recomputes (``edge_widths``); they may overlap, and every
        block in them comes out exact."""
        head, tail = self.edge_widths
        return ((0, head), (tail_start[0, 0], tail))

    def _local_step_k_p2p(self, state_local: Array, table: Array,
                          send_prev: Array, send_next: Array,
                          tail_start: Array, k: int) -> Array:
        """One fused depth-``k`` p2p launch on this shard: start the two
        neighbor ``ppermute`` shifts, update every local block from
        local data only WHILE they are in flight (remote neighbors read
        as zero, so boundary blocks come out provisional and interior
        ones final), then recompute the head and tail slot ranges,
        which hold every boundary block, with the received rows and
        write them back in place.

        state_local (B, C, nb_local, rho, rho) -> same, k steps later.
        ``table`` is this shard's (nb_local + 1, 9) step-table rows in
        the decomposition's combined coordinates ([0, nb_local) local |
        nb_local the ghost | ms_next rows from prev | ms_prev from
        next); ``send_prev``/``send_next`` this shard's (1, m) routing
        rows (indices into [0, nb_local], nb_local the zero row),
        ``tail_start`` its (1, 1) tail range start."""
        if self.compute == "jnp":
            return self._local_step_k_p2p_blocks(
                state_local, table, send_prev, send_next, tail_start, k)
        layout = self.layout
        rho, nbl = layout.rho, self.nb_local
        b, nc = state_local.shape[0], state_local.shape[1]

        # 1. pack my edge bands + the shared ghost/sentinel zero row
        flat = state_local.reshape(b * nc, nbl, rho, rho)
        strips = layout.pack_edge_strips(flat, k)
        strips_z = jnp.concatenate(
            [strips,
             jnp.zeros((strips.shape[0], 1) + strips.shape[2:],
                       strips.dtype)], axis=1)
        # 2. halo exchange: ONLY the strips each neighbor needs (dead
        # routing slots ship the zero row)
        recv_prev, recv_next = self._exchange_p2p(
            strips_z[:, send_prev[0]], strips_z[:, send_next[0]])
        # 3a. full-domain overlap pass: every remote reference reads the
        # ghost zero row, so these kernels have no data dependence on
        # the in-flight permutes
        table_int = jnp.minimum(table[:nbl, :8], nbl)
        halo_full = tuple(
            h.reshape((b, nc) + h.shape[1:])
            for h in layout.halo_from_strips_k(strips_z, table_int, k))
        out = self._compute_k(state_local, halo_full, table[:nbl], nbl, k)
        # 3b. boundary fix-up from local + received strips
        combined = jnp.concatenate(
            [strips_z, recv_prev, recv_next], axis=1)
        for start, width in self._boundary_ranges(tail_start):
            rows = jax.lax.dynamic_slice_in_dim(table, start, width, 0)
            halo = tuple(
                h.reshape((b, nc) + h.shape[1:]) for h in
                layout.halo_from_strips_k(combined, rows[:, :8], k))
            part = jax.lax.dynamic_slice_in_dim(state_local, start, width,
                                                2)
            out = jax.lax.dynamic_update_slice_in_dim(
                out, self._compute_k(part, halo, rows, nbl, k), start, 2)
        return out

    def _local_step_k_p2p_blocks(self, state_local: Array, table: Array,
                                 send_prev: Array, send_next: Array,
                                 tail_start: Array, k: int) -> Array:
        """The XLA compute of a p2p launch: whole boundary blocks on the
        wire, and both passes through ``BlockLayout.map_chunks``, so
        every window is chunk-sized however large the shard is. The
        interior pass reads local blocks only; the boundary pass reads
        one buffer of local blocks and received rows (``extra``: the
        zero row, then the rows from prev, then from next, which is the
        combined coordinate convention) and writes its two slot ranges
        back in place (a scatter would relay the whole state out)."""
        layout = self.layout
        rho, nbl = layout.rho, self.nb_local
        b, nc = state_local.shape[0], state_local.shape[1]
        ranges = self._boundary_ranges(tail_start)

        def send(idx, start, width):
            # the rows a neighbor needs lie in one edge range: gather them
            # from its slice, not from the whole state (which a gather
            # would relay out first)
            part = jax.lax.dynamic_slice_in_dim(state_local, start, width,
                                                2)
            lane = part.reshape(b * nc, width, rho * rho)
            return layout.block_rows(lane, idx[0] - start, width,
                                     rho).reshape(b * nc, -1, rho * rho)

        recv_prev, recv_next = self._exchange_p2p(
            send(send_prev, *ranges[0]), send(send_next, *ranges[1]))
        zero = jnp.zeros((b, nc, 1, rho * rho), state_local.dtype)
        update = self._block_update(k, nbl)
        chunk = self._chunk(k)
        # the three passes gather from one source (one packing a launch)
        source = layout.chunk_source(state_local)
        out = layout.map_chunks(state_local, table[:nbl], chunk, update,
                                source=source)
        extra = jnp.concatenate(
            [zero, recv_prev.reshape((b, nc) + recv_prev.shape[1:]),
             recv_next.reshape((b, nc) + recv_next.shape[1:])], axis=2)
        for start, width in ranges:
            ids = start + jnp.arange(width, dtype=table.dtype)
            rows = jax.lax.dynamic_slice_in_dim(table, start, width, 0)
            part = layout.map_chunks(state_local, rows, chunk, update,
                                     ids=ids, extra=extra, source=source)
            out = jax.lax.dynamic_update_slice_in_dim(out, part, start, 2)
        return out

    def _jnp_step_k(self, states: Array, halo, rows: Array, ghost: int,
                    k: int) -> Array:
        """XLA window path of the gather exchange: assemble (B, C, n_sel,
        rho+2k, rho+2k) tiles from strip halos and run the workload's k
        fused substeps under the occupancy rebuilt from the table rows
        (padding slots all dead)."""
        rho = self.layout.rho
        w = rho + 2 * k
        top, bot, west, east = halo
        b, nc, nbl = states.shape[:3]
        padded = jnp.zeros((b, nc, nbl, w, w), states.dtype)
        padded = padded.at[..., k:k + rho, k:k + rho].set(states)
        padded = padded.at[..., :k, :].set(top)
        padded = padded.at[..., w - k:, :].set(bot)
        padded = padded.at[..., k:k + rho, :k].set(west)
        padded = padded.at[..., k:k + rho, w - k:].set(east)
        return self._substeps(padded, rows, ghost, k).astype(states.dtype)

    # ------------------------------------------------------- compiled steps
    @functools.cached_property
    def _cache(self) -> dict:
        """Per-instance memo of jitted step/run programs."""
        return {}

    def _memo(self, key, build):
        cache = self._cache
        if key not in cache:
            cache[key] = build()
        return cache[key]

    def _launch(self, k: int):
        """One fused depth-``k`` launch (one halo exchange) as a
        shard_map over lane-dense (B, C, nb_padded, rho*rho) states and
        the sharded operands. Lane-dense, because the launch reads and
        writes whole block rows: a state kept as (rho, rho) tiles would
        be relaid out, whole, around every launch."""
        rho = self.layout.rho
        lane = P(None, None, self.axis, None)
        row = P(self.axis, None)
        if self.exchange_mode == "p2p":
            step = functools.partial(self._local_step_k_p2p, k=k)
            in_specs = (lane, row, row, row, row)
        else:
            step = functools.partial(self._local_step_k, k=k)
            in_specs = (lane, row)

        def local(x, *ops):
            b, c, n, _ = x.shape
            return step(x.reshape(b, c, n, rho, rho), *ops).reshape(x.shape)

        # no varying-axes check: pallas_call has no shard_map rule for
        # it, and map_chunks' loop carries a fresh (unvarying) buffer
        return jax.shard_map(local, mesh=self.mesh, in_specs=in_specs,
                             out_specs=lane, check_vma=False)

    def _step5_fn(self, k: int, donate: bool = False):
        """Jitted single depth-``k`` launch over canonical 5D states."""
        def build():
            launch = self._launch(k)

            def step(s5, *ops):
                lane = s5.reshape(s5.shape[:3] + (-1,))
                return launch(lane, *ops).reshape(s5.shape)

            return jax.jit(step, donate_argnums=0) if donate \
                else jax.jit(step)
        return self._memo(("step5", k, donate), build)

    def _call_step(self, k: int, s5: Array, donate: bool = False) -> Array:
        return self._step5_fn(k, donate)(s5, *self.operands())

    def run_program(self, donate: bool = False):
        """The whole run as ONE jitted program, ``(states, steps, ops)
        -> states`` with ``states`` (B, C?, nb_padded, rho, rho) and
        ``steps`` traced: a ``fori_loop`` of depth-k launches, then one
        launch of depth ``steps % k`` chosen by ``lax.switch`` (none when
        it is 0), so a run makes exactly ceil(steps/k) exchanges and
        one compile serves every step count. It names its own in/out
        shardings, so it lowers from unsharded shapes."""
        def build():
            k = self.effective_fusion_k
            launches = [self._launch(j) for j in range(1, k + 1)]
            branches = [lambda s, *ops: s] + launches[:-1]

            def run(states, steps, ops):
                s5, _ = self._canon(states)
                lane = s5.reshape(s5.shape[:3] + (-1,))
                lane = jax.lax.fori_loop(
                    0, steps // k, lambda _, s: launches[-1](s, *ops), lane)
                lane = jax.lax.switch(steps % k, branches, lane, *ops)
                return lane.reshape(states.shape)

            state = self.sharding(4 + (self.workload.n_channels > 1))
            row = NamedSharding(self.mesh, P(self.axis, None))
            n_ops = 4 if self.exchange_mode == "p2p" else 1
            return jax.jit(
                run, in_shardings=(state, NamedSharding(self.mesh, P()),
                                   (row,) * n_ops),
                out_shardings=state, donate_argnums=0 if donate else ())
        return self._memo(("run", donate), build)

    def count_run(self, steps: int, batch: int) -> None:
        """Account one run of ``steps`` on ``batch`` states, from (steps,
        k) on the host: floor(steps/k) depth-k launches plus one
        remainder launch. With telemetry on, also sets the
        ``dist.shard_live_cells{shard}`` gauge (live cells of one state
        on each shard)."""
        k = self.effective_fusion_k
        n_fused, rem = divmod(int(steps), k)
        if n_fused:
            self._account(k, n_fused, batch)
        if rem:
            self._account(rem, 1, batch)
        if obs.enabled():
            per_block = int(self.layout.micro_mask.sum())
            for sh, n in enumerate(self.shard_blocks):
                obs.set_gauge("dist.shard_live_cells", int(n) * per_block,
                              shard=str(sh))

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
        """``steps`` steps through ``run_program``: floor(steps/k) fused
        depth-k launches plus ONE remainder launch of depth steps % k —
        exactly ceil(steps/k) halo exchanges total. ``donate=True``
        donates a batched state buffer to XLA (zero-copy stepping; the
        caller must not reuse ``state`` afterwards)."""
        steps = int(steps)
        if steps < 0:
            raise ValueError(f"steps must be >= 0, got {steps}")
        batched = self._canon(state)[1]
        states = state if batched else state[None]
        states = jax.device_put(states, self.sharding(states.ndim))
        k = self.effective_fusion_k
        with obs.span("dist.run", compute=self.compute, steps=steps,
                      k=k, batch=states.shape[0]):
            out = self.run_program(donate and batched)(
                states, jnp.int32(steps), self.operands())
        self.count_run(steps, states.shape[0])
        if donate:
            obs.inc("engine.donated_runs",
                    engine=type(self).__name__, variant=self.compute)
        return out if batched else out[0]

    def lowered_step_text(self, state: Array, k: int) -> str:
        """Lowered StableHLO of one fused depth-``k`` launch — the tests
        count its collectives (one all_gather per gather launch; two
        collective_permutes and ZERO all_gathers per p2p launch)."""
        s5, _ = self._canon(state)
        return self._step5_fn(k).lower(
            s5, *self.operands()).as_text()


def _row_words(x: np.ndarray) -> np.ndarray:
    """``x`` (..., n, rho, rho) as (..., n, W) words of the widest
    unsigned type that divides a block's bytes (a view)."""
    per = x.shape[-1] * x.shape[-2] * x.dtype.itemsize
    size = next(s for s in (8, 4, 2, 1) if per % s == 0)
    return x.view(np.uint8).reshape(x.shape[:-2] + (per,)).view(
        f"<u{size}")


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
