"""Invariants of the locality-aware strip decomposition
(`core.compact.StripDecomposition`) — the static machinery behind the
neighbor-only p2p halo exchange.

The decomposition is pure host-side geometry, so everything here is
checked exhaustively in numpy: coverage (every block owned exactly
once), contiguity and balance of the row partition, the +-1-shard Moore
adjacency guarantee, full decode of the combined-coordinate table
against the layout's offset_table, interior/boundary classification,
routing buffer consistency, degenerate-mesh detection, and the wire
accounting the scaling gate reads.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fractals, maps
from repro.core.compact import (BlockLayout, StripDecomposition,
                                _balanced_contiguous_partition)

CONFIGS = [
    (fractals.SIERPINSKI, 5, 2, 2),
    (fractals.SIERPINSKI, 5, 2, 4),
    (fractals.SIERPINSKI, 7, 2, 8),
    (fractals.SIERPINSKI, 8, 1, 8),
    (fractals.CARPET, 3, 1, 4),
]


def _decomp(frac, r, m, ns):
    layout = BlockLayout(frac, r, m)
    layout.materialize()
    return layout, layout.strip_decomposition(ns)


@pytest.mark.parametrize("frac,r,m,ns", CONFIGS,
                         ids=lambda c: getattr(c, "name", c))
def test_perm_covers_every_block_once(frac, r, m, ns):
    layout, d = _decomp(frac, r, m, ns)
    assert d.valid
    real = d.perm[d.perm >= 0]
    assert sorted(real.tolist()) == list(range(layout.n_blocks))
    assert d.perm.shape == (d.nb_local * ns,)
    # shard_of/local_of invert perm
    for i, b in enumerate(d.perm):
        if b < 0:
            continue
        assert d.shard_of[b] == i // d.nb_local
        assert d.local_of[b] == i % d.nb_local


@pytest.mark.parametrize("frac,r,m,ns", CONFIGS,
                         ids=lambda c: getattr(c, "name", c))
def test_strips_are_contiguous_expanded_rows(frac, r, m, ns):
    """Each shard owns whole expanded block-grid rows, contiguous and
    ordered: rows never split, shard boundaries monotone in ey."""
    layout, d = _decomp(frac, r, m, ns)
    ey = layout.block_origin_expanded[:, 1] // layout.rho
    for y in np.unique(ey):
        shards = {int(d.shard_of[b]) for b in np.where(ey == y)[0]}
        assert len(shards) == 1, f"row {y} split across {shards}"
    row_shard = [int(d.shard_of[np.where(ey == y)[0][0]])
                 for y in np.unique(ey)]
    assert row_shard == sorted(row_shard), "strips out of row order"
    assert set(row_shard) == set(range(ns)), "some shard owns no row"


@pytest.mark.parametrize("frac,r,m,ns", CONFIGS,
                         ids=lambda c: getattr(c, "name", c))
def test_moore_neighbors_within_one_shard(frac, r, m, ns):
    """The load-bearing guarantee: every radius-1 neighbor of a block on
    shard s lives on shard s-1, s or s+1."""
    layout, d = _decomp(frac, r, m, ns)
    table = layout.neighbor_table
    for b in range(layout.n_blocks):
        for nb in table[b]:
            if nb == layout.ghost:
                continue
            assert abs(int(d.shard_of[nb]) - int(d.shard_of[b])) <= 1


@pytest.mark.parametrize("frac,r,m,ns", CONFIGS,
                         ids=lambda c: getattr(c, "name", c))
def test_combined_table_decodes_to_neighbor_table(frac, r, m, ns):
    """Full decode of the combined-coordinate table: every entry maps
    back to exactly the block offset_table(1) says — local slots to the
    shard's own strips, recv slabs through the neighbor's send buffer,
    the ghost row to layout.ghost."""
    layout, d = _decomp(frac, r, m, ns)
    nbl = d.nb_local
    want = layout.neighbor_table
    for s in range(ns):
        for li in range(nbl):
            b = d.perm[s * nbl + li]
            for dd in range(8):
                slot = int(d.table[s, li, dd])
                if b < 0:  # dead slot: all-ghost row
                    assert slot == nbl
                    continue
                wn = int(want[b, dd])
                if slot < nbl:                      # local strip
                    got = int(d.perm[s * nbl + slot])
                elif slot == nbl:                   # ghost zero row
                    got = layout.ghost
                elif slot < nbl + 1 + d.ms_next:    # from prev shard
                    j = slot - (nbl + 1)
                    lo = int(d.send_next_idx[s - 1, j])
                    got = (layout.ghost if lo == nbl
                           else int(d.perm[(s - 1) * nbl + lo]))
                else:                               # from next shard
                    j = slot - (nbl + 1 + d.ms_next)
                    lo = int(d.send_prev_idx[s + 1, j])
                    got = (layout.ghost if lo == nbl
                           else int(d.perm[(s + 1) * nbl + lo]))
                assert got == wn, (s, li, dd, slot, got, wn)


@pytest.mark.parametrize("frac,r,m,ns", CONFIGS,
                         ids=lambda c: getattr(c, "name", c))
def test_interior_boundary_partition(frac, r, m, ns):
    """Every boundary slot (one with a remote reference: a combined slot
    past the ghost row) lies in its shard's head [0, first row) with a
    prev neighbor, or in its tail [n - last row, n) with a next one
    (``edge_rows``, the ranges the p2p boundary pass recomputes); every
    other slot's table row is fully local; padding slots past the
    shard's n real blocks are interior."""
    layout, d = _decomp(frac, r, m, ns)
    nbl = d.nb_local
    real = (d.perm.reshape(ns, nbl) >= 0).sum(axis=1)
    for s in range(ns):
        first, last = d.edge_rows[s]
        n = real[s]
        remote = (d.table[s] > nbl).any(axis=1)
        for li in np.flatnonzero(remote):
            head = s > 0 and li < first
            tail = s < ns - 1 and n - last <= li < n
            assert head or tail, (s, li, first, last, n)
        assert not remote[n:].any()
        assert (d.table[s][~remote] <= nbl).all()
    assert d.edge_rows.sum() > 0


@pytest.mark.parametrize("frac,r,m,ns", CONFIGS,
                         ids=lambda c: getattr(c, "name", c))
def test_send_buffers_cover_remote_reads(frac, r, m, ns):
    """Whatever a shard's table reads from a recv slab, the neighbor's
    send buffer actually ships (no dangling routing slots), and send
    indices are valid local slots of the sender."""
    layout, d = _decomp(frac, r, m, ns)
    nbl = d.nb_local
    assert d.send_prev_idx.shape == (ns, d.ms_prev)
    assert d.send_next_idx.shape == (ns, d.ms_next)
    assert (d.send_prev_idx <= nbl).all()
    assert (d.send_next_idx <= nbl).all()
    # shard 0 has no prev neighbor, last shard no next: sentinel-only
    assert (d.send_prev_idx[0] == nbl).all()
    assert (d.send_next_idx[ns - 1] == nbl).all()


def test_degenerate_mesh_detected():
    """Fewer occupied expanded rows than shards -> invalid (the engine
    falls back to gather); never an exception."""
    layout = BlockLayout(fractals.SIERPINSKI, 3, 2)  # 2 block rows
    d = layout.strip_decomposition(8)
    assert not d.valid
    assert layout.strip_decomposition(2).valid


def test_single_shard_decomposition():
    """ns=1: everything local, no remote refs, zero wire bytes."""
    layout = BlockLayout(fractals.SIERPINSKI, 5, 2)
    d = layout.strip_decomposition(1)
    assert d.valid and d.nb_local == layout.n_blocks
    assert (d.table[0] <= d.nb_local).all()
    assert d.wire_bytes_per_exchange(2, 1) == 0


def test_memoized_per_layout():
    layout = BlockLayout(fractals.SIERPINSKI, 5, 2)
    assert layout.strip_decomposition(4) is layout.strip_decomposition(4)
    assert isinstance(layout.strip_decomposition(4), StripDecomposition)


def test_balanced_contiguous_partition():
    """Partition helper: contiguous groups, every group non-empty, max
    load minimized vs the trivial lower bound."""
    rng = np.random.default_rng(3)
    for _ in range(20):
        n = int(rng.integers(4, 30))
        g = int(rng.integers(1, n + 1))
        counts = rng.integers(1, 50, n)
        bounds = _balanced_contiguous_partition(counts, g)
        assert len(bounds) == g
        prev = 0
        loads = []
        for lo, hi in bounds:
            assert lo == prev and hi > lo
            loads.append(int(counts[lo:hi].sum()))
            prev = hi
        assert prev == n
        assert max(loads) >= counts.sum() / g  # sanity on the cap
        assert max(loads) <= counts.sum()      # and it is a partition


def test_wire_accounting_scales_with_shards_not_blocks():
    """Per-device wire bytes depend on the boundary geometry (ms_*),
    not on nb: the r=11/m=1 curve the scaling gate pins is flat."""
    layout = BlockLayout(fractals.SIERPINSKI, 8, 1)
    pd = {ns: layout.strip_decomposition(ns)
          .wire_bytes_per_device_per_exchange(2, 1)
          for ns in (2, 4, 8)}
    total = {ns: layout.strip_decomposition(ns)
             .wire_bytes_per_exchange(2, 1) for ns in (2, 4, 8)}
    # totals grow with the pair count, per-device stays within the
    # widest-row bound rather than tracking nb/ns
    assert total[8] == (layout.strip_decomposition(8).ms_prev
                        + layout.strip_decomposition(8).ms_next) * 7 \
        * layout.strip_decomposition(8).slot_bytes(2, 1)
    nb_share = layout.n_blocks // 8 * 4 * 2 * layout.rho
    assert pd[8] < nb_share, "per-device wire bytes track nb — not flat"


@pytest.mark.parametrize("frac,r,m", [
    (fractals.SIERPINSKI, 7, 2), (fractals.SIERPINSKI, 6, 0),
    (fractals.SIERPINSKI, 3, 2),
    (fractals.CARPET, 4, 1), (fractals.VICSEK, 4, 1),
    (fractals.EMPTY_BOTTLES, 3, 1), (fractals.CHANDELIER, 3, 0)],
    ids=lambda c: getattr(c, "name", c))
def test_block_tables_match_the_paper_maps(frac, r, m):
    """The host tables the decomposition is built from are the paper's
    maps at block granularity: each block's origin is lambda of its
    compact coordinate, and ``block_ids_at`` is nu with membership at
    every expanded block position, two beyond each edge included."""
    layout = BlockLayout(frac, r, m)
    bx, by = layout.block_coords
    ex, ey = maps.lambda_map(frac, layout.r_b, jnp.asarray(bx),
                             jnp.asarray(by))
    np.testing.assert_array_equal(
        layout.block_origin_expanded,
        np.stack([ex, ey], axis=1) * layout.rho)
    side = frac.s ** layout.r_b
    y, x = np.meshgrid(np.arange(-2, side + 2, dtype=np.int32),
                       np.arange(-2, side + 2, dtype=np.int32),
                       indexing="ij")
    cx, cy, valid = maps.nu_with_membership(frac, layout.r_b,
                                            jnp.asarray(x), jnp.asarray(y))
    want = np.where(valid, cy * layout.block_dims[1] + cx, layout.ghost)
    got = layout.block_ids_at(x, y)
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
