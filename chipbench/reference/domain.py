"""The plain reference's view of a fractal domain, built from a
configuration file alone.

Nothing here imports the program. The fractal is read from the
configuration (`s` and the occupied slots in replica order), the
program's compact block order is rebuilt from the paper's lambda map
(Squeeze, arXiv:2201.00613, Eqs. 2-5), and the seeded initial field is
the documented per-cell hash of the expanded coordinates.

The reference simulates in the expanded embedding, tile by tile: a
tile of side `T = s**t` is simulated in a window `halo` cells wider on
each side, with every cell outside the window dead. After `n <= halo`
steps the tile's own cells are exact, since a step moves information by
one cell. Only tiles that hold fractal cells are simulated.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

#: bytes of window state (all tiles of one batch, all channels) that one
#: jitted tile batch may hold
BATCH_BYTES = 512 * 2 ** 20


@dataclasses.dataclass(frozen=True)
class Fractal:
    """An NBB fractal: an `s x s` grid of slots, `positions` occupied,
    listed as (x, y) in replica order (y grows downward)."""

    s: int
    positions: tuple

    @classmethod
    def from_config(cls, cfg: dict) -> "Fractal":
        f = cfg["fractal"]
        return cls(int(f["s"]), tuple(tuple(int(v) for v in p)
                                      for p in f["positions"]))

    @property
    def k(self) -> int:
        return len(self.positions)

    @functools.cached_property
    def slots(self) -> np.ndarray:
        """(s, s) uint8 occupancy indexed [y, x]."""
        g = np.zeros((self.s, self.s), np.uint8)
        for x, y in self.positions:
            g[y, x] = 1
        return g

    def mask(self, level: int) -> np.ndarray:
        """(s**level, s**level) uint8 occupancy, [y, x]."""
        m = np.ones((1, 1), np.uint8)
        for _ in range(level):
            m = np.kron(self.slots, m)
        return m

    def occupied(self, level: int, x: np.ndarray, y: np.ndarray):
        """Whether cell (x, y) of the level-`level` embedding is in the
        fractal; False outside [0, s**level)."""
        x = np.asarray(x, np.int64)
        y = np.asarray(y, np.int64)
        n = self.s ** level
        ok = (x >= 0) & (x < n) & (y >= 0) & (y < n)
        xc, yc = np.clip(x, 0, n - 1), np.clip(y, 0, n - 1)
        for _ in range(level):
            ok &= self.slots[yc % self.s, xc % self.s] == 1
            xc, yc = xc // self.s, yc // self.s
        return ok

    def lambda_map(self, level: int, cx: np.ndarray, cy: np.ndarray):
        """Compact (cx, cy) -> expanded (ex, ey) at `level` (paper Eqs.
        2-5): digit mu-1 of the compact coordinate, read from x at odd
        levels and from y at even ones, picks the replica whose slot is
        added at scale s**(mu-1)."""
        pos = np.asarray(self.positions, np.int64)
        ex = np.zeros(np.shape(cx), np.int64)
        ey = np.zeros(np.shape(cy), np.int64)
        for mu in range(1, level + 1):
            w = cx if mu % 2 == 1 else cy
            beta = (np.asarray(w, np.int64)
                    // self.k ** ((mu - 1) // 2)) % self.k
            ex += pos[beta, 0] * self.s ** (mu - 1)
            ey += pos[beta, 1] * self.s ** (mu - 1)
        return ex, ey

    def block_origins(self, r: int, m: int):
        """(bx, by): the expanded position, in units of blocks of side
        s**m, of each block of a level-r domain, in the program's compact
        block order (id = by_c * cols + bx_c on the level-(r-m) compact
        rectangle of k**floor(rb/2) rows by k**ceil(rb/2) columns)."""
        rb = r - m
        cols = self.k ** ((rb + 1) // 2)
        ids = np.arange(self.k ** rb, dtype=np.int64)
        return self.lambda_map(rb, ids % cols, ids // cols)


# ------------------------------------------------------- seeded field
def _mix32(x):
    """The lowbias32 finalizer on uint32."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def cell_bits(seed, x, y):
    """One uint32 word per cell from the request seed and the cell's
    expanded coordinates: h = mix(seed ^ 0x9E3779B9), then h = mix(h ^
    x), then h = mix(h ^ y)."""
    h = _mix32(jnp.asarray(seed).astype(jnp.uint32) ^ jnp.uint32(0x9E3779B9))
    h = _mix32(h ^ x.astype(jnp.uint32))
    return _mix32(h ^ y.astype(jnp.uint32))


# -------------------------------------------------------- tile plan
@dataclasses.dataclass(frozen=True)
class TilePlan:
    """Which tiles to simulate and where each compact block lies."""

    frac: Fractal
    r: int
    m: int
    t: int            # tile side T = s**t expanded cells
    halo: int
    tiles: np.ndarray       # (n_tiles, 2) tile coordinates (tx, ty)
    block_tile: np.ndarray  # (n_blocks,) tile index of each block
    block_ly: np.ndarray    # (n_blocks,) block row inside its tile
    block_lx: np.ndarray    # (n_blocks,) block column inside its tile

    @property
    def side(self) -> int:
        return self.frac.s ** self.t

    @property
    def window(self) -> int:
        return self.side + 2 * self.halo

    @property
    def rho(self) -> int:
        return self.frac.s ** self.m

    def neighbourhood(self, idx: np.ndarray) -> np.ndarray:
        """(len(idx), 3, 3) uint8: which of the 3 x 3 tiles around each
        tile (rows dy, columns dx) hold fractal cells."""
        tx, ty = self.tiles[idx, 0], self.tiles[idx, 1]
        out = np.zeros((len(idx), 3, 3), np.uint8)
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                out[:, dy + 1, dx + 1] = self.frac.occupied(
                    self.r - self.t, tx + dx, ty + dy)
        return out


def plan_tiles(frac: Fractal, r: int, m: int, halo: int) -> TilePlan:
    """Choose the tile level that simulates the fewest window cells
    (tile side at least the block side and the halo) and place every
    compact block in its tile."""
    best = None
    for t in range(m, r + 1):
        side = frac.s ** t
        if side < halo:
            continue
        cells = frac.k ** (r - t) * (side + 2 * halo) ** 2
        if best is None or cells < best[0]:
            best = (cells, t)
    if best is None:
        raise ValueError(f"halo {halo} is wider than the whole domain")
    t = best[1]
    bx, by = frac.block_origins(r, m)
    per = frac.s ** (t - m)  # blocks per tile side
    tx, ty = bx // per, by // per
    keys = ty * frac.s ** (r - t) + tx
    uniq, block_tile = np.unique(keys, return_inverse=True)
    tiles = np.stack([uniq % frac.s ** (r - t), uniq // frac.s ** (r - t)],
                     axis=1)
    return TilePlan(frac, r, m, t, halo, tiles, block_tile.astype(np.int64),
                    (by % per).astype(np.int64), (bx % per).astype(np.int64))


def tile_batches(plan: TilePlan, cell_bytes: int):
    """Index arrays of the tiles simulated together, all of one size
    (the last batch is padded with repeats of its first tile)."""
    n = len(plan.tiles)
    size = max(1, min(n, BATCH_BYTES // (cell_bytes * plan.window ** 2)))
    for lo in range(0, n, size):
        idx = np.arange(lo, min(lo + size, n))
        pad = np.full(size - len(idx), idx[0])
        yield idx, np.concatenate([idx, pad])


def window_mask(side: int, halo: int, low, nbhd):
    """(B, W, W) occupancy of each tile's window: the level-t mask
    `low` (T x T) repeated over the 3 x 3 tiles around the tile, each
    gated by whether that tile holds fractal cells, cropped to the
    window."""
    b = nbhd.shape[0]
    big = (low[None, None, :, None, :]
           * nbhd[:, :, None, :, None])          # (B, 3, T, 3, T)
    big = big.reshape(b, 3 * side, 3 * side)
    return big[:, side - halo:2 * side + halo, side - halo:2 * side + halo]


def window_coords(side: int, halo: int, tiles):
    """(B, W, W) expanded x and y of every window cell."""
    w = side + 2 * halo
    off = jnp.arange(w, dtype=jnp.int32) - halo
    x = tiles[:, 0, None, None] * side + off[None, None, :]
    y = tiles[:, 1, None, None] * side + off[None, :, None]
    return (jnp.broadcast_to(x, (tiles.shape[0], w, w)),
            jnp.broadcast_to(y, (tiles.shape[0], w, w)))


def shifts(x):
    """The 8 Moore neighbours of every cell of (..., H, W), cells beyond
    the edge dead: dict (dx, dy) -> (..., H, W)."""
    pad = [(0, 0)] * (x.ndim - 2) + [(1, 1), (1, 1)]
    p = jnp.pad(x, pad)
    h, w = x.shape[-2:]
    return {(dx, dy): p[..., 1 + dy:h + 1 + dy, 1 + dx:w + 1 + dx]
            for dy in (-1, 0, 1) for dx in (-1, 0, 1) if (dx, dy) != (0, 0)}


def tile_blocks(plan: TilePlan, rule, seed: int, checkpoints,
                control=None):
    """Run `rule` from the seeded field, tile batch by tile batch, and
    yield (step, ids, blocks) at each step in `checkpoints` (the largest
    at most the plan's halo): the compact ids of the batch's blocks and
    their (C?, n, rho, rho) states, both on the device. `control`
    selects the rule's deliberately wrong variant."""
    checkpoints = sorted(set(int(c) for c in checkpoints))
    if checkpoints[-1] > plan.halo:
        raise ValueError(f"step {checkpoints[-1]} beyond halo {plan.halo}")
    low = plan.frac.mask(plan.t)
    low_step = rule.step_mask(plan, control)
    seg = np.diff([0] + checkpoints)
    run = _tile_runner(rule, plan.window, control)
    start = _tile_start(rule, plan.side, plan.halo, control)
    take = _block_taker(plan.side, plan.halo, plan.rho, rule.channels)
    order = np.argsort(plan.block_tile, kind="stable")
    sorted_tile = plan.block_tile[order]
    low_d = jnp.asarray(low)
    low_step_d = low_d if low_step is None else jnp.asarray(low_step)
    for idx, padded in tile_batches(plan, rule.cell_bytes):
        state, mask = start(jnp.asarray(plan.tiles[padded], jnp.int32),
                            jnp.asarray(plan.neighbourhood(padded)),
                            low_d, low_step_d, jnp.uint32(seed))
        a, b = np.searchsorted(sorted_tile, [idx[0], idx[-1] + 1])
        ids = order[a:b]
        where = (jnp.asarray(plan.block_tile[ids] - idx[0], jnp.int32),
                 jnp.asarray(plan.block_ly[ids], jnp.int32),
                 jnp.asarray(plan.block_lx[ids], jnp.int32))
        ids = jnp.asarray(ids, jnp.int32)
        for c, n in zip(checkpoints, seg):
            state = run(state, mask, jnp.int32(n))
            yield c, ids, take(state, *where)


def compare_states(plan: TilePlan, rule, seed: int, states: dict) -> float:
    """The rule's number (`rule.compare`) between the program's states
    {step: (C?, n_blocks, rho, rho) host array} and the reference,
    computed on the device batch by batch."""
    shape = rule.channel_shape + (len(plan.block_tile), plan.rho, plan.rho)
    worst = 0.0
    dev = {}
    for step, got in states.items():
        got = np.asarray(got)
        if got.shape != shape or got.dtype != rule.dtype:
            return rule.mismatch(shape)
        dev[step] = jax.device_put(got)
    acc = jnp.zeros((), jnp.float32)
    for step, ids, want in tile_blocks(plan, rule, seed, list(states)):
        acc = rule.combine(acc, rule.measure(
            jnp.take(dev[step], ids, axis=-3), want))
    worst = max(worst, rule.finish(acc))
    return worst


def compare_control(plan: TilePlan, rule, seed: int, steps: int,
                    control) -> float:
    """The rule's number between the control and the reference after
    `steps`."""
    acc = jnp.zeros((), jnp.float32)
    for (_, _, want), (_, _, got) in zip(
            tile_blocks(plan, rule, seed, [steps]),
            tile_blocks(plan, rule, seed, [steps], control)):
        acc = rule.combine(acc, rule.measure(got, want))
    return rule.finish(acc)


@functools.lru_cache(maxsize=None)
def _block_taker(side, halo, rho, channels):
    """jit of the blocks at (tile slot, block row, block column) of a
    batch's stepped windows, as (C?, n, rho, rho)."""
    per = side // rho

    @jax.jit
    def take(state, slot, ly, lx):
        core = state[..., halo:halo + side, halo:halo + side]
        blocks = core.reshape(core.shape[:-2] + (per, rho, per, rho))
        blocks = jnp.moveaxis(blocks, -3, -2)
        if channels == 1:
            return blocks[slot, ly, lx]
        return jnp.moveaxis(blocks[slot, :, ly, lx], 0, 1)
    return take


@functools.lru_cache(maxsize=None)
def _tile_start(rule, side, halo, control):
    """jit of a tile batch's seeded initial state and the occupancy its
    steps apply: `low` masks the field, `low_step` the steps."""

    @jax.jit
    def start(tiles, nbhd, low, low_step, seed):
        mask = window_mask(side, halo, low, nbhd)
        state = rule.init(seed, *window_coords(side, halo, tiles), mask,
                          control)
        return state, window_mask(side, halo, low_step, nbhd)
    return start


@functools.lru_cache(maxsize=None)
def _tile_runner(rule, window, control):
    """jit of `n` steps of `rule` on a batch of windows (n traced)."""
    del window  # part of the cache key only: one compile per window size

    @jax.jit
    def run(state, mask, n):
        return jax.lax.fori_loop(0, n, lambda _, s: rule.step(s, mask,
                                                              control),
                                 state)
    return run
