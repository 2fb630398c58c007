"""Concrete stencil workloads.

  * ``TotalisticCA``  — outer-totalistic cellular automaton with arbitrary
                        born/survive neighbor-count sets; ``LIFE`` (B3/S23)
                        is the paper's Section 4 case study, ``LIFE3D``
                        (B6/S5-7) is the 3D variant used by stencil3d.
  * ``HeatDiffusion`` — float32 Jacobi iteration of the heat equation with
                        Dirichlet-0 holes (orthogonal-neighbor Laplacian).
  * ``GrayScott``     — 2-channel float32 Gray-Scott reaction-diffusion
                        (9-point Laplacian, Karl Sims' classic parameters).

All are frozen dataclasses: hashable, usable as jit static arguments and
as compiled-engine cache keys (workloads/runner.py).
"""
from __future__ import annotations

import dataclasses
from typing import FrozenSet

import jax.numpy as jnp

from repro.workloads.base import StencilWorkload, uniform_from_bits

Array = jnp.ndarray


def life_rule(alive: Array, neighbors: Array) -> Array:
    """Conway B3/S23, uint8 in/out (the paper's Section 4 rule; kept as a
    function because the engine tests and kernel oracles bind to it)."""
    born = neighbors == 3
    survive = (alive > 0) & (neighbors == 2)
    return (born | survive).astype(jnp.uint8)


def _count_in(agg: Array, counts: FrozenSet[int]) -> Array:
    """Boolean: agg is one of the (static) counts. Built from
    comparisons only (no boolean constant array, which Mosaic cannot
    materialize)."""
    hit = agg < 0  # all False: counts are never negative
    for c in sorted(counts):
        hit = hit | (agg == c)
    return hit


@dataclasses.dataclass(frozen=True)
class TotalisticCA(StencilWorkload):
    """Outer-totalistic CA over the Moore neighborhood: a dead cell is born
    when its live-neighbor count is in ``born``; a live cell survives when
    it is in ``survive``. Holes and out-of-fractal cells count 0."""

    name: str = "life"
    born: FrozenSet[int] = frozenset({3})
    survive: FrozenSet[int] = frozenset({2, 3})

    #: neighbor counts are at most 26 (3D Moore), so uint8 holds them
    #: exactly: a haloed block window is never widened past the state's
    #: bytes (the Pallas kernels still count on 32-bit lanes)
    agg_dtype = jnp.uint8

    def apply(self, center, agg, mask):
        alive = center > 0
        nxt = ((alive & _count_in(agg, self.survive))
               | (~alive & _count_in(agg, self.born))).astype(center.dtype)
        return self.masked(nxt, mask)

    def init(self, bits):
        return (bits >> 31).astype(jnp.uint8)


@dataclasses.dataclass(frozen=True)
class HeatDiffusion(StencilWorkload):
    """Explicit Jacobi step u += alpha * lap(u) with Dirichlet-0 holes.

    The Laplacian is the orthogonal-neighbor stencil ``agg - degree * u``
    (degree = 4 in 2D, 6 in 3D); diagonal directions carry weight 0 and
    are never gathered. Stable for alpha <= 1/degree.
    """

    name: str = "heat"
    alpha: float = 0.2
    degree: int = 4  # 2 * ndim

    dtype = jnp.float32
    agg_dtype = jnp.float32

    @property
    def ndim(self):
        return self.degree // 2  # degree = 2 * ndim orthogonal neighbors

    def weight(self, offset):
        return 1 if sum(abs(d) for d in offset) == 1 else 0

    def apply(self, center, agg, mask):
        u = center.astype(jnp.float32)
        nxt = u + self.alpha * (agg - self.degree * u)
        return self.masked(nxt, mask)

    def init(self, bits):
        return uniform_from_bits(bits)


@dataclasses.dataclass(frozen=True)
class GrayScott(StencilWorkload):
    """Gray-Scott reaction-diffusion, channels (U, V):

        u' = u + du * lap(u) - u v^2 + feed (1 - u)
        v' = v + dv * lap(v) + u v^2 - (feed + kill) v

    with the normalized 9-point Laplacian (0.2 orthogonal, 0.05 diagonal,
    weights sum to 1: lap = agg - u) and dt = 1. Holes are Dirichlet-0 in
    both channels.
    """

    name: str = "gray-scott"
    du: float = 1.0
    dv: float = 0.5
    feed: float = 0.055
    kill: float = 0.062

    n_channels = 2
    ndim = 2
    dtype = jnp.float32
    agg_dtype = jnp.float32

    def weight(self, offset):
        if len(offset) != 2:
            raise ValueError("GrayScott is a 2D workload")
        return 0.2 if sum(abs(d) for d in offset) == 1 else 0.05

    def apply(self, center, agg, mask):
        u, v = center[0].astype(jnp.float32), center[1].astype(jnp.float32)
        lap_u = agg[0] - u
        lap_v = agg[1] - v
        uvv = u * v * v
        nu = u + self.du * lap_u - uvv + self.feed * (1.0 - u)
        nv = v + self.dv * lap_v + uvv - (self.feed + self.kill) * v
        return self.masked(jnp.stack([nu, nv]), mask)

    def init(self, bits):
        seeds = uniform_from_bits(bits) < 0.02
        u = 1.0 - 0.5 * seeds.astype(jnp.float32)
        v = 0.25 * seeds.astype(jnp.float32)
        return jnp.stack([u, v])


LIFE = TotalisticCA()
LIFE3D = TotalisticCA(name="life3d", born=frozenset({6}),
                      survive=frozenset({5, 6, 7}))
HIGHLIFE = TotalisticCA(name="highlife", born=frozenset({3, 6}),
                        survive=frozenset({2, 3}))
SEEDS = TotalisticCA(name="seeds", born=frozenset({2}),
                     survive=frozenset())
HEAT = HeatDiffusion()
HEAT3D = HeatDiffusion(name="heat3d", alpha=0.125, degree=6)
GRAY_SCOTT = GrayScott()

#: name -> workload registry (2D engine-compatible entries only)
WORKLOADS = {w.name: w for w in
             (LIFE, HIGHLIFE, SEEDS, HEAT, GRAY_SCOTT)}


def get_workload(name: str) -> StencilWorkload:
    try:
        return WORKLOADS[name]
    except KeyError:
        raise KeyError(f"unknown workload {name!r}; "
                       f"have {sorted(WORKLOADS)}") from None
