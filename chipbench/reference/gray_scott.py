"""Gray-Scott reaction-diffusion (Pearson, Science 261, 1993), two
float32 channels (U, V), explicit Euler with dt = 1:

    u' = u + du * lap(u) - u v^2 + feed (1 - u)
    v' = v + dv * lap(v) + u v^2 - (feed + kill) v

with the 9-point Laplacian lap(x) = w_orth * (4 edge neighbours) +
w_diag * (4 corner neighbours) - x. Cells outside the fractal hold 0 in
both channels (Dirichlet 0), and the initial field sets u = 0.5,
v = 0.25 where the cell's uniform draw (top 24 bits of its word) is
under 0.02, and u = 1, v = 0 elsewhere.

The comparison is the largest absolute difference over every value of
every compared state. Its control is the same computation in bfloat16.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from chipbench.reference.domain import cell_bits, shifts

CONTROLS = ("bfloat16",)


@dataclasses.dataclass(frozen=True)
class GrayScott:
    du: float
    dv: float
    feed: float
    kill: float
    w_orth: float
    w_diag: float

    channels = 2
    channel_shape = (2,)
    cell_bytes = 8
    number = "max_abs_err"

    def _dtype(self, control):
        if control is None:
            return jnp.float32
        if control != "bfloat16":
            raise ValueError(f"unknown control {control!r}")
        return jnp.bfloat16

    def step_mask(self, plan, control):
        self._dtype(control)
        return None

    def init(self, seed, x, y, mask, control):
        bits = cell_bits(seed, x, y)
        uniform = (bits >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)
        seeded = (uniform < 0.02).astype(jnp.float32)
        m = mask.astype(jnp.float32)
        u = (1.0 - 0.5 * seeded) * m
        v = 0.25 * seeded * m
        return jnp.stack([u, v], axis=-3).astype(self._dtype(control))

    def _lap(self, x, dt):
        nb = shifts(x)
        edge = nb[(0, -1)] + nb[(0, 1)] + nb[(-1, 0)] + nb[(1, 0)]
        corner = nb[(-1, -1)] + nb[(1, -1)] + nb[(-1, 1)] + nb[(1, 1)]
        return dt(self.w_orth) * edge + dt(self.w_diag) * corner - x

    def step(self, state, mask, control):
        dt = self._dtype(control)
        c = lambda v: jnp.asarray(v, dt)  # noqa: E731
        u, v = state[..., 0, :, :], state[..., 1, :, :]
        uvv = u * v * v
        nu = u + c(self.du) * self._lap(u, c) - uvv + c(self.feed) * (
            c(1.0) - u)
        nv = v + c(self.dv) * self._lap(v, c) + uvv - c(
            self.feed + self.kill) * v
        m = mask.astype(dt)
        return jnp.stack([nu * m, nv * m], axis=-3)

    dtype = np.dtype(np.float32)

    @staticmethod
    def measure(got, want):
        """Largest absolute difference, in float32; NaN where either
        side holds one."""
        return jnp.max(jnp.abs(got.astype(jnp.float32)
                               - want.astype(jnp.float32)))

    @staticmethod
    def combine(acc, x):
        return jnp.where(jnp.isnan(x) | jnp.isnan(acc), jnp.nan,
                         jnp.maximum(acc, x))

    @staticmethod
    def finish(acc) -> float:
        """A NaN reads infinite."""
        v = float(acc)
        return v if np.isfinite(v) else float("inf")

    @staticmethod
    def mismatch(shape) -> float:
        """A state of another shape or dtype is infinitely far off."""
        return float("inf")


def from_config(cfg: dict) -> GrayScott:
    w = cfg["workload"]
    return GrayScott(*(float(w[k]) for k in
                       ("du", "dv", "feed", "kill", "w_orth", "w_diag")))
