"""Outer-totalistic cellular automata (Life is B3/S23), uint8 states.

A dead cell with a count of live Moore neighbours in `born` comes
alive; a live one with a count in `survive` stays alive; every other
cell, and every cell outside the fractal, is dead.

The comparison is exact: the number of cells that differ, limit 0.
Life has no lower precision that it could fail: its states are 0 and 1
and its counts 0 to 8, exact in every integer width down to 4 bits
(where 8 wraps to -8, which is neither a birth nor a survival count
either). So its control breaks a guarantee the configuration states
instead: `hole_mask_skipped` updates the holes inside each block as if
they were cells, which is what a kernel that drops its per-cell mask
multiply would do.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from chipbench.reference.domain import cell_bits, shifts

CONTROLS = ("hole_mask_skipped",)


@dataclasses.dataclass(frozen=True)
class Totalistic:
    born: tuple
    survive: tuple

    channels = 1
    channel_shape = ()
    cell_bytes = 1
    number = "mismatched_cells"

    def step_mask(self, plan, control):
        """The level-t mask a step applies, where the control changes it
        (None: the fractal's own)."""
        if control is None:
            return None
        if control != "hole_mask_skipped":
            raise ValueError(f"unknown control {control!r}")
        rho = plan.rho
        return np.kron(plan.frac.mask(plan.t - plan.m),
                       np.ones((rho, rho), np.uint8))

    def init(self, seed, x, y, mask, control):
        return (cell_bits(seed, x, y) >> 31).astype(jnp.uint8) * mask

    def step(self, state, mask, control):
        count = sum(shifts(state).values())  # uint8, at most 8
        alive = state > 0
        born = jnp.zeros(state.shape, bool)
        for c in self.born:
            born = born | (count == c)
        keep = jnp.zeros(state.shape, bool)
        for c in self.survive:
            keep = keep | (count == c)
        nxt = (alive & keep) | (~alive & born)
        return nxt.astype(jnp.uint8) * mask

    dtype = np.dtype(np.uint8)

    @staticmethod
    def measure(got, want):
        """Cells that differ."""
        return jnp.sum(got != want, dtype=jnp.float32)

    @staticmethod
    def combine(acc, x):
        return acc + x

    @staticmethod
    def finish(acc) -> float:
        return float(acc)

    @staticmethod
    def mismatch(shape) -> float:
        """A state of another shape or dtype differs in every cell."""
        return float(np.prod(shape))


def from_config(cfg: dict) -> Totalistic:
    w = cfg["workload"]
    return Totalistic(tuple(int(c) for c in w["born"]),
                      tuple(int(c) for c in w["survive"]))
