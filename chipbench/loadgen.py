"""The load generator: the requests a run sends, from a traffic mix and
`--seed`.

A mix (`chipbench/traffic/<mix>.json`) gives the loop (`closed`: each
of `clients` sends its next request when its last one has come back),
the depth `r`, the `steps` and the `snapshot_every` of every request,
each as a {"value": weight} object with one value, and `deadline_s`.
Every seed gets the same work; the seed draws each request's initial
field.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Planned:
    """One request as the generator plans it."""

    idx: int
    r: int
    steps: int
    snapshot_every: int
    seed: int        # the request's own seed, for its initial field


def _rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2 ** 64, *salt])


def choices(dist: dict) -> list:
    """[(value, weight)] of a {"value": weight} object."""
    return [(int(v), float(w)) for v, w in dist.items()]


def request_seed(rng: np.random.Generator) -> int:
    """A request seed the program takes (a non-negative int32)."""
    return int(rng.integers(0, 2 ** 31 - 1))


def one_size(traffic: dict, key: str) -> int:
    """The single value of a closed mix's choice."""
    items = choices(traffic[key])
    if len(items) != 1:
        raise ValueError(f"a closed mix gives one {key}, got {items}")
    return items[0][0]


def closed_requests(traffic: dict, seed: int):
    """Per client, an endless generator of its requests."""
    r, steps = one_size(traffic, "r"), one_size(traffic, "steps")
    snap = one_size(traffic, "snapshot_every")

    def client(c: int):
        rng = _rng(seed, 1, c)
        j = 0
        while True:
            yield Planned(j * traffic["clients"] + c, r, steps, snap,
                          request_seed(rng))
            j += 1
    return [client(c) for c in range(int(traffic["clients"]))]


def warm_sizes(traffic: dict, max_batch: int) -> list:
    """Batch sizes the mix can put in one launch: 1 up to its clients
    (at most the service's `max_batch`)."""
    return list(range(1, min(int(traffic["clients"]), max_batch) + 1))


def depths(traffic: dict) -> list:
    return sorted(v for v, _ in choices(traffic["r"]))
