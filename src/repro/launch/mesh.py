"""Production mesh builders. Functions, not module constants — importing
this module never touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis explicitly ``AxisType.Auto``."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False, data: int = 16,
                         model: int = 16):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips.

    ``data``/``model`` may be re-split (same chip count) for the §Perf
    mesh-layout experiments — e.g. (data=64, model=4) narrows TP, which
    shrinks the per-device activation all-reduce payload linearly
    (payload ~ B/dp) at equal compute."""
    assert data * model == 256, "single pod is 256 chips"
    shape = (2, data, model) if multi_pod else (data, model)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1):
    """Mesh over whatever devices exist (tests / CPU examples)."""
    n = jax.device_count()
    assert n % model_axis == 0
    shape = (n // model_axis, model_axis)
    return make_mesh(shape, ("data", "model"))
