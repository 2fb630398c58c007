"""Shared helpers for the Pallas kernel entry points."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

#: VMEM bytes one program's working window may fill per live 32-bit
#: array; the blocks-per-program tiling is sized from it
VMEM_TILE_BYTES = 384 * 1024

#: scoped-VMEM limit requested for every compiled stencil kernel (a
#: v5e core has 128 MiB; the default scoped limit is 16 MiB)
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


#: precision of every MXU contraction of the stencil kernels: the banded
#: operands hold irrational SVD factors, so the products run at full f32
#: precision — a one-pass bf16 product would round both the factors and
#: the Gray-Scott fields (0/1 Life counts survive it only by rounding)
MXU_PRECISION = jax.lax.Precision.HIGHEST


class KernelCapacityError(RuntimeError):
    """A compiled kernel cannot hold this layout (e.g. a scalar-prefetch
    table larger than SMEM). Raised before anything is traced."""


class NoKernelBackendError(RuntimeError):
    """The default backend is neither a TPU (compiled Mosaic) nor the
    CPU (the Pallas interpreter)."""


def default_interpret() -> bool:
    """False on TPU (compile the Mosaic kernel), True on the CPU (run the
    Pallas interpreter: the test suite's backend). Any other backend
    raises — the kernels are never interpreted silently off the CPU."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise NoKernelBackendError(
        f"Pallas stencil kernels compile for TPU and interpret on CPU; "
        f"the default backend is {backend!r}")


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` means auto-detect via ``default_interpret``; explicit bools
    pass through unchanged (tests pass ``interpret=True`` so they stay
    deterministic on any backend)."""
    return default_interpret() if interpret is None else bool(interpret)


def compiler_params(interpret: bool):
    """Mosaic compiler parameters of the stencil kernels (None when
    interpreting)."""
    if interpret:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def block_tiling(n: int, per_item_bytes: int) -> Tuple[int, int]:
    """(items per program, padded item count) for a grid over ``n``
    items of ``per_item_bytes`` VMEM each: a multiple of 8 (the sublane
    tile of the per-program existence rows) no larger than the padded
    ``n``, and ``n`` rounded up to a multiple of it."""
    t = max(8, VMEM_TILE_BYTES // max(per_item_bytes, 1) // 8 * 8)
    t = min(t, -(-n // 8) * 8)
    return t, -(-n // t) * t


def work_dtype(workload):
    """In-kernel arithmetic dtype: int32 for integer aggregates (Mosaic
    computes on 32-bit lanes), float32 otherwise."""
    if jnp.issubdtype(jnp.dtype(workload.agg_dtype), jnp.integer):
        return jnp.int32
    return jnp.float32


def to_dtype(x, dtype):
    """Cast for kernel loads/stores; float <-> narrow-int casts go
    through int32, the conversions Mosaic lowers."""
    src, dst = jnp.dtype(x.dtype), jnp.dtype(dtype)
    if src == dst:
        return x
    if jnp.issubdtype(src, jnp.floating) != jnp.issubdtype(dst,
                                                          jnp.floating):
        x = x.astype(jnp.int32)
    return x.astype(dst)
