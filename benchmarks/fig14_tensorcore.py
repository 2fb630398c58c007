"""Paper Fig. 14: the impact of tensor-core acceleration of the maps.

On this CPU container "tensor core on/off" maps to the two formulations:
  * MXU/matmul-encoded maps (nu_map_matmul / lambda_map_matmul — one dot
    per coordinate batch, the paper's MMA encoding), vs
  * the scalar per-level accumulation path (nu_map / lambda_map).
We report wall-ratio on CPU plus the op-structure facts that carry to
TPU (1 dot of (N,128)@(128,2) replaces r dependent int adds/muls).
The Pallas kernels run the same encoding in interpret mode (correctness
proof); their compiled-TPU speedup cannot be measured here.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fractals, maps
from benchmarks.common import emit, time_fn


def run():
    frac = fractals.SIERPINSKI
    for r, n_coords in ((8, 1 << 14), (12, 1 << 16), (16, 1 << 18)):
        rng = np.random.default_rng(0)
        rows, cols = frac.compact_dims(r)
        cx = jnp.asarray(rng.integers(0, cols, n_coords).astype(np.int32))
        cy = jnp.asarray(rng.integers(0, rows, n_coords).astype(np.int32))
        ex, ey = maps.lambda_map(frac, r, cx, cy)

        lam_scalar = jax.jit(lambda a, b: maps.lambda_map(frac, r, a, b))
        lam_mma = jax.jit(lambda a, b: maps.lambda_map_matmul(frac, r, a, b))
        nu_scalar = jax.jit(lambda a, b: maps.nu_map(frac, r, a, b))
        nu_mma = jax.jit(lambda a, b: maps.nu_map_matmul(frac, r, a, b))

        t_ls = time_fn(lam_scalar, cx, cy)
        t_lm = time_fn(lam_mma, cx, cy)
        t_ns = time_fn(nu_scalar, ex, ey)
        t_nm = time_fn(nu_mma, ex, ey)
        emit(f"fig14/lambda/r={r}/N={n_coords}", t_lm,
             f"scalar_us={t_ls:.1f};mma_over_scalar={t_ls / t_lm:.2f}x")
        emit(f"fig14/nu/r={r}/N={n_coords}", t_nm,
             f"scalar_us={t_ns:.1f};mma_over_scalar={t_ns / t_nm:.2f}x")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
