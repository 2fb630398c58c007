"""§Perf (paper cell): per-step HBM traffic of the three block-level
Squeeze stencil kernels, analytic (bytes/block, uint8 cells) plus a
CPU-XLA proxy measurement of the two halo-assembly strategies (full
neighbor-block gather vs strip gather) via cost_analysis bytes.

v1 (blocks): center + 8 full neighbor blocks into VMEM     ~ 10 rho^2
v2 (strips): XLA strip gather to a (nb,4,rho+2) halo tensor,
             kernel reads center+halo                      ~ 2 rho^2 + 12 rho
v3 (fused):  strip arrays read in-kernel via scalar-prefetch
             index maps; halo tensor never materialised    ~ 2 rho^2 + 8 rho
"""
import jax
import jax.numpy as jnp

from repro.core import fractals
from repro.core.compact import BlockLayout
from repro.core.stencil import SqueezeBlockEngine
from repro.kernels import squeeze_stencil as sk
from benchmarks.common import emit, time_fn


def analytic_bytes_per_block(rho: int) -> dict:
    return {
        "v1_blocks": 9 * rho * rho + rho * rho,
        "v2_strips": (rho * rho + 4 * (rho + 2)      # kernel reads
                      + rho * rho                     # kernel write
                      + 2 * 4 * (rho + 2)),           # halo build r/w
        "v3_fused": (rho * rho + 4 * rho + 4          # kernel reads
                     + rho * rho                      # kernel write
                     + 2 * 4 * rho),                  # strip array build
    }


def run():
    for rho in (4, 8, 16, 32):
        a = analytic_bytes_per_block(rho)
        base = a["v1_blocks"]
        emit(f"stencil_traffic/analytic/rho={rho}", None,
             ";".join(f"{k}={v}B({base / v:.2f}x)" for k, v in a.items()))

    # CPU-XLA proxy: halo assembly traffic, full-block vs strip gather
    frac = fractals.SIERPINSKI
    layout = BlockLayout(frac, 9, 2).materialize()   # 2187 blocks, rho=4
    eng = SqueezeBlockEngine(layout)
    state = eng.init_random(seed=0)
    table = jnp.asarray(layout.neighbor_table)

    @jax.jit
    def gather_full_blocks(s):
        padded = jnp.concatenate(
            [s, jnp.zeros((1,) + s.shape[1:], s.dtype)], 0)
        return jnp.stack([jnp.take(padded, table[:, d], axis=0)
                          for d in range(8)], 1)

    @jax.jit
    def gather_strips(s):
        return sk.gather_halo_strips(layout, s)

    t_full = time_fn(gather_full_blocks, state)
    t_strip = time_fn(gather_strips, state)
    ca_full = jax.jit(gather_full_blocks).lower(state).compile(
    ).cost_analysis() or {}
    ca_strip = jax.jit(gather_strips).lower(state).compile(
    ).cost_analysis() or {}
    b_full = ca_full.get("bytes accessed", 0.0)
    b_strip = ca_strip.get("bytes accessed", 0.0)
    emit("stencil_traffic/halo_assembly/full_blocks", t_full,
         f"bytes={b_full:.3e}")
    emit("stencil_traffic/halo_assembly/strips", t_strip,
         f"bytes={b_strip:.3e};traffic_win={b_full / max(b_strip, 1):.2f}x;"
         f"wall_win={t_full / t_strip:.2f}x")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
