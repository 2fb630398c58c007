"""Paper Fig. 12: execution time per game-of-life step for the three
approaches (BB / lambda / Squeeze) on the Sierpinski triangle, sweeping
the level r and the Squeeze block size rho.

IMPORTANT CAVEAT (recorded in EXPERIMENTS.md): this container is CPU-only,
so absolute times are NOT comparable to the paper's GPU walls; the
structural signal (compact engines touch k^r cells vs the BB's s^2r, and
the crossover as r grows) is what we validate. The TPU deployment path is
the Pallas kernel pair (kernels/squeeze_stencil.py).
"""
from repro.core import fractals
from repro.core.baselines import BBEngine, LambdaEngine
from repro.core.compact import BlockLayout
from repro.core.stencil import SqueezeBlockEngine, SqueezeCellEngine
from benchmarks.common import emit, time_fn

LEVELS = (5, 7, 9)
RHO_M = (1, 2, 4)   # rho = 2^m


def run(levels=LEVELS):
    frac = fractals.SIERPINSKI
    results = {}
    for r in levels:
        engines = {"bb": BBEngine(frac, r), "lambda": LambdaEngine(frac, r),
                   "cell": SqueezeCellEngine(frac, r)}
        for m in RHO_M:
            if m < r:
                engines[f"block_rho{2**m}"] = SqueezeBlockEngine(
                    BlockLayout(frac, r, m))
        for name, eng in engines.items():
            state = eng.init_random(seed=1)
            us = time_fn(eng.step, state, warmup=2, iters=8)
            results[(r, name)] = us
            cells = (frac.side(r) ** 2 if name in ("bb", "lambda")
                     else frac.volume(r))
            emit(f"fig12/time/sierpinski/r={r}/{name}", us,
                 f"cells={cells};ns_per_cell={1e3 * us / cells:.2f}")
    return results


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
