"""Paper Table 2: memory needed + memory-reduction-factor per approach on
the Sierpinski triangle at r=16, across block sizes rho. Analytic bytes
(1 byte/cell), cross-checked against allocated array sizes at a small r
(both formulas are exact, so the small-r measurement certifies the
r=16 analytic row). Paper: 99.8x / 74.8 / 56.1 / 42.1 / 31.6 / 23.7."""
import numpy as np

from repro.core import fractals
from repro.core.baselines import BBEngine
from repro.core.compact import BlockLayout
from repro.core.stencil import SqueezeBlockEngine, SqueezeCellEngine
from benchmarks.common import emit

PAPER_R16 = {1: 99.8, 2: 74.8, 4: 56.1, 8: 42.1, 16: 31.6, 32: 23.7}


def run():
    frac = fractals.SIERPINSKI
    r = 16
    bb = BBEngine(frac, r).memory_bytes()
    emit("table2/bb/r=16", None, f"bytes={bb};gb={bb / 2 ** 30:.2f}")
    for m, rho in ((0, 1), (1, 2), (2, 4), (3, 8), (4, 16), (5, 32)):
        # analytic bytes (BlockLayout.memory_bytes is O(1); engines would
        # materialize 3^16-block neighbor tables)
        mem = (BlockLayout(frac, r, m).memory_bytes() if m
               else frac.volume(r))
        mrf = bb / mem
        paper = PAPER_R16[rho]
        emit(f"table2/squeeze/rho={rho}", None,
             f"bytes={mem};mrf={mrf:.1f};paper={paper};"
             f"match={abs(mrf - paper) / paper < 0.02}")

    # measured cross-check at r=8: allocated nbytes equals the formula
    r_small = 8
    for m in (0, 2):
        eng = SqueezeBlockEngine(BlockLayout(frac, r_small, m)) if m else \
            SqueezeCellEngine(frac, r_small)
        state = eng.init_random(seed=0)
        assert int(np.asarray(state).nbytes) == eng.memory_bytes()
    emit("table2/crosscheck/r=8", None, "allocated==formula")

    # the r=20 capability claim: Squeeze fits where BB needs 4 TB
    r20 = 20
    bb20 = BBEngine(frac, r20).memory_bytes()
    sq20 = BlockLayout(frac, r20, 4).memory_bytes()
    emit("table2/r=20", None,
         f"bb_tb={bb20 / 2 ** 40:.2f};squeeze_gb={sq20 / 2 ** 30:.2f};"
         f"mrf={bb20 / sq20:.0f}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
