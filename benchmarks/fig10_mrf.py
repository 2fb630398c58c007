"""Paper Fig. 10: theoretical memory-reduction-factor of Squeeze vs the
expanded bounding-box, MRF(n) = s^2r / k^r, for Vicsek / Sierpinski /
Carpet. Paper's stated values at n = 2^16: ~400x, ~105x, ~3.4x."""
from repro.core import fractals
from benchmarks.common import emit

#: (fractal, n at which the paper reads the plot, paper's stated MRF)
PAPER_POINTS = [
    (fractals.VICSEK, 3 ** 10, 400.0),        # closest power of s to 2^16
    (fractals.SIERPINSKI, 2 ** 16, 105.0),
    (fractals.CARPET, 3 ** 10, 3.4),
]


def run():
    for frac, n, paper in PAPER_POINTS:
        r = frac.level_of_side(n)
        mrf = frac.mrf(r)
        ok = abs(mrf - paper) / paper < 0.25
        emit(f"fig10/mrf/{frac.name}/n={n}", None,
             f"mrf={mrf:.1f};paper~{paper};match={ok}")
    # the growth curve itself (per level), sierpinski
    f = fractals.SIERPINSKI
    for r in range(1, 21, 4):
        emit(f"fig10/curve/sierpinski/r={r}", None, f"mrf={f.mrf(r):.2f}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
