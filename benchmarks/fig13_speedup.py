"""Paper Fig. 13: speedup of Squeeze over BB, S = T_bb / T_squeeze, per
block size. Derived from the fig12 measurements (same CPU caveat), plus
the machine-independent work-ratio bound s^2r / k^r that drives the
paper's observed growth of S with r."""
from repro.core import fractals
from benchmarks import fig12_times
from benchmarks.common import emit


def run():
    times = fig12_times.run(levels=(5, 7, 9))
    frac = fractals.SIERPINSKI
    for (r, name), us in sorted(times.items()):
        if name in ("bb",):
            continue
        s = times[(r, "bb")] / us
        bound = frac.side(r) ** 2 / frac.volume(r)
        emit(f"fig13/speedup/sierpinski/r={r}/{name}", None,
             f"S={s:.2f};work_ratio_bound={bound:.1f}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
