"""3D fused-block sweep: block3d (k-fused) vs the cell3d per-cell engine.

Sweeps r x rho x k on the 3D NBB fractals (Sierpinski tetrahedron,
Menger sponge): per configuration the cell-level engine (one lambda3
per cell + one nu3 per neighbor, re-evaluated every step) is the
baseline and the block engine steps through its depth-k fused path
(``step_k``; k = 1 is the unfused block step). Step-for-step parity
against the cell engine is asserted before timing (bit-exact for
LIFE3D, 1e-5 for HEAT3D), so the bench doubles as the CI 3D
correctness smoke.

    PYTHONPATH=src python benchmarks/stencil3d_bench.py [--smoke]
                                                        [--min-speedup 1.5]

Writes BENCH_3d.json (one record per (fractal, workload, engine, r, m,
k): us_per_step amortized over the fused launch, mcells_per_s,
state_bytes). After the JSON is written the gate *fails the process*
unless the geometric mean over configurations of the best fused-block
(k >= 2) per-step speedup over the cell engine reaches ``--min-speedup``
— the CI 3d perf-gate step (benchmarks/ci_gates.py --gate 3d).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import fractals3d as f3  # noqa: E402
from repro.core.stencil import make_engine  # noqa: E402
from repro.workloads import HEAT3D, LIFE3D  # noqa: E402
from benchmarks.common import emit, time_fn  # noqa: E402

WORKLOADS = (LIFE3D, HEAT3D)


def _tol(wl):
    return dict(rtol=0, atol=0) if wl is LIFE3D \
        else dict(rtol=1e-5, atol=1e-5)


def _single_steps(eng, state, n):
    for _ in range(n):
        state = eng.step(state)
    return state


def bench_cell(frac, r, wl, iters) -> dict:
    eng = make_engine("cell3d", frac, r, workload=wl)
    state = eng.init_random(seed=0)
    us = time_fn(eng.step, state, iters=iters)
    cells = frac.volume(r)
    rec = {
        "workload": wl.name, "engine": "cell3d", "fractal": frac.name,
        "r": r, "m": 0, "k": 1, "us_per_step": us,
        "cells": cells, "mcells_per_s": cells / us,
        "state_bytes": eng.memory_bytes(),
    }
    emit(f"stencil3d/{wl.name}/cell3d/r{r}", us,
         f"mcups={rec['mcells_per_s']:.1f}")
    return rec


def bench_block(frac, r, m, wl, k, iters, want) -> dict:
    """Amortized per-step cost of one fused block3d launch; parity vs
    the cell engine's expanded trajectory (``want``) is asserted before
    timing. Both engines seed their start state from the same BB3D
    ``init_random(seed=0)`` path, so the trajectories are comparable."""
    eng = make_engine("block3d", frac, r, m, workload=wl, fusion_k=k)
    state = eng.init_random(seed=0)
    got = eng.to_expanded(eng.step_k(state, k) if k > 1
                          else eng.step(state))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), **_tol(wl),
        err_msg=f"3d parity broke: block3d/{wl.name}/r={r}/m={m}/k={k}")
    if k > 1:
        us = time_fn(lambda s: eng.step_k(s, k), state, iters=iters) / k
    else:
        us = time_fn(eng.step, state, iters=iters)
    cells = frac.volume(r)
    rho = frac.s ** m
    rec = {
        "workload": wl.name, "engine": "block3d", "fractal": frac.name,
        "r": r, "m": m, "rho": rho, "k": k, "us_per_step": us,
        "cells": cells, "mcells_per_s": cells / us,
        "state_bytes": eng.memory_bytes(),
    }
    emit(f"stencil3d/{wl.name}/block3d/r{r}/rho{rho}/k{k}", us,
         f"mcups={rec['mcells_per_s']:.1f}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--smoke", action="store_true",
                    help="smaller levels (CI end-to-end check)")
    ap.add_argument("--min-speedup", type=float, default=1.5,
                    help="geomean best fused-block speedup over the cell "
                         "engine required to pass (the CI 3d gate)")
    ap.add_argument("--out", default="BENCH_3d.json")
    args = ap.parse_args()
    iters = max(args.iters, 10)

    # (fractal, r, block levels m) — rho = s**m per level
    configs = ([(f3.SIERPINSKI3D, 6, (1, 2)), (f3.MENGER, 3, (1,))]
               if args.smoke else
               [(f3.SIERPINSKI3D, 8, (1, 2)), (f3.MENGER, 3, (1,))])

    records, speedups = [], []
    for frac, r, ms in configs:
        for wl in WORKLOADS:
            cell_eng = make_engine("cell3d", frac, r, workload=wl)
            base = bench_cell(frac, r, wl, iters)
            records.append(base)
            for m in ms:
                rho = frac.s ** m
                ks = sorted({1, 2, rho})
                # one shared oracle trajectory per (config, k)
                s0 = cell_eng.init_random(seed=0)
                best = 0.0
                for k in ks:
                    want = cell_eng.to_expanded(
                        _single_steps(cell_eng, s0, k))
                    rec = bench_block(frac, r, m, wl, k, iters, want)
                    records.append(rec)
                    if k >= 2:
                        best = max(best,
                                   base["us_per_step"] / rec["us_per_step"])
                speedups.append((f"{frac.name}/{wl.name}/r{r}/rho{rho}",
                                 best))

    out = pathlib.Path(args.out)
    out.write_text(json.dumps({
        "backend": jax.default_backend(),
        "min_speedup": args.min_speedup,
        "records": records}, indent=2))
    print(f"wrote {out} ({len(records)} records)")
    # JSON first, so a regression still leaves the timings behind
    for name, x in speedups:
        print(f"3d fused-block speedup {name}: {x:.2f}x")
    geomean = float(np.exp(np.mean(np.log([x for _, x in speedups]))))
    print(f"3d gate: geomean best fused (k>=2) block3d speedup over "
          f"cell3d = {geomean:.2f}x ({len(speedups)} configs)")
    if geomean < args.min_speedup:
        raise SystemExit(
            f"3d fused-block geomean speedup {geomean:.2f}x < "
            f"{args.min_speedup}x over the cell3d engine")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
