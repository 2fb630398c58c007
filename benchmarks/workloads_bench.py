"""Per-workload step timing on the Sierpinski triangle: one step of each
workload (life, totalistic highlife, heat, Gray-Scott) on the cell, block,
and Pallas-strips engines, the batched-runner throughput at batch 8, and
the temporal-fusion k sweep (fused k-step launches vs single stepping on
the block engines, with a parity assertion).

    PYTHONPATH=src python benchmarks/workloads_bench.py [--r 9] [--m 2]
                                                        [--smoke]
                                                        [--fusion-only]

Writes BENCH_workloads.json (one record per (workload, engine)) and
BENCH_fusion.json (one record per (engine, workload, k): us_per_step and
mcells_per_s, amortized over the fused launch), and prints the
common.emit CSV rows. ``--smoke`` shrinks the level so the script doubles
as a CI check that every (workload, engine, k) combination runs end to
end; the fusion sweep *fails* (nonzero exit) if fused-k stepping diverges
from k single steps. ``--fusion-only`` skips the workload section (the CI
perf-smoke step).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import fractals  # noqa: E402
from repro.core.stencil import make_engine  # noqa: E402
from repro.workloads import (GRAY_SCOTT, HEAT, HIGHLIFE, LIFE,  # noqa: E402
                             BatchedRunner)
from benchmarks.common import emit, time_fn  # noqa: E402

ENGINES = ("cell", "block", "pallas-strips")
WORKLOADS = (LIFE, HIGHLIFE, HEAT, GRAY_SCOTT)

FUSION_ENGINES = ("block", "pallas-strips")
FUSION_WORKLOADS = (LIFE, HEAT, GRAY_SCOTT)
FUSION_KS = (1, 2, 3)


def bench_one(kind: str, frac, r: int, m: int, wl, iters: int) -> dict:
    eng = make_engine(kind, frac, r, m, workload=wl)
    state = eng.init_random(seed=0)
    us = time_fn(eng.step, state, iters=iters)
    cells = frac.volume(r)
    rec = {
        "workload": wl.name, "engine": kind, "fractal": frac.name,
        "r": r, "m": m, "us_per_step": us,
        "cells": cells, "mcells_per_s": cells / us,
        "state_bytes": eng.memory_bytes(
            dtype_size=jax.numpy.dtype(wl.dtype).itemsize),
    }
    emit(f"workloads/{wl.name}/{kind}", us,
         f"r={r};m={m};mcups={rec['mcells_per_s']:.1f}")
    return rec


def bench_batched(frac, r: int, m: int, wl, iters: int, batch: int) -> dict:
    runner = BatchedRunner()
    states = runner.init_batch("cell", frac, r, seeds=range(batch),
                               workload=wl)
    us = time_fn(lambda s: runner.step("cell", frac, r, s, workload=wl),
                 states, iters=iters)
    cells = frac.volume(r) * batch
    rec = {
        "workload": wl.name, "engine": f"runner-cell-b{batch}",
        "fractal": frac.name, "r": r, "m": m, "us_per_step": us,
        "cells": cells, "mcells_per_s": cells / us,
        "builds": runner.stats.builds, "traces": runner.stats.traces,
    }
    emit(f"workloads/{wl.name}/runner-b{batch}", us,
         f"r={r};mcups={rec['mcells_per_s']:.1f}")
    return rec


def _tol(wl):
    return dict(rtol=0, atol=0) if wl is LIFE or wl is HIGHLIFE \
        else dict(rtol=1e-5, atol=1e-5)


def bench_fusion_one(kind: str, frac, r: int, m: int, wl, k: int,
                     iters: int) -> dict:
    """Amortized per-step cost of k-fused stepping: one timed call is one
    ``step_k`` launch (k=1: one ``step``), us_per_step = launch / k.
    Fused-vs-single parity is asserted before timing — the bench doubles
    as the CI fused-k correctness smoke."""
    eng = make_engine(kind, frac, r, m, workload=wl, fusion_k=k)
    state = eng.init_random(seed=0)
    if k > 1:
        want = state
        for _ in range(k):
            want = eng.step(want)
        got = eng.step_k(state, k)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), **_tol(wl),
            err_msg=f"fused-k parity broke: {kind}/{wl.name}/k={k}")
        us = time_fn(lambda s: eng.step_k(s, k), state, iters=iters) / k
    else:
        us = time_fn(eng.step, state, iters=iters)
    cells = frac.volume(r)
    rec = {
        "workload": wl.name, "engine": kind, "fractal": frac.name,
        "r": r, "m": m, "k": k, "us_per_step": us,
        "cells": cells, "mcells_per_s": cells / us,
    }
    emit(f"fusion/{wl.name}/{kind}/k{k}", us,
         f"r={r};m={m};mcups={rec['mcells_per_s']:.1f}")
    return rec


def bench_fusion(frac, r: int, m: int, iters: int, out_path: str,
                 min_speedup: float = 1.0) -> None:
    # the speedup gate below compares wall-clock medians, so never drop
    # below 10 reps even in --smoke mode (2 reps flake on loaded runners)
    iters = max(iters, 10)
    rho = frac.s ** m
    records = []
    for kind in FUSION_ENGINES:
        for wl in FUSION_WORKLOADS:
            for k in FUSION_KS:
                if k > rho and kind.startswith("pallas"):
                    emit(f"fusion/{wl.name}/{kind}/k{k}", None,
                         f"skipped:k>rho={rho}")
                    continue  # v4 kernel is one-block-ring only
                records.append(
                    bench_fusion_one(kind, frac, r, m, wl, k, iters))
    # the point of temporal fusion: at least one fused configuration must
    # beat single stepping per step (fail loudly if the hot path regressed)
    speedups = []
    for rec in records:
        if rec["k"] == 1:
            continue
        base = next(b for b in records
                    if b["k"] == 1 and b["engine"] == rec["engine"]
                    and b["workload"] == rec["workload"])
        speedups.append((rec["us_per_step"] < base["us_per_step"],
                         rec["engine"], rec["workload"], rec["k"],
                         base["us_per_step"] / rec["us_per_step"]))
    out = pathlib.Path(out_path)
    out.write_text(json.dumps({
        "fractal": frac.name, "r": r, "m": m,
        "backend": jax.default_backend(), "records": records}, indent=2))
    print(f"wrote {out} ({len(records)} records)")
    # JSON is written first so a regression still leaves the timings behind
    best = max((x for *_, x in speedups), default=0.0)
    if not any(s[0] for s in speedups) or best < min_speedup:
        raise SystemExit(
            f"fused k>=2 stepping beats k=1 nowhere by >= "
            f"{min_speedup:.2f}x (best {best:.2f}x): "
            + "; ".join(f"{e}/{w}/k={k}: {x:.2f}x"
                        for _, e, w, k, x in speedups))


# ---------------------------------------------------------------- v5 MXU
MXU_WORKLOADS = (LIFE, HEAT, GRAY_SCOTT)


def bench_mxu_one(runner, kind, frac, r, m, wl, k, batch, steps, iters):
    states = runner.init_batch(kind, frac, r, seeds=range(batch), m=m,
                               workload=wl)
    us = time_fn(
        lambda s: runner.run(kind, frac, r, s, steps=steps, m=m,
                             workload=wl, k=k),
        states, iters=iters) / steps
    cells = frac.volume(r) * batch
    rho = frac.s ** m
    rec = {
        "workload": wl.name, "engine": kind, "fractal": frac.name,
        "r": r, "m": m, "rho": rho, "k": k if k is not None else "auto",
        "batch": batch, "us_per_step": us,
        "cells": cells, "mcells_per_s": cells / us,
    }
    emit(f"mxu/{wl.name}/{kind}/rho{rho}/b{batch}/k{rec['k']}", us,
         f"r={r};mcups={rec['mcells_per_s']:.1f}")
    return rec


def bench_mxu(frac, r, ms, iters, batches, out_path,
              min_speedup: float = 1.5) -> None:
    """v5 (pallas-mxu, stencil-as-matmul macro-tiles + native batch grid)
    vs v2/v4 (pallas-strips single-step / fused-k) across rho and batch
    size. Per configuration, step-for-step parity between the two kinds
    is asserted first (bit-exact for CA, 1e-5 for the PDE workloads);
    after writing the JSON the speedup gate *fails the process* unless
    the geometric-mean pallas-mxu speedup over pallas-strips across the
    batched (B >= 8) configurations at rho <= 9 reaches 1.5x mcells/s —
    the acceptance bar for the MXU path on the serving-shaped workloads
    (see DESIGN.md Section 2.2; individual configurations are printed so
    a single-cell regression is still visible in the CI log).
    """
    iters = max(iters, 10)
    steps = 6
    records = []
    for m in ms:
        if m > r:
            continue
        for wl in MXU_WORKLOADS:
            for batch in batches:
                runner = BatchedRunner()  # fresh cache per config: honest
                states = runner.init_batch("pallas-strips", frac, r,
                                           seeds=range(batch), m=m,
                                           workload=wl)
                want = runner.run("pallas-strips", frac, r, states,
                                  steps=steps, m=m, workload=wl)
                got = runner.run("pallas-mxu", frac, r, states,
                                 steps=steps, m=m, workload=wl)
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(want), **_tol(wl),
                    err_msg=f"mxu parity broke: {wl.name}/m={m}/b={batch}")
                for kind in ("pallas-strips", "pallas-mxu"):
                    records.append(bench_mxu_one(
                        runner, kind, frac, r, m, wl, None, batch, steps,
                        iters))
    out = pathlib.Path(out_path)
    out.write_text(json.dumps({
        "fractal": frac.name, "r": r, "ms": list(ms),
        "batches": list(batches), "backend": jax.default_backend(),
        "records": records}, indent=2))
    print(f"wrote {out} ({len(records)} records)")
    # JSON first, so a regression still leaves the timings behind
    speedups, gated = [], []
    for rec in records:
        if rec["engine"] != "pallas-mxu":
            continue
        base = next(b for b in records
                    if b["engine"] == "pallas-strips"
                    and b["workload"] == rec["workload"]
                    and b["m"] == rec["m"] and b["batch"] == rec["batch"])
        x = rec["mcells_per_s"] / base["mcells_per_s"]
        speedups.append((rec, x))
        if rec["rho"] <= 9 and rec["batch"] >= 8:
            gated.append(x)
    for rec, x in speedups:
        print(f"mxu speedup {rec['workload']}/rho{rec['rho']}"
              f"/b{rec['batch']}: {x:.2f}x")
    if gated:
        geomean = float(np.exp(np.mean(np.log(gated))))
        print(f"mxu gate: geomean over batched rho<=9 = {geomean:.2f}x "
              f"({len(gated)} configs)")
        if geomean < min_speedup:
            raise SystemExit(
                f"pallas-mxu geomean speedup {geomean:.2f}x < "
                f"{min_speedup}x over pallas-strips on batched rho<=9 "
                "configurations")


# ---------------------------------------------------- telemetry overhead
def bench_telemetry(frac, out_path: str, max_overhead_pct: float = 2.0,
                    rounds: int = 100, calls: int = 8) -> None:
    """Overhead of the instrumented runner hot path (the CI telemetry
    gate: benchmarks/ci_gates.py --gate telemetry).

    Three variants of the same fused batched run (block/LIFE, r=6, m=2,
    batch=4, steps=24 — a serving-shaped call where the dispatch is not
    degenerate), interleaved round-robin. The gate statistic is the
    MEDIAN OF PAIRED PER-ROUND DIFFERENCES (disabled minus direct,
    within the same round) over the median direct round: adjacent
    samples share whatever load the machine is under, so common-mode
    noise cancels where a ratio of independent mins does not (a loaded
    CI runner flips the sign of min-based ratios). The telemetry
    overhead is a fixed few machine instructions per ``run`` call, so
    the JSON records absolute us_per_run for all three variants
    alongside the relative gate:

    - ``direct``: the pre-PR fast path — exactly what
      ``BatchedRunner.run`` did before instrumentation: the LRU cache
      probe, the steps->int32 cast, and the ``batched_run`` dispatch,
      with none of the telemetry branches.
    - ``disabled``: ``BatchedRunner.run`` with telemetry off — the
      instrumented code with every obs helper short-circuiting. The
      gate: this must stay within ``max_overhead_pct`` of ``direct``.
    - ``enabled``: the same with telemetry on (informational; the
      opt-in cost of counters + histograms + spans per run).
    """
    from repro import obs

    r, m, batch, steps = 6, 2, 4, 24
    runner = BatchedRunner()
    states = runner.init_batch("block", frac, r, seeds=range(batch), m=m,
                               workload=LIFE)

    def run_runner(s):
        return runner.run("block", frac, r, s, steps=steps, m=m,
                          workload=LIFE)

    def run_direct(s):
        entry = runner._get("block", frac, r, m, LIFE, None, None, None)
        return entry.batched_run(
            s, jax.numpy.asarray(steps, jax.numpy.int32))

    prev = obs.enabled()
    variants = {
        "direct": (run_direct, False),
        "disabled": (run_runner, False),
        "enabled": (run_runner, True),
    }
    samples = {name: [] for name in variants}
    try:
        for fn, on in variants.values():  # warm every path once
            obs.enable(on)
            jax.block_until_ready(fn(states))
        for _ in range(rounds):
            for name, (fn, on) in variants.items():
                obs.enable(on)
                t0 = time.perf_counter()
                for _ in range(calls):
                    out = fn(states)
                jax.block_until_ready(out)
                samples[name].append((time.perf_counter() - t0) / calls)
    finally:
        obs.enable(prev)

    def median(xs):
        xs = sorted(xs)
        return xs[len(xs) // 2]

    us = {k: median(v) * 1e6 for k, v in samples.items()}
    pct = {}
    for k in ("disabled", "enabled"):
        diffs = [b - a for a, b in zip(samples["direct"], samples[k])]
        pct[k] = median(diffs) * 1e6 / us["direct"] * 100.0
    for name in variants:
        emit(f"telemetry/{name}", us[name],
             f"r={r};m={m};b={batch};steps={steps}")
    print(f"telemetry overhead: disabled {pct['disabled']:+.2f}% "
          f"enabled {pct['enabled']:+.2f}% (gate: disabled <= "
          f"{max_overhead_pct:.1f}%)")
    out = pathlib.Path(out_path)
    out.write_text(json.dumps({
        "backend": jax.default_backend(),
        "config": {"engine": "block", "workload": LIFE.name,
                   "fractal": frac.name, "r": r, "m": m, "batch": batch,
                   "steps": steps, "rounds": rounds,
                   "calls_per_sample": calls},
        "us_per_run": us,
        "gate": {"threshold_pct": max_overhead_pct,
                 "overhead_disabled_pct": pct["disabled"],
                 "overhead_enabled_pct": pct["enabled"]},
    }, indent=2))
    print(f"wrote {out}")
    # JSON first, so a regression still leaves the timings behind
    if pct["disabled"] > max_overhead_pct:
        raise SystemExit(
            f"telemetry-disabled runner overhead {pct['disabled']:.2f}% "
            f"> {max_overhead_pct:.1f}% over the direct fast path")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--r", type=int, default=9)
    ap.add_argument("--m", type=int, default=2)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny level, 2 iters (CI end-to-end check)")
    ap.add_argument("--fusion-only", action="store_true",
                    help="run only the temporal-fusion k sweep")
    ap.add_argument("--no-fusion", action="store_true",
                    help="skip the temporal-fusion k sweep (CI runs it "
                         "as its own step)")
    ap.add_argument("--mxu-only", action="store_true",
                    help="run only the v5 MXU vs strips sweep + gate "
                         "(the CI MXU perf-gate step)")
    ap.add_argument("--telemetry", action="store_true",
                    help="run only the telemetry-overhead microbench + "
                         "gate (the CI telemetry perf-gate step)")
    ap.add_argument("--max-overhead-pct", type=float, default=2.0,
                    help="telemetry gate: max %% slowdown of the "
                         "instrumented-but-disabled runner hot path vs "
                         "the direct fast path")
    ap.add_argument("--telemetry-out", default="BENCH_telemetry.json")
    ap.add_argument("--mxu-ms", type=int, nargs="+", default=None,
                    help="block levels m for the MXU rho sweep "
                         "(default: {m, m+1} clipped to r)")
    ap.add_argument("--mxu-batches", type=int, nargs="+", default=(1, 8))
    ap.add_argument("--min-speedup", type=float, default=None,
                    help="speedup gate threshold: best fused-k speedup "
                         "for the fusion sweep (default 1.0), geomean "
                         "batched mxu speedup for the mxu sweep "
                         "(default 1.5); benchmarks/ci_gates.py owns the "
                         "CI values")
    ap.add_argument("--out", default="BENCH_workloads.json")
    ap.add_argument("--fusion-out", default="BENCH_fusion.json")
    ap.add_argument("--mxu-out", default="BENCH_mxu.json")
    args = ap.parse_args()
    if args.smoke:
        args.r, args.m, args.iters = 5, 2, 2

    frac = fractals.SIERPINSKI
    if args.telemetry:
        bench_telemetry(frac, args.telemetry_out,
                        max_overhead_pct=args.max_overhead_pct)
        return
    if args.mxu_only:
        ms = args.mxu_ms or [m for m in (args.m, args.m + 1) if m <= args.r]
        bench_mxu(frac, args.r, ms, args.iters, tuple(args.mxu_batches),
                  args.mxu_out,
                  min_speedup=(1.5 if args.min_speedup is None
                               else args.min_speedup))
        return
    if not args.fusion_only:
        records = []
        for wl in WORKLOADS:
            for kind in ENGINES:
                records.append(bench_one(kind, frac, args.r, args.m, wl,
                                         args.iters))
            records.append(bench_batched(frac, args.r, args.m, wl,
                                         args.iters, args.batch))

        out = pathlib.Path(args.out)
        out.write_text(json.dumps({
            "fractal": frac.name, "r": args.r, "m": args.m,
            "backend": jax.default_backend(), "records": records},
            indent=2))
        print(f"wrote {out} ({len(records)} records)")

    if not args.no_fusion:
        bench_fusion(frac, args.r, args.m, args.iters, args.fusion_out,
                     min_speedup=(1.0 if args.min_speedup is None
                                  else args.min_speedup))


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
