"""Distributed k-fused halo exchange sweep on the 8-device CPU mesh.

Sweeps fusion depth k x n_devices x workload for the multi-device engine
(core/distributed.py, 'dist-block' XLA compute — the kernel computes run
the Pallas interpreter off-TPU, so their CPU timings say nothing about
the MXU path and are not swept here). k=1 reproduces the pre-fusion
engine's every-step-exchange pattern (one strip all-gather per step) and
is the baseline; fused k>=2 exchanges depth-k strips once per k steps.

    PYTHONPATH=src python benchmarks/distributed_bench.py [--r 6] [--m 2]
                                                          [--smoke]

Per configuration the bench asserts parity against the single-device
block engine (bit-exact for Life, 1e-5 for the PDE workloads) and records
the engine's ``exchange_stats()`` (collectives per step, strip bytes
gathered per step) and ``memory_bytes()`` next to the timing. Writes
BENCH_distributed.json; after the JSON is written, the gate *fails the
process* unless the geometric mean over the 8-device configurations of
the best fused (k>=2) per-step speedup vs the k=1 baseline reaches 1.5x
— the CI distributed perf-gate step.

Methodology notes (the host-platform "mesh" is threads on a few cores,
so wall-clock is noisy): every k of a (workload, n_devices) cell is
timed in INTERLEAVED rounds and scored by its minimum per-step time
(noise on a shared runner only ever adds time), and each timed call runs
``--steps 32`` steps inside the engine's compiled fori_loop so the
per-call Python/dispatch overhead — identical for every k — does not
dilute the per-step exchange signal being measured.

The script forces 8 single-threaded host-platform CPU devices; it must
own the process (the flag precedes the jax import), which is also why CI
runs it as its own step rather than inside pytest.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

# hard assignment, not setdefault: the CI gate depends on the 8-device
# mesh existing — a stray inherited XLA_FLAGS must not silently shrink it
# (same pattern as tests/_distributed_check.py)
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8"
                           " --xla_cpu_multi_thread_eigen=false")

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.core import fractals  # noqa: E402
from repro.core.compact import BlockLayout  # noqa: E402
from repro.core.distributed import make_distributed_engine  # noqa: E402
from repro.core.stencil import SqueezeBlockEngine  # noqa: E402
from repro.workloads import GRAY_SCOTT, HEAT, LIFE  # noqa: E402
from benchmarks.common import emit  # noqa: E402

WORKLOADS = (LIFE, HEAT, GRAY_SCOTT)


def _tol(wl):
    return dict(rtol=0, atol=0) if wl is LIFE \
        else dict(rtol=1e-5, atol=1e-5)


def _reference(layout, wl, steps):
    eng = SqueezeBlockEngine(layout, wl, fusion_k=1)
    s = eng.init_random(0)
    for _ in range(steps):
        s = eng.step(s)
    return np.asarray(s)


def _one_time(eng, state, steps) -> float:
    t0 = time.perf_counter()
    jax.block_until_ready(eng.run(state, steps))
    return (time.perf_counter() - t0) / steps * 1e6


def bench_cell(layout, mesh, wl, ks, steps, rounds, want) -> list:
    """All fusion depths of one (workload, mesh) cell, interleaved."""
    engines, states = {}, {}
    for k in ks:
        # pinned to the all-gather exchange: this sweep's gate measures
        # the k-fusion win against its own k=1 baseline, and pinning
        # keeps the series comparable across PRs. The exchange-mode
        # comparison (p2p vs gather across device counts) is the
        # --scaling sweep below.
        eng = make_distributed_engine(layout, mesh=mesh, workload=wl,
                                      compute="jnp", fusion_k=k,
                                      exchange="gather")
        state = eng.init_random(0)
        got = eng.run(state, steps)  # warm + parity in one
        np.testing.assert_allclose(
            np.asarray(eng.to_dense(got)), want, **_tol(wl),
            err_msg=f"distributed parity broke: {wl.name}"
                    f"/nd={eng.n_shards}/k={k}")
        engines[k], states[k] = eng, state
    acc = {k: [] for k in ks}
    for k in ks:  # second warmup round, uninterleaved
        _one_time(engines[k], states[k], steps)
    for _ in range(rounds):
        for k in ks:
            acc[k].append(_one_time(engines[k], states[k], steps))
    records = []
    for k in ks:
        eng = engines[k]
        eng.reset_exchange_stats()
        eng.run(states[k], steps)
        st = eng.exchange_stats()
        us = min(acc[k])
        cells = layout.frac.volume(layout.r)
        records.append({
            "workload": wl.name, "engine": "dist-block",
            "fractal": layout.frac.name, "r": layout.r, "m": layout.m,
            "n_devices": eng.n_shards, "k": k, "us_per_step": us,
            "cells": cells, "mcells_per_s": cells / us,
            "memory_bytes": eng.memory_bytes(),
            "collectives_per_step": st.collectives_per_step,
            "bytes_gathered_per_step": st.bytes_per_step,
        })
        emit(f"dist/{wl.name}/nd{eng.n_shards}/k{k}", us,
             f"r={layout.r};coll/step={st.collectives_per_step:.2f};"
             f"KiB/step={st.bytes_per_step / 1024:.1f}")
    return records


# ------------------------------------------------ device-count scaling
def _scaling_cell(layout, nd, wl, k, steps, rounds, want):
    """One device count, both exchange modes, interleaved timing.
    Returns {exchange: record}."""
    mesh = Mesh(np.array(jax.devices()[:nd]), ("data",))
    engines, states = {}, {}
    for ex in ("gather", "p2p"):
        eng = make_distributed_engine(layout, mesh=mesh, workload=wl,
                                      compute="jnp", fusion_k=k,
                                      exchange=ex)
        state = eng.init_random(0)
        got = eng.run(state, steps)  # warm + parity in one
        np.testing.assert_allclose(
            np.asarray(eng.to_dense(got)), want, **_tol(wl),
            err_msg=f"scaling parity broke: {wl.name}/nd={nd}/{ex}")
        engines[ex], states[ex] = eng, state
    acc = {ex: [] for ex in engines}
    for ex in engines:  # second warmup round, uninterleaved
        _one_time(engines[ex], states[ex], steps)
    for _ in range(rounds):
        for ex in engines:
            acc[ex].append(_one_time(engines[ex], states[ex], steps))
    out = {}
    for ex, eng in engines.items():
        eng.reset_exchange_stats()
        eng.run(states[ex], steps)
        st = eng.exchange_stats()
        us = min(acc[ex])
        # per-device wire bytes per STEP: the scaling gate's curve. The
        # accounting is static (routing tables), so this is exact, not
        # a measurement.
        pd_step = eng.wire_bytes_per_device(k) / k
        out[ex] = {
            "workload": wl.name, "engine": "dist-block",
            "fractal": layout.frac.name, "r": layout.r, "m": layout.m,
            "exchange": ex, "n_devices": nd, "k": k,
            "us_per_step": us,
            "wire_bytes_per_device_per_step": pd_step,
            "exchanged_bytes_per_step": st.bytes_per_step,
            "neighbor_sends": st.neighbor_sends,
            "collectives_per_step": st.collectives_per_step,
        }
        emit(f"dist-scaling/{ex}/nd{nd}", us,
             f"r={layout.r};wireB/dev/step={pd_step:.0f}")
    return out


def main_scaling(args):
    """p2p-vs-gather device-count scaling sweep + gate: p2p per-device
    exchanged bytes/step must be flat in the device count (the gather
    curve grows ~linearly), and p2p must not lose to gather on the full
    mesh. Writes BENCH_dist_scaling.json."""
    n_avail = jax.device_count()
    devices = tuple(args.devices)
    if max(devices) > n_avail:
        raise SystemExit(
            f"--devices {max(devices)} exceeds the {n_avail} "
            "available devices (the gated mesh would silently shrink)")
    frac = fractals.SIERPINSKI
    # the default r=11/m=1 keeps the 8-shard strip decomposition valid
    # AND exactly flat: every shard boundary lands inside the widest
    # row band, so ms_prev/ms_next (and with them the per-device wire
    # bytes) are identical at nd = 2, 4 and 8
    layout = BlockLayout(frac, args.r, args.m)
    if not layout.strip_decomposition(max(devices)).valid:
        raise SystemExit(
            f"r={args.r}, m={args.m} has too few occupied rows for a "
            f"{max(devices)}-shard p2p decomposition — raise --r")
    wl, k = LIFE, min(2, layout.rho)
    want = _reference(layout, wl, args.steps)

    def sweep():
        cells = {}
        for nd in devices:
            cells[nd] = _scaling_cell(layout, nd, wl, k, args.steps,
                                      args.rounds, want)
        return cells

    def curve(cells, ex, field):
        return {nd: cells[nd][ex][field] for nd in devices}

    # byte curves are static routing-table arithmetic: one sweep decides
    # them. Wall-clock on the oversubscribed shared CPU runner is noisy:
    # the time condition gets up to 3 measurement attempts (best kept).
    attempts, best_cells, best_ratio = 0, None, float("inf")
    while attempts < (1 if args.smoke else 3):
        attempts += 1
        cells = sweep()
        nd_max = max(devices)
        ratio = (cells[nd_max]["p2p"]["us_per_step"]
                 / cells[nd_max]["gather"]["us_per_step"])
        if ratio < best_ratio:
            best_cells, best_ratio = cells, ratio
        if best_ratio <= args.max_slowdown:
            break
        if attempts < 3 and not args.smoke:
            print(f"scaling gate attempt {attempts}: p2p/gather time "
                  f"ratio {ratio:.2f} > {args.max_slowdown} — "
                  "re-measuring")
    cells = best_cells
    nd_lo = min(nd for nd in devices if nd >= 2)
    nd_max = max(devices)
    p2p_pd = curve(cells, "p2p", "wire_bytes_per_device_per_step")
    gat_pd = curve(cells, "gather", "wire_bytes_per_device_per_step")
    p2p_flat = p2p_pd[nd_max] <= p2p_pd[nd_lo] * args.flat_tol
    gather_grows = gat_pd[nd_max] >= gat_pd[nd_lo] * 1.5
    p2p_fast = best_ratio <= args.max_slowdown
    gate = {
        "n_devices": nd_max, "attempts": attempts,
        "p2p_bytes_per_device": p2p_pd,
        "gather_bytes_per_device": gat_pd,
        "flat_tol": args.flat_tol,
        "p2p_bytes_flat": bool(p2p_flat),
        "gather_bytes_grow": bool(gather_grows),
        "p2p_vs_gather_time_ratio": best_ratio,
        "max_slowdown": args.max_slowdown,
        "p2p_no_time_regression": bool(p2p_fast),
        "pass": bool(p2p_flat and gather_grows and p2p_fast),
    }
    records = [rec for nd in devices for rec in cells[nd].values()]
    out = pathlib.Path(args.out)
    out.write_text(json.dumps({
        "mode": "scaling", "fractal": frac.name, "r": args.r,
        "m": args.m, "k": k, "steps": args.steps,
        "rounds": args.rounds, "backend": jax.default_backend(),
        "n_devices_available": n_avail,
        "records": records, "gate": gate,
    }, indent=2))
    print(f"wrote {out} ({len(records)} records)")
    for nd in devices:
        p_us = cells[nd]["p2p"]["us_per_step"]
        g_us = cells[nd]["gather"]["us_per_step"]
        print(f"scaling nd={nd}: p2p {p_us:.1f}us/step "
              f"({p2p_pd[nd]:.0f} B/dev/step), gather {g_us:.1f}"
              f"us/step ({gat_pd[nd]:.0f} B/dev/step)")
    # JSON first, so a regression still leaves the curves behind
    if args.smoke:
        print(f"smoke: p2p/gather time ratio {best_ratio:.2f} "
              "(gate not enforced)")
        return
    if not gate["pass"]:
        msgs = []
        if not p2p_flat:
            msgs.append(
                f"p2p per-device bytes grew with the mesh: "
                f"{p2p_pd[nd_lo]:.0f} B @ nd={nd_lo} -> "
                f"{p2p_pd[nd_max]:.0f} B @ nd={nd_max} "
                f"(tol {args.flat_tol}x)")
        if not gather_grows:
            msgs.append("gather per-device bytes did not grow — the "
                        "baseline curve is wrong")
        if not p2p_fast:
            msgs.append(
                f"p2p lost to gather on nd={nd_max}: time ratio "
                f"{best_ratio:.2f} > {args.max_slowdown}")
        raise SystemExit("dist-scaling gate failed: " + "; ".join(msgs))
    print(f"dist-scaling gate: p2p bytes flat "
          f"({p2p_pd[nd_lo]:.0f} -> {p2p_pd[nd_max]:.0f} B/dev/step), "
          f"gather grows ({gat_pd[nd_lo]:.0f} -> {gat_pd[nd_max]:.0f}), "
          f"p2p/gather time ratio {best_ratio:.2f} on nd={nd_max}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--r", type=int, default=6)
    ap.add_argument("--m", type=int, default=2)
    ap.add_argument("--rounds", type=int, default=15,
                    help="interleaved timing rounds per cell")
    ap.add_argument("--steps", type=int, default=32,
                    help="steps per timed run() call")
    ap.add_argument("--devices", type=int, nargs="+", default=(2, 4, 8))
    ap.add_argument("--ks", type=int, nargs="+", default=(1, 2, 4))
    ap.add_argument("--gate", type=float, default=1.5)
    ap.add_argument("--scaling", action="store_true",
                    help="p2p-vs-gather device-count scaling sweep + "
                         "gate instead of the k-fusion sweep (r/m "
                         "default to 11/1 — the exchange-bound fine-"
                         "block regime; devices default to 1 2 4 8)")
    ap.add_argument("--max-slowdown", type=float, default=1.05,
                    help="scaling gate: max allowed p2p/gather "
                         "per-step time ratio on the full mesh")
    ap.add_argument("--flat-tol", type=float, default=1.25,
                    help="scaling gate: max allowed growth of p2p "
                         "per-device bytes from the smallest multi-"
                         "device mesh to the full mesh")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sweep: {1,8} devices, 4 rounds (dev loop; "
                         "gate not enforced)")
    ap.add_argument("--out", default="BENCH_distributed.json")
    args = ap.parse_args()
    if args.scaling:
        # scaling defaults differ: full device curve, and the fine-block
        # exchange-bound regime (m=1 -> rho=2: ~4*ns/rho wire bytes per
        # compute cell under gather) where the neighbor-only exchange
        # is the difference that shows — at coarse blocks the all-gather
        # is a negligible in-process memcpy and the sweep measures
        # nothing but compute
        if ap.get_default("r") == args.r:
            args.r = 11
        if ap.get_default("m") == args.m:
            args.m = 1
        if tuple(args.devices) == tuple(ap.get_default("devices")):
            args.devices = (1, 2, 4, 8)
        if ap.get_default("out") == args.out:
            args.out = "BENCH_dist_scaling.json"
        if args.smoke:
            args.rounds, args.devices = 4, (1, 2, 8)
        return main_scaling(args)
    if args.smoke:
        args.rounds, args.devices = 4, (1, 8)

    n_avail = jax.device_count()
    if max(args.devices) > n_avail:
        raise SystemExit(
            f"--devices {max(args.devices)} exceeds the {n_avail} "
            "available devices (the gated mesh would silently shrink)")
    frac = fractals.SIERPINSKI
    layout = BlockLayout(frac, args.r, args.m)
    ks = tuple(k for k in args.ks if k <= layout.rho)

    refs = {wl.name: _reference(layout, wl, args.steps)
            for wl in WORKLOADS}

    def sweep(nd):
        mesh = Mesh(np.array(jax.devices()[:nd]), ("data",))
        return [rec for wl in WORKLOADS
                for rec in bench_cell(layout, mesh, wl, ks, args.steps,
                                      args.rounds, refs[wl.name])]

    def cell_speedups(recs):
        """Best fused (k>=2) speedup vs the k=1 every-step-exchange
        baseline, per (workload, n_devices) cell."""
        out = []
        for rec in recs:
            if rec["k"] != 1:
                continue
            fused = [f for f in recs
                     if f["workload"] == rec["workload"]
                     and f["n_devices"] == rec["n_devices"]
                     and f["k"] > 1]
            if not fused:
                continue
            best = min(fused, key=lambda f: f["us_per_step"])
            out.append({
                "workload": rec["workload"],
                "n_devices": rec["n_devices"], "best_k": best["k"],
                "speedup": rec["us_per_step"] / best["us_per_step"],
            })
        return out

    def geo(sps):
        vals = [s["speedup"] for s in sps]
        return float(np.exp(np.mean(np.log(vals)))) if vals \
            else float("nan")

    records = []
    for nd in args.devices:
        if nd <= n_avail and nd != max(args.devices):
            records.extend(sweep(nd))
    # the gated mesh: wall-clock on an oversubscribed shared CPU runner
    # is noisy, so a below-threshold geomean is re-measured (up to 3
    # attempts, best kept) — a structural regression fails every attempt
    attempts = 0
    gated_records, geomean = [], float("-inf")
    while attempts < (1 if args.smoke else 3):
        attempts += 1
        recs = sweep(max(args.devices))
        g = geo(cell_speedups(recs))
        if g > geomean:
            gated_records, geomean = recs, g
        if geomean >= args.gate:
            break
        if attempts < 3 and not args.smoke:
            print(f"gate attempt {attempts}: geomean {g:.2f}x < "
                  f"{args.gate}x — re-measuring")
    records.extend(gated_records)
    speedups = cell_speedups(records)
    gated = [s["speedup"] for s in speedups
             if s["n_devices"] == max(args.devices)]

    out = pathlib.Path(args.out)
    out.write_text(json.dumps({
        "fractal": frac.name, "r": args.r, "m": args.m,
        "steps": args.steps, "rounds": args.rounds,
        "backend": jax.default_backend(),
        "n_devices_available": n_avail,
        "records": records, "speedups": speedups,
        "gate": {"n_devices": max(args.devices), "threshold": args.gate,
                 "geomean_fused_speedup": geomean,
                 "attempts": attempts},
    }, indent=2))
    print(f"wrote {out} ({len(records)} records)")
    for s in speedups:
        print(f"dist speedup {s['workload']}/nd{s['n_devices']}: "
              f"{s['speedup']:.2f}x (best k={s['best_k']})")
    # JSON first, so a regression still leaves the timings behind
    if args.smoke:
        print(f"smoke: geomean fused speedup on nd={max(args.devices)} = "
              f"{geomean:.2f}x (gate not enforced)")
        return
    if not gated or not math.isfinite(geomean):
        raise SystemExit("no gated configurations ran")
    print(f"dist gate: geomean fused speedup on nd={max(args.devices)} = "
          f"{geomean:.2f}x over {len(gated)} workloads")
    if geomean < args.gate:
        raise SystemExit(
            f"k-fused distributed stepping geomean speedup {geomean:.2f}x "
            f"< {args.gate}x vs the every-step-exchange baseline on the "
            f"{max(args.devices)}-device mesh")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
