"""Serving-layer gate: continuous-batching throughput + bounded
recovery.

Phase 1 (throughput): N same-bucket requests served with continuous
batching (``max_batch=N`` — one vmapped launch per segment for the
whole batch) vs the per-request baseline (``max_batch=1`` — the same
service machinery, one row per launch, which is what serving without
batching costs). Continuous batching must reach ``--min-speedup`` x
the per-request rate. The raw sequential engine loop (no service at
all) is also timed and recorded — informational: on single-device CPU
its compute equals the vmapped batch's, so it bounds what any serving
layer can reach rather than gating this one. Results are asserted
bit-exact against the raw engine runs first — a fast wrong answer
never passes.

Phase 2 (recovery): the same workload with an in-step crash and a
corrupted checkpoint injected mid-run. Every request must still finish
``ok`` and bit-exact, and the measured recovery time (failure ->
batch healthy again, from the ``serve.recovery_seconds`` histogram)
must stay under ``--max-recovery-s``.

Writes ``BENCH_serve.json`` (records + a ``gate`` verdict) before the
gate check, so a failing run still leaves its numbers behind for the
CI artifact upload.

Usage:
    PYTHONPATH=src python benchmarks/serve_bench.py \
        --min-speedup 1.0 --max-recovery-s 10.0 --out BENCH_serve.json
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time

import numpy as np

from repro import obs
from repro.core import fractals
from repro.core.stencil import make_engine
from repro.runtime.fault import Fault, FaultInjector
from repro.serving import FractalService, ServiceConfig, SimRequest
from repro.workloads import LIFE

FRAC = fractals.SIERPINSKI
R = 5
M = 2


def _reqs(n, steps, prefix, snapshot_every=0):
    return [SimRequest(frac=FRAC, r=R, steps=steps, m=M, workload=LIFE,
                       seed=s, snapshot_every=snapshot_every,
                       rid=f"{prefix}-{s}")
            for s in range(n)]


def _sequential(n, steps, eng):
    """The no-service baseline: one engine, one request at a time."""
    outs = []
    t0 = time.perf_counter()
    for s in range(n):
        state = eng.run(eng.init_random(s), steps)
        outs.append(np.asarray(state))  # host read, like SimResult.state
    dt = time.perf_counter() - t0
    return outs, dt


def _serve_timed(cfg, runner, reqs):
    svc = FractalService(cfg, runner=runner)
    t0 = time.perf_counter()
    res = svc.serve(reqs)
    return res, time.perf_counter() - t0


def bench_throughput(n, steps, cfg, base_cfg, runner):
    # warm every path OUTSIDE the timed region: the raw loop pays its
    # single-sim trace, each service config its vmapped trace at its
    # real batch size (the shared runner keeps the compiled entries
    # across service instances)
    eng = make_engine("block", FRAC, R, M, workload=LIFE)
    _sequential(n, 2, eng)
    FractalService(base_cfg, runner=runner).serve(_reqs(2, 2, "w1"))
    FractalService(cfg, runner=runner).serve(_reqs(n, 2, "wn"))
    refs, raw_s = _sequential(n, steps, eng)

    base_res, base_s = _serve_timed(base_cfg, runner,
                                    _reqs(n, steps, "base"))
    res, svc_s = _serve_timed(cfg, runner, _reqs(n, steps, "tput"))
    for i, r in enumerate(res):
        assert r.ok, (r.rid, r.status, r.error)
        np.testing.assert_array_equal(refs[i], r.state)
    for i, r in enumerate(base_res):
        assert r.ok, (r.rid, r.status, r.error)
        np.testing.assert_array_equal(refs[i], r.state)
    return {"phase": "throughput", "n": n, "steps": steps,
            "raw_seq_s": raw_s, "raw_seq_rps": n / raw_s,
            "per_request_s": base_s, "per_request_rps": n / base_s,
            "svc_s": svc_s, "svc_rps": n / svc_s,
            "speedup": base_s / svc_s}


def bench_recovery(n, steps, cfg, reg, runner):
    eng = make_engine("block", FRAC, R, M, workload=LIFE)
    refs, _ = _sequential(n, steps, eng)
    inj = FaultInjector([Fault(kind="exception", at_segment=1),
                         Fault(kind="corrupt", at_segment=1),
                         Fault(kind="exception", at_segment=3)])
    svc = FractalService(cfg, runner=runner, injector=inj)
    t0 = time.perf_counter()
    res = svc.serve(_reqs(n, steps, "chaos", snapshot_every=8))
    wall = time.perf_counter() - t0
    for i, r in enumerate(res):
        assert r.ok, (r.rid, r.status, r.error)
        np.testing.assert_array_equal(refs[i], r.state)
    assert inj.all_fired(), inj.pending()
    rec = reg.histogram("serve.recovery_seconds", kind="block")
    assert rec.count >= 2, "recoveries not recorded"
    return {"phase": "recovery", "n": n, "steps": steps, "wall_s": wall,
            "faults": [f.kind for f in inj.faults],
            "recoveries": rec.count, "mean_recovery_s": rec.mean,
            "max_recovery_s": rec.max}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=8,
                    help="requests per phase (one bucket)")
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--min-speedup", type=float, default=1.0)
    ap.add_argument("--max-recovery-s", type=float, default=10.0)
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()

    from repro.workloads import BatchedRunner
    runner = BatchedRunner()
    with obs.enabled_scope(True) as reg:
        obs.reset()
        # throughput phase: no snapshots requested, so let one segment
        # cover the whole run — the batch advantage is one vmapped call
        # for all n requests vs n sequential dispatches
        cfg = ServiceConfig(max_batch=args.n,
                            max_segment_steps=args.steps,
                            backoff_base_s=0.01, backoff_cap_s=0.1,
                            hang_threshold_s=30.0)
        base_cfg = ServiceConfig(max_batch=1,
                                 max_segment_steps=args.steps,
                                 backoff_base_s=0.01,
                                 backoff_cap_s=0.1,
                                 hang_threshold_s=30.0)
        records = [bench_throughput(args.n, args.steps, cfg, base_cfg,
                                    runner)]
        with tempfile.TemporaryDirectory() as d:
            ccfg = ServiceConfig(max_batch=args.n, max_segment_steps=8,
                                 backoff_base_s=0.01, backoff_cap_s=0.1,
                                 hang_threshold_s=30.0, ckpt_dir=d)
            records.append(bench_recovery(args.n, args.steps, ccfg,
                                          reg, runner))

    tput, rec = records
    gate = {
        "min_speedup": args.min_speedup,
        "speedup": tput["speedup"],
        "per_request_rps": tput["per_request_rps"],
        "raw_seq_rps": tput["raw_seq_rps"],
        "svc_rps": tput["svc_rps"],
        "max_recovery_s": args.max_recovery_s,
        "recovery_s": rec["max_recovery_s"],
        "recoveries": rec["recoveries"],
        "passed": (tput["speedup"] >= args.min_speedup
                   and rec["max_recovery_s"] <= args.max_recovery_s),
    }
    with open(args.out, "w") as f:
        json.dump({"records": records, "gate": gate}, f, indent=2)
    print(f"[serve_bench] per-request {tput['per_request_rps']:.2f} "
          f"req/s -> batched {tput['svc_rps']:.2f} req/s "
          f"({tput['speedup']:.2f}x; raw loop "
          f"{tput['raw_seq_rps']:.2f} req/s); recovery "
          f"{rec['max_recovery_s']:.3f}s over {rec['recoveries']} "
          f"recoveries")
    if not gate["passed"]:
        raise SystemExit(
            f"serve gate FAILED: speedup {tput['speedup']:.2f} < "
            f"{args.min_speedup} or recovery "
            f"{rec['max_recovery_s']:.3f}s > {args.max_recovery_s}s")
    print("[serve_bench] gate passed")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
