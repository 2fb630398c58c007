"""CI gate for the autotuner: tuned knobs must not lose to the static
heuristics.

Runs the bounded ``ci`` preset sweep (``repro.tuning.preset_specs``)
with the same interleaved min-of-rounds timing the real tuner uses,
then gates on

* parity: every candidate in every sweep must match the heuristic
  engine bit-exactly (integer CA) / within tolerance (float PDE) — a
  parity failure anywhere fails the gate regardless of speed;
* geomean speedup of tuned-best vs the static heuristic across the
  preset, measured on the SAME timing matrix: must be >= the
  ``--min-speedup`` threshold (1.0 in CI — the heuristic baseline is
  itself in the candidate space, so a healthy tuner can never lose;
  < 1.0 means the sweep or the timer is broken).

Writes ``BENCH_tuner.json``:

    {"records": [{key, best, baseline, speedup, times,
                  parity_failures, roofline_s, suspect} ...],
     "gate": {geomean_speedup, parity_ok, suspects, min_speedup,
              passed}}

Run via ``python benchmarks/ci_gates.py --gate tuner`` (CI) or
directly: ``PYTHONPATH=src python benchmarks/tuner_bench.py``.
"""
from __future__ import annotations

import argparse
import json

from repro.tuning import geomean, preset_specs, tune_spec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="ci", choices=["ci", "default"])
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-candidates", type=int, default=8)
    ap.add_argument("--min-speedup", type=float, default=1.0)
    ap.add_argument("--out", default="BENCH_tuner.json")
    args = ap.parse_args()

    records = []
    speedups = []
    parity_ok = True
    for spec in preset_specs(args.preset):
        res = tune_spec(spec, steps=args.steps, rounds=args.rounds,
                        seed=args.seed,
                        max_candidates=args.max_candidates)
        parity_ok &= not res.parity_failures
        speedups.append(res.speedup)
        records.append({
            "key": res.spec.tuning_key(),
            "best": res.best.label,
            "baseline": res.baseline.label,
            "speedup": res.speedup,
            "times": res.times,
            "parity_failures": res.parity_failures,
            "roofline_s": res.roofline_s,
            "suspect": res.suspect,
        })
        print(f"tuner,{res.spec.tuning_key()},best={res.best.label},"
              f"baseline={res.baseline.label},"
              f"speedup={res.speedup:.3f}", flush=True)

    gm = geomean(speedups)
    gate = {
        "geomean_speedup": gm,
        "parity_ok": parity_ok,
        "suspects": sum(1 for r in records if r["suspect"]),
        "min_speedup": args.min_speedup,
        "passed": parity_ok and gm >= args.min_speedup,
    }
    with open(args.out, "w", encoding="utf-8") as fh:
        json.dump({"records": records, "gate": gate}, fh, indent=2)
    print(f"tuner gate: geomean={gm:.3f}x (min {args.min_speedup}), "
          f"parity_ok={parity_ok} -> "
          f"{'PASS' if gate['passed'] else 'FAIL'}", flush=True)
    return 0 if gate["passed"] else 1


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
