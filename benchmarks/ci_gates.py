"""One entry point for the CI perf-gate matrix.

The workflow used to carry four copy-pasted inline bench invocations
(workloads smoke, fusion, mxu, distributed) whose thresholds, output
paths and env quirks lived in YAML. This script owns all of that:

    PYTHONPATH=src python benchmarks/ci_gates.py --gate <name>
    PYTHONPATH=src python benchmarks/ci_gates.py --list

one gate per CI matrix entry ({workloads, fusion, mxu, distributed,
3d, telemetry}). Each gate shells out to its bench script in a fresh
interpreter — deliberately: the distributed gate must set XLA_FLAGS
before jax is imported (it forces the 8-device host-platform mesh),
and a subprocess keeps every gate's device/backend state isolated from
this process and from the other gates. The bench scripts keep their
own parity assertions; the *thresholds* and JSON artifact paths are
pinned here so the workflow matrix calls this with one flag and
nothing else.

Every gate run also writes ``gate_report_<name>.json`` next to the
bench JSON — a structured verdict for the artifact upload: the
threshold, the measured numbers re-derived from the bench JSON (so the
report is self-contained even if the raw JSON rots), parity status,
exit status, and a telemetry snapshot of the gate subprocess (the
subprocess runs with ``SQUEEZE_TELEMETRY=1`` and dumps its registry
via ``SQUEEZE_TELEMETRY_DUMP`` at exit — kernel entry counts, fused
launches, cache hits, collective counts land in the CI artifact for
free). The report is written even when the bench fails, before the
exit status propagates.

Exit status is the bench's: nonzero on parity breakage or a speedup
below the gate threshold. The JSON is written before the gate check,
so a failing run still leaves its timings behind for the artifact
upload (`if: always()`).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import subprocess
import sys

BENCH_DIR = pathlib.Path(__file__).resolve().parent


# --------------------------------------------------- per-gate summarizers
# Each takes the gate's parsed bench JSON and returns the measured
# numbers the gate decided on — mirroring (not re-running) the bench's
# own gate arithmetic so the report is honest about what was compared.
def _summ_workloads(data):
    mc = [r["mcells_per_s"] for r in data["records"]]
    return {"records": len(mc),
            "max_mcells_per_s": max(mc), "min_mcells_per_s": min(mc)}


def _summ_fusion(data):
    records = data["records"]
    best = 0.0
    for rec in records:
        if rec["k"] == 1:
            continue
        base = next(b for b in records
                    if b["k"] == 1 and b["engine"] == rec["engine"]
                    and b["workload"] == rec["workload"])
        best = max(best, base["us_per_step"] / rec["us_per_step"])
    return {"records": len(records), "best_fused_speedup": best}


def _summ_mxu(data):
    records = data["records"]
    gated = []
    for rec in records:
        if rec["engine"] != "pallas-mxu":
            continue
        base = next(b for b in records
                    if b["engine"] == "pallas-strips"
                    and b["workload"] == rec["workload"]
                    and b["m"] == rec["m"] and b["batch"] == rec["batch"])
        if rec["rho"] <= 9 and rec["batch"] >= 8:
            gated.append(rec["mcells_per_s"] / base["mcells_per_s"])
    geomean = (math.exp(sum(map(math.log, gated)) / len(gated))
               if gated else None)
    return {"records": len(records), "gated_configs": len(gated),
            "geomean_batched_speedup": geomean}


def _summ_distributed(data):
    return dict(data["gate"])


def _summ_3d(data):
    records = data["records"]
    best = {}  # (fractal, workload, r, m) -> best fused speedup
    for rec in records:
        if rec["engine"] != "block3d" or rec["k"] < 2:
            continue
        base = next(b for b in records
                    if b["engine"] == "cell3d"
                    and b["fractal"] == rec["fractal"]
                    and b["workload"] == rec["workload"]
                    and b["r"] == rec["r"] and b["m"] == rec["m"])
        key = (rec["fractal"], rec["workload"], rec["r"], rec["m"])
        x = base["us_per_step"] / rec["us_per_step"]
        best[key] = max(best.get(key, 0.0), x)
    xs = list(best.values())
    geomean = (math.exp(sum(map(math.log, xs)) / len(xs))
               if xs else None)
    return {"records": len(records), "configs": len(xs),
            "geomean_best_fused_speedup": geomean}


def _summ_telemetry(data):
    return dict(data["gate"])


def _summ_serve(data):
    return dict(data["gate"])


def _summ_chaos_dist(data):
    return dict(data["gate"])


def _summ_dist_scaling(data):
    return dict(data["gate"])


def _summ_tuner(data):
    return dict(data["gate"])


#: gate name -> spec. Thresholds and output paths live HERE, not in the
#: workflow and not in bench defaults. ``threshold`` is the number the
#: bench gate compares against (None: correctness/parity-only gate);
#: ``summarize`` re-derives the measured side from the bench JSON for
#: the gate report.
GATES = {
    # every (workload, engine, batch) combination runs end to end
    "workloads": dict(
        script="workloads_bench.py",
        args=["--smoke", "--no-fusion", "--out", "BENCH_workloads.json"],
        env={}, out="BENCH_workloads.json", threshold=None,
        summarize=_summ_workloads),
    # fused k>=2 stepping must beat single stepping somewhere (parity
    # asserted per configuration first)
    "fusion": dict(
        script="workloads_bench.py",
        args=["--smoke", "--fusion-only", "--min-speedup", "1.0",
              "--fusion-out", "BENCH_fusion.json"],
        env={}, out="BENCH_fusion.json", threshold=1.0,
        summarize=_summ_fusion),
    # v5 stencil-as-matmul vs pallas-strips at a block count large
    # enough to exercise the macro-tile grid: geomean batched speedup
    # at rho <= 9 must reach 1.5x (bit-exact CA / 1e-5 PDE parity)
    "mxu": dict(
        script="workloads_bench.py",
        args=["--mxu-only", "--r", "7", "--mxu-ms", "2",
              "--mxu-batches", "8", "--min-speedup", "1.5",
              "--mxu-out", "BENCH_mxu.json"],
        env={}, out="BENCH_mxu.json", threshold=1.5,
        summarize=_summ_mxu),
    # k-fused strip halo exchange vs every-step exchange on the 8-device
    # host-platform CPU mesh; geomean best fused per-step speedup on the
    # largest mesh must reach 1.5x. XLA_FLAGS is set by the bench itself
    # before importing jax — which is exactly why it needs its own
    # interpreter.
    "distributed": dict(
        script="distributed_bench.py",
        args=["--gate", "1.5", "--out", "BENCH_distributed.json"],
        env={}, out="BENCH_distributed.json", threshold=1.5,
        summarize=_summ_distributed),
    # 3D stack: block3d fused k-stepping vs the cell3d per-cell engine
    # across r x rho x k (parity per configuration); geomean best fused
    # speedup must reach 1.5x
    "3d": dict(
        script="stencil3d_bench.py",
        args=["--smoke", "--min-speedup", "1.5", "--out",
              "BENCH_3d.json"],
        env={}, out="BENCH_3d.json", threshold=1.5,
        summarize=_summ_3d),
    # the instrumented-but-disabled BatchedRunner hot path must stay
    # within 2% of the pre-instrumentation fast path (threshold is a
    # max overhead %, not a min speedup). The bench toggles telemetry
    # itself, so no SQUEEZE_TELEMETRY in env (it would be ignored —
    # but don't imply otherwise).
    "telemetry": dict(
        script="workloads_bench.py",
        args=["--telemetry", "--max-overhead-pct", "2.0",
              "--telemetry-out", "BENCH_telemetry.json"],
        env={}, out="BENCH_telemetry.json", threshold=2.0,
        summarize=_summ_telemetry, no_telemetry_env=True),
    # continuous batching vs per-request serving (same service
    # machinery, max_batch=1) — batched must not lose; plus the chaos
    # phase: injected crash + corrupt checkpoint must recover bit-exact
    # within the wall-time bound. The bench drives its own
    # enabled_scope registry, so no SQUEEZE_TELEMETRY needed (the dump
    # env is still honored for the artifact snapshot).
    "serve": dict(
        script="serve_bench.py",
        args=["--min-speedup", "1.0", "--max-recovery-s", "10.0",
              "--out", "BENCH_serve.json"],
        env={}, out="BENCH_serve.json", threshold=1.0,
        summarize=_summ_serve),
    # the distributed chaos matrix on the 8-device CPU mesh: every
    # shard-level fault class (exception, stalled launch, device loss
    # + elastic 8->4 reshard, corrupted halo band, damaged sharded
    # checkpoint) must recover BIT-EXACT vs an uninterrupted run, and
    # no recovery may take longer than the bound (threshold is a max
    # recovery time in seconds, not a min speedup). XLA_FLAGS is set
    # by the bench itself before importing jax — its own interpreter,
    # like the distributed gate.
    "chaos-dist": dict(
        script="chaos_dist_bench.py",
        args=["--max-recovery-s", "20.0",
              "--out", "BENCH_chaos_dist.json"],
        env={}, out="BENCH_chaos_dist.json", threshold=20.0,
        summarize=_summ_chaos_dist),
    # neighbor-only ppermute exchange vs the all-gather baseline across
    # the device-count curve (nd = 1, 2, 4, 8) in the exchange-bound
    # fine-block regime: p2p per-device exchanged bytes/step must stay
    # flat in the device count while the gather curve grows, and p2p
    # must not lose to gather on the full mesh (threshold is the max
    # allowed p2p/gather per-step time ratio). Parity against the
    # single-device engine is asserted per cell before any timing.
    # XLA_FLAGS is set by the bench itself — own interpreter, like the
    # distributed gate.
    "dist-scaling": dict(
        script="distributed_bench.py",
        args=["--scaling", "--max-slowdown", "1.05",
              "--out", "BENCH_dist_scaling.json"],
        env={}, out="BENCH_dist_scaling.json", threshold=1.05,
        summarize=_summ_dist_scaling),
    # the autotuner's bounded ci-preset sweep: every candidate parity-
    # gated against the heuristic engine, tuned-best geomean speedup
    # vs the static heuristic (same interleaved timing matrix) must
    # reach 1.0x — the baseline is in the candidate space, so below
    # 1.0 means the sweep or the timer is broken, not "slow hardware".
    # SQUEEZE_TUNING=off pins the baseline to the true heuristic (the
    # shipped table must not leak into the thing it is compared to).
    "tuner": dict(
        script="tuner_bench.py",
        args=["--preset", "ci", "--min-speedup", "1.0",
              "--out", "BENCH_tuner.json"],
        env={"SQUEEZE_TUNING": "off"}, out="BENCH_tuner.json",
        threshold=1.0, summarize=_summ_tuner),
}


def run_gate(name: str) -> int:
    gate = GATES[name]
    env = dict(os.environ, **gate["env"])
    # the benches import repro; make a bare `python benchmarks/ci_gates
    # .py` work outside CI too
    root = str(BENCH_DIR.parent / "src")
    env["PYTHONPATH"] = (root + os.pathsep + env["PYTHONPATH"]
                         if env.get("PYTHONPATH") else root)
    # capture the gate subprocess's registry in the artifact: enable
    # collection (except for the overhead gate, which drives the toggle
    # itself) and dump the snapshot at interpreter exit
    dump = f"telemetry_{name}.jsonl"
    env["SQUEEZE_TELEMETRY_DUMP"] = dump
    if not gate.get("no_telemetry_env"):
        env["SQUEEZE_TELEMETRY"] = "1"
    cmd = [sys.executable, str(BENCH_DIR / gate["script"]), *gate["args"]]
    print(f"[ci_gates] {name}: {' '.join(cmd)}", flush=True)
    rc = subprocess.call(cmd, env=env)
    write_report(name, gate, cmd, rc, dump)
    return rc


def write_report(name: str, gate: dict, cmd, rc: int, dump: str) -> None:
    """gate_report_<name>.json — always written, even on a failed bench
    (the artifact upload runs `if: always()`)."""
    report = {
        "gate": name,
        "command": cmd,
        "exit_status": rc,
        "passed": rc == 0,
        "threshold": gate["threshold"],
        "bench_json": gate["out"],
        # the benches assert parity BEFORE writing their JSON, so a
        # parseable bench JSON means every parity check passed; no JSON
        # means the bench died before or during the sweep
        "parity": "unknown",
        "measured": None,
        "telemetry": None,
    }
    try:
        data = json.loads(pathlib.Path(gate["out"]).read_text())
        report["parity"] = "ok"
        report["measured"] = gate["summarize"](data)
    except FileNotFoundError:
        report["parity"] = "no-bench-json"
    except Exception as e:  # summarizer bug must not mask the bench rc
        report["parity"] = f"report-error: {e}"
    try:
        lines = pathlib.Path(dump).read_text().splitlines()
        # metrics only: span lines can number one per runner.run call
        # and belong in the raw dump, not a readable report
        report["telemetry"] = [
            m for m in (json.loads(x) for x in lines if x.strip())
            if m.get("type") in ("counter", "gauge", "histogram")]
    except FileNotFoundError:
        pass
    path = pathlib.Path(f"gate_report_{name}.json")
    path.write_text(json.dumps(report, indent=2))
    print(f"[ci_gates] wrote {path} (passed={report['passed']})",
          flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--gate", choices=sorted(GATES),
                    help="which perf gate to run")
    ap.add_argument("--list", action="store_true",
                    help="print the gate names (the CI matrix) and exit")
    args = ap.parse_args()
    if args.list:
        print("\n".join(sorted(GATES)))
        return
    if not args.gate:
        ap.error("--gate is required (or --list)")
    raise SystemExit(run_gate(args.gate))


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
