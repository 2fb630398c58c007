"""Distributed chaos matrix on the 8-device CPU mesh: every shard-level
fault class must recover bit-exact, inside the recovery-time bound.

    PYTHONPATH=src python benchmarks/chaos_dist_bench.py \
        [--smoke] [--max-recovery-s 20] [--out BENCH_chaos_dist.json]

Seven scenarios, one per fault class of the shard-aware chaos matrix
(DESIGN.md Sections 9-10), each driving an
:class:`~repro.core.elastic.ElasticDistributedRunner` over the full
8-device mesh with sharded checkpointing enabled:

  * ``shard_exception`` — a shard raises mid-run: backoff + restore;
  * ``shard_stall``     — a fused launch stalls past the launch
    timeout: the launch is abandoned, the engine rebuilt, the run
    restored (the hang class);
  * ``halo_corrupt``    — a shard's tiles come back poisoned: the
    post-launch dead-cell integrity check detects it, restore;
  * ``damaged_ckpt``    — the newest checkpoint is corrupted on disk,
    then a shard raises: the restore falls back to the previous intact
    step (crc32 walk);
  * ``device_loss``     — a shard's device is lost: elastic reshard
    8 -> 4 devices, the newest intact sharded checkpoint restores onto
    the smaller mesh (repadded, operands rebuilt), degraded-mode
    finish;
  * ``strip_drop``      — a neighbor strip send is lost in flight on
    the p2p (``ppermute``) exchange path: the launch aborts, backoff +
    restore relaunches and re-issues the permutes;
  * ``strip_corrupt``   — a received neighbor strip was damaged on the
    wire (p2p path): the dead-cell integrity check catches the
    poisoned band rows, restore.

Every scenario asserts the final state is BIT-EXACT against an
uninterrupted single-device run of the same seed (Life CA), and
records the runner's recovery stats (failures / retries / reshards /
recovery seconds). After the JSON is written the gate fails the
process if any scenario's parity broke or the maximum recovery time
exceeded ``--max-recovery-s`` — the CI chaos-dist gate. Prints
``CHAOS_DIST_OK`` on success (the pytest wrapper greps for it).

The script forces 8 single-threaded host-platform CPU devices; the
flag must precede the jax import, which is why CI runs it in its own
interpreter (same pattern as distributed_bench.py).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

# hard assignment, not setdefault: the suite depends on the 8-device
# mesh existing — a stray inherited XLA_FLAGS must not silently shrink
# it (same pattern as tests/_distributed_check.py)
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8"
                           " --xla_cpu_multi_thread_eigen=false")

import numpy as np  # noqa: E402

from repro.core.compact import BlockLayout  # noqa: E402
from repro.core.elastic import ElasticDistributedRunner  # noqa: E402
from repro.core.fractals import SIERPINSKI  # noqa: E402
from repro.core.stencil import SqueezeBlockEngine  # noqa: E402
from repro.runtime.fault import Fault, FaultInjector  # noqa: E402
from repro.workloads import LIFE  # noqa: E402

SEED = 11


K = 2  # fused launch depth of every scenario


def scenarios(steps, ckpt_every):
    """name -> (faults, runner kwargs, ckpt_every). ``at_segment``
    indexes the runner's launch-attempt counter (k=2 -> launch n
    starts at step 2n); checkpoints land every ``ckpt_every`` steps,
    so the checkpoint at step ``c`` is written when the counter reads
    ``c / k``."""
    # damaged_ckpt needs TWO checkpoints before the crash so the
    # fallback walk has an intact earlier step to land on
    ce = max(K, (steps // 4) // K * K)
    second = 2 * ce // K            # counter at the 2nd checkpoint
    return {
        "shard_exception": (
            [Fault("shard_exception", at_segment=2, shard=1)],
            {}, ckpt_every),
        "shard_stall": (
            [Fault("shard_stall", at_segment=2, stall_s=3.0)],
            dict(launch_timeout_s=1.0, compile_grace_s=120.0),
            ckpt_every),
        "halo_corrupt": (
            [Fault("halo_corrupt", at_segment=3, shard=2)],
            {}, ckpt_every),
        # damage the 2nd checkpoint the moment it lands, then crash a
        # shard: the restore must fall back to the 1st (intact) step
        "damaged_ckpt": (
            [Fault("corrupt", at_segment=second),
             Fault("shard_exception", at_segment=second + 1)],
            {}, ce),
        "device_loss": (
            [Fault("device_loss", at_segment=5, shard=3)],
            {}, ckpt_every),
        # the neighbor-only exchange: pin exchange='p2p' so recovery is
        # proven on the ppermute path specifically (the other scenarios
        # ride the 'auto' default, which also resolves to p2p here)
        "strip_drop": (
            [Fault("strip_drop", at_segment=2, shard=1)],
            dict(exchange="p2p"), ckpt_every),
        "strip_corrupt": (
            [Fault("strip_corrupt", at_segment=3, shard=2,
                   band_rows=K)],
            dict(exchange="p2p"), ckpt_every),
    }


def run_scenario(name, faults, kwargs, layout, ref, steps, ckpt_every):
    inj = FaultInjector(faults)
    with tempfile.TemporaryDirectory() as ckpt_dir:
        runner = ElasticDistributedRunner(
            layout, workload=LIFE, fusion_k=K, ckpt_dir=ckpt_dir,
            ckpt_every=ckpt_every, max_retries=4,
            backoff_base_s=0.02, backoff_cap_s=0.25,
            injector=inj, **kwargs)
        n0 = runner.n_shards
        t0 = time.perf_counter()
        out = runner.run(steps, seed=SEED)
        wall = time.perf_counter() - t0
        final = np.asarray(runner.engine.to_dense(out))
        runner.close()
    exact = bool(np.array_equal(final, ref))
    st = runner.stats
    rec = {
        "scenario": name, "bit_exact": exact, "wall_s": wall,
        "shards_before": n0, "shards_after": runner.n_shards,
        "fired": [list(e) for e in inj.log],
        "pending": len(inj.pending()),
        **{f.name: getattr(st, f.name)
           for f in dataclasses.fields(st)},
        "max_recovery_s": st.max_recovery_s,
    }
    print(f"[chaos-dist] {name}: bit_exact={exact} "
          f"failures={st.failures} retries={st.retries} "
          f"reshards={st.reshards} shards={n0}->{runner.n_shards} "
          f"max_recovery={st.max_recovery_s:.3f}s", flush=True)
    assert inj.all_fired(), f"{name}: unfired faults {inj.pending()}"
    assert st.failures >= 1, f"{name}: no fault was detected"
    if name == "device_loss":
        assert runner.n_shards < n0, "device loss did not reshard"
        assert st.degraded and st.reshards == 1
    if name == "damaged_ckpt":
        assert st.restores >= 1, "no fallback restore happened"
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--r", type=int, default=5)
    ap.add_argument("--m", type=int, default=2)
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--ckpt-every", type=int, default=8)
    ap.add_argument("--smoke", action="store_true",
                    help="shorter run (same scenario coverage)")
    ap.add_argument("--max-recovery-s", type=float, default=None,
                    help="gate: fail if any recovery exceeds this")
    ap.add_argument("--out", default="BENCH_chaos_dist.json")
    args = ap.parse_args()
    steps = 16 if args.smoke else args.steps
    ckpt_every = min(args.ckpt_every, steps // 2)

    import jax
    assert len(jax.devices()) == 8, jax.devices()
    layout = BlockLayout(SIERPINSKI, r=args.r, m=args.m)
    eng = SqueezeBlockEngine(layout, LIFE, fusion_k=K)
    ref = np.asarray(eng.run(eng.init_random(SEED), steps))

    records = []
    for name, (faults, kwargs, ce) in scenarios(steps,
                                                ckpt_every).items():
        records.append(run_scenario(name, faults, kwargs, layout, ref,
                                    steps, ce))

    max_rec = max(r["max_recovery_s"] for r in records)
    all_exact = all(r["bit_exact"] for r in records)
    gate = {"scenarios": len(records), "bit_exact": all_exact,
            "max_recovery_s": max_rec,
            "bound_s": args.max_recovery_s,
            "pass": all_exact and (args.max_recovery_s is None
                                   or max_rec <= args.max_recovery_s)}
    out = pathlib.Path(args.out)
    out.write_text(json.dumps({"records": records, "gate": gate},
                              indent=2))
    print(f"[chaos-dist] wrote {out}; gate={gate}", flush=True)
    if not gate["pass"]:
        print("[chaos-dist] GATE FAILED", flush=True)
        raise SystemExit(1)
    print("CHAOS_DIST_OK", flush=True)


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
