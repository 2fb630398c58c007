"""Smoke run of the Squeeze service on one TPU chip (or one 4-chip host).

    python chip_smoke.py               # one chip: reference, main, float PDE
    python chip_smoke.py --four-chips  # the sharded domain on 4 chips only

Drives the main path — ``FractalService`` -> ``BatchedRunner`` ->
``make_engine(spec)`` — with seeded random states and checks what comes
out against the repository's own references. Every phase prints its own
lines; the last line of standard output is one JSON object naming the
device. Exits non-zero, with no result line, when JAX finds no TPU or
when any phase fails. Rates printed here are smoke numbers from a
handful of requests, not benchmark results.

Phases (one chip):
  * device     — platform, device kind and count, jax/jaxlib/libtpu;
  * reference  — Life and Gray-Scott on Sierpinski r=14, m=4, 64 steps
                 through the service (kind 'block') against ``BBEngine``,
                 the plain expanded-space engine (2^28 cells) on the chip:
                 Life bit-exact, Gray-Scott within ``GS_ATOL``;
  * main       — 4 Life requests on Sierpinski r=17, m=4 (2^34 expanded
                 cells, 3^17 compact), 256 steps, snapshots every 64,
                 through the default kind ('block') and again through
                 'pallas-fused' and 'pallas-mxu': final states bit-equal;
  * float PDE  — Gray-Scott on the carpet r=8, m=2, 'pallas-mxu' against
                 'block' within ``GS_ATOL``.
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

#: Gray-Scott fields are O(1) float32; the Pallas kernels and the
#: expanded reference evaluate the same update with other operation
#: orders (MXU contractions, fused windows), so they agree to rounding
GS_ATOL = 1e-5

#: per-request deadline: long enough for every cold compile of a phase
DEADLINE_S = 900.0

#: the one ServiceConfig change: a 64-step segment of 4 requests at r=17
#: takes ~10 s on a v5e, which the default 10 s hang threshold reads as
#: a hang (measured in the first smoke run on the chip)
HANG_THRESHOLD_S = 300.0

#: (r, m, steps) of each phase — the sizes in the module docstring
REFERENCE = (14, 4, 64)
MAIN = (17, 4, 256)
PDE = (8, 2, 64)
FOUR_CHIP = (17, 4, 64)
SNAPSHOT_EVERY = 64


def log(*parts):
    print(*parts, flush=True)


def device_phase(expect: int):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: jax.devices()[0].platform is "
                         f"{dev.platform!r}")
    count = len(jax.devices())
    if count < expect:
        raise SystemExit(f"need {expect} TPU devices, found {count}")

    def version(pkg):
        try:
            return importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            return "absent"

    log(f"[device] platform=tpu kind={dev.device_kind} count={count} "
        f"jax={jax.__version__} jaxlib={version('jaxlib')} "
        f"libtpu={version('libtpu')}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": count}


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def serve(tag, reqs, runner=None, pallas=False):
    """Serve ``reqs`` (one bucket) through a fresh FractalService after
    compiling the bucket's batched run ahead; print compile/steady
    seconds, the resolved spec and a smoke rate. Returns the results."""
    from repro.serving import FractalService, ServiceConfig
    from repro.workloads.runner import BatchedRunner

    runner = runner or BatchedRunner()
    rep = reqs[0]
    spec = rep.bucket
    eng = runner.engine_for(spec, frac=rep.frac, workload=rep.workload)
    shape = (len(reqs),) + tuple(jax.eval_shape(eng.init_random, 0).shape)
    t0 = time.perf_counter()
    compiled = runner.compile_run(
        spec, jax.ShapeDtypeStruct(shape, rep.workload.dtype), donate=True,
        frac=rep.frac, workload=rep.workload)
    compile_s = time.perf_counter() - t0
    if pallas and "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{tag}: compiled {spec.kind} step has no "
                             "tpu_custom_call (kernel not compiled)")
    p = None
    if spec.is_mxu:
        p = eng.layout.macro_tiles(spec.fusion_k, p=spec.macro_p)[0]
    t0 = time.perf_counter()
    res = FractalService(ServiceConfig(hang_threshold_s=HANG_THRESHOLD_S),
                         runner=runner).serve(reqs)
    steady_s = time.perf_counter() - t0
    bad = [(r.rid, r.status, r.error) for r in res if r.status != "ok"]
    if bad:
        raise AssertionError(f"{tag}: requests did not end ok: {bad}")
    cells = rep.frac.volume(rep.r) * rep.workload.n_channels
    rate = cells * sum(q.steps for q in reqs) / steady_s / 1e6
    log(f"[{tag}] kind={spec.kind} k={spec.fusion_k} P={p} "
        f"batch={len(reqs)} compile_s={compile_s:.3f} "
        f"steady_s={steady_s:.3f} smoke_Mcell_updates_per_s={rate:.1f} "
        f"peak_bytes_in_use={peak_bytes()} spec={spec}")
    return res


def requests(frac, r, m, wl, kind, seeds, steps, snapshot_every=0):
    from repro.serving import SimRequest
    return [SimRequest(frac=frac, r=r, m=m, steps=steps, workload=wl,
                       kind=kind, seed=s, snapshot_every=snapshot_every,
                       deadline_s=DEADLINE_S, rid=f"{kind}-{wl.name}-{s}")
            for s in seeds]


def reference_phase():
    from repro.core.baselines import BBEngine
    from repro.core.compact import BlockLayout
    from repro.core.fractals import SIERPINSKI
    from repro.workloads.rules import GRAY_SCOTT, LIFE

    r, m, steps = REFERENCE
    layout = BlockLayout(SIERPINSKI, r, m)
    for wl in (LIFE, GRAY_SCOTT):
        (res,) = serve(f"reference:{wl.name}",
                       requests(SIERPINSKI, r, m, wl, "block", [7], steps))
        bb = BBEngine(SIERPINSKI, r, wl)
        t0 = time.perf_counter()
        want = bb.run(bb.init_random(7), steps)
        want = np.asarray(jax.device_get(layout.from_expanded(want)))
        bb_s = time.perf_counter() - t0
        got = res.state
        if wl is LIFE:
            ok = np.array_equal(got, want)
            detail = f"mismatches={int(np.count_nonzero(got != want))}"
        else:
            err = float(np.max(np.abs(got - want)))
            ok = err <= GS_ATOL
            detail = f"max_abs_err={err:.3e} atol={GS_ATOL}"
        log(f"[reference:{wl.name}] block vs BBEngine (2^{2 * r} cells) "
            f"r={r} m={m} steps={steps} bb_s={bb_s:.3f} {detail} "
            f"{'ok' if ok else 'FAIL'}")
        if not ok:
            raise AssertionError(f"reference {wl.name} mismatch: {detail}")


def main_phase():
    from repro.core.fractals import SIERPINSKI
    from repro.workloads.rules import LIFE

    (r, m, steps), seeds = MAIN, [0, 1, 2, 3]
    finals = {}
    for kind in ("block", "pallas-fused", "pallas-mxu"):
        res = serve(f"main:{kind}",
                    requests(SIERPINSKI, r, m, LIFE, kind, seeds, steps,
                             snapshot_every=SNAPSHOT_EVERY),
                    pallas=kind != "block")
        snaps = [len(x.snapshots) for x in res]
        if snaps != [(steps - 1) // SNAPSHOT_EVERY] * len(seeds):
            raise AssertionError(f"main {kind}: snapshots {snaps}")
        finals[kind] = [x.state for x in res]
        del res
        if kind != "block":
            same = all(np.array_equal(a, b) for a, b in
                       zip(finals[kind], finals["block"]))
            log(f"[main:{kind}] final states == block (bit-exact, "
                f"{len(seeds)} requests): {'ok' if same else 'FAIL'}")
            if not same:
                raise AssertionError(f"main {kind} != block")
            del finals[kind]
    live = [int(np.count_nonzero(s)) for s in finals["block"]]
    log(f"[main] r={r} m={m} steps={steps} live cells at the end: {live}")


def float_pde_phase():
    from repro.core.fractals import CARPET
    from repro.workloads.rules import GRAY_SCOTT

    r, m, steps = PDE
    out = {}
    for kind in ("block", "pallas-mxu"):
        (res,) = serve(f"pde:{kind}",
                       requests(CARPET, r, m, GRAY_SCOTT, kind, [3],
                                steps), pallas=kind != "block")
        out[kind] = res.state
    err = float(np.max(np.abs(out["pallas-mxu"] - out["block"])))
    ok = err <= GS_ATOL and np.all(np.isfinite(out["block"]))
    log(f"[pde] gray-scott carpet r={r} m={m} steps={steps} "
        f"pallas-mxu vs block max_abs_err={err:.3e} atol={GS_ATOL} "
        f"{'ok' if ok else 'FAIL'}")
    if not ok:
        raise AssertionError(f"gray-scott pallas-mxu vs block: {err}")


def four_chip_phase():
    from jax.sharding import Mesh

    from repro.core.compact import BlockLayout
    from repro.core.fractals import SIERPINSKI
    from repro.core.stencil import SqueezeBlockEngine, make_engine
    from repro.tuning.spec import EngineSpec
    from repro.workloads.rules import LIFE

    (r, m, steps), seed = FOUR_CHIP, 5
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    t0 = time.perf_counter()
    single = SqueezeBlockEngine(BlockLayout(SIERPINSKI, r, m), LIFE)
    want = np.asarray(jax.device_get(
        single.run(single.init_random(seed), steps)))
    log(f"[4chip] single-chip block on device 0: "
        f"{time.perf_counter() - t0:.3f}s")
    for kind in ("dist-block", "dist-mxu"):
        spec = EngineSpec.from_args(kind, SIERPINSKI, r, m, LIFE,
                                    mesh=mesh, exchange="auto")
        t0 = time.perf_counter()
        eng = make_engine(spec, mesh=mesh)
        state = eng.init_random(seed)
        jax.block_until_ready(state)
        build_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = eng.run(state, steps)
        got = np.asarray(jax.device_get(eng.to_dense(out)))
        run_s = time.perf_counter() - t0
        same = np.array_equal(got, want)
        log(f"[4chip:{kind}] exchange={eng.exchange_mode} k={eng.fusion_k} "
            f"host_build_s={build_s:.3f} run_s(incl. compile)={run_s:.3f} "
            f"exchange_stats={eng.exchange_stats()} "
            f"bit-exact vs single-chip block: {'ok' if same else 'FAIL'}")
        if not same:
            raise AssertionError(f"{kind} != single-chip block")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded 4-chip phase")
    args = ap.parse_args(argv)
    device = device_phase(4 if args.four_chips else 1)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.utils.compile_cache import enable_compile_cache
    log(f"[device] compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase()
    else:
        reference_phase()
        main_phase()
        float_pde_phase()
    log(f"[done] all phases ok in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
