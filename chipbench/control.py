"""The readings that a cell's correctness limit is set from:

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 [--program]

For each seed, the control (the plain reference in the configuration's
deliberately wrong variant: a lower precision, or a stated guarantee
broken) is compared with the plain reference by the cell's own number,
at the cell's own sizes; that is the upper reading. With `--program`,
the program serves one batch of the cell's requests per group of seeds
(as many rows as the mix has clients) through `FractalService`, and
each result is compared the same way; the largest is the lower reading. The
benchmark's own runs do not run this. Last line: one JSON object.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def control_readings(cfg: dict, r: int, steps: int, seeds, control=None):
    """[control vs reference] per seed, at depth r after `steps`."""
    from chipbench.reference import domain, rule_for
    rule = rule_for(cfg)
    mod = sys.modules[type(rule).__module__]
    control = control or mod.CONTROLS[0]
    plan = domain.plan_tiles(domain.Fractal.from_config(cfg), r,
                             int(cfg["m"]), steps)
    return [domain.compare_control(plan, rule, s, steps, control)
            for s in seeds]


def program_readings(cell, seeds, need_tpu=True):
    """[program vs reference] per seed: the cell's requests served in
    batches of the mix's clients through FractalService."""
    import jax

    from chipbench import cells, harness, loadgen
    from chipbench.reference import domain, rule_for
    peaks = cells.load_json(cell.root / harness.PEAKS)
    if need_tpu:
        harness.device_info(jax, cell.chips, peaks)
    prog = harness.Program(cell.config)
    tr = cell.traffic
    r, steps = max(loadgen.depths(tr)), max(v for v, _ in
                                            loadgen.choices(tr["steps"]))
    batch = int(tr["clients"])
    results = []

    async def go():
        from repro.serving import FractalService
        from repro.workloads.runner import BatchedRunner
        svc = FractalService(prog.service_config, runner=BatchedRunner())
        await svc.start()
        try:
            for i in range(0, len(seeds), batch):
                group = seeds[i:i + batch]
                reqs = [prog.request(loadgen.Planned(j, r, steps, 0, s),
                                     float(tr["deadline_s"]), f"c{i}-{j}")
                        for j, s in enumerate(group)]
                res = await asyncio.gather(*(svc.submit(q) for q in reqs))
                results.extend(zip(group, res))
        finally:
            await svc.stop()

    asyncio.run(go())
    jax.clear_caches()
    rule = rule_for(cell.config)
    plan = domain.plan_tiles(domain.Fractal.from_config(cell.config), r,
                             int(cell.config["m"]), steps)
    out = []
    for s, res in results:
        if res.status != "ok":
            out.append(float("inf"))
            continue
        out.append(domain.compare_states(plan, rule, s,
                                         {steps: res.state}))
    return r, steps, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import cells, harness
    harness.use_compile_cache(ROOT)
    cell = cells.resolve(ROOT, cells.load_benchmark(ROOT), args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    out = {"workload": args.workload}
    if args.program:
        t = time.perf_counter()
        r, steps, got = program_readings(cell, seeds)
        out.update(r=r, steps=steps, program=got,
                   program_s=time.perf_counter() - t)
    else:
        from chipbench import loadgen
        r = max(loadgen.depths(cell.traffic))
        steps = max(v for v, _ in loadgen.choices(cell.traffic["steps"]))
    t = time.perf_counter()
    out["control"] = control_readings(cell.config, r, steps, seeds)
    out["control_s"] = time.perf_counter() - t
    out["limit"] = cell.config["check_limit"]
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
