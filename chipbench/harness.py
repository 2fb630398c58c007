"""One run of one cell: set-up, the measured window, the comparison with
the plain reference, and the result line.

The system under test is entered only through its public surface:
`FractalService` (asyncio `submit`) with a `BatchedRunner`,
`ServiceConfig`, `SimRequest`, the fractal and workload registries,
`BatchedRunner.compile_run` (to name the step program) and the
`repro.obs` registry. Requests leave the engine kind and fusion depth
at the program's defaults.
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import json
import math
import os
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

from chipbench import cells, loadgen

PEAKS = "chipbench/peaks.json"

#: how long past the close of the window a run waits for the answers
#: still in flight; one that has not come back by then never comes
LATE_S = 60.0

#: how long a run waits for the service to stop after a window whose
#: answers did not all come back
STOP_S = 20.0

#: JAX's persistent compilation cache, inside the checkout
CACHE_DIR = ".jax_cache"


def use_compile_cache(root) -> str:
    """Keep JAX's persistent compilation cache, and so the program's, in
    `<root>/.jax_cache`; called by every entry point before JAX is
    imported. A `JAX_COMPILATION_CACHE_DIR` set outside is overridden,
    not honoured: the cache's path is part of its key, so it has to stay
    fixed, and two checkouts measured side by side must not share one
    cache, as a variable set for both would make them."""
    path = str(Path(root) / CACHE_DIR)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    return path


class DeviceError(RuntimeError):
    """No accelerator of a kind the peaks table knows, or too few."""


def log(*parts):
    print(*parts, flush=True)


# ------------------------------------------------------------- device
def device_info(jax, chips: int, peaks: dict) -> dict:
    """Platform, kind and count of the devices JAX sees; raises
    `DeviceError` unless they are TPUs listed in `peaks` and at least
    `chips` of them."""
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise DeviceError(f"no TPU: JAX's devices are {dev.platform!r}")
    if len(devs) < chips:
        raise DeviceError(f"the cell needs {chips} chips, JAX sees "
                          f"{len(devs)}")
    if dev.device_kind not in peaks:
        raise DeviceError(f"device kind {dev.device_kind!r} is not in "
                          f"{PEAKS}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def peak_bytes(jax) -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


# -------------------------------------------------------- the program
class Program:
    """The system under test, configured from a configuration file."""

    def __init__(self, cfg: dict):
        from repro.core.fractals import get_fractal
        from repro.serving import ServiceConfig
        from repro.workloads.rules import get_workload
        self.cfg = cfg
        self.frac = get_fractal(cfg["fractal"]["name"])
        self.workload = get_workload(cfg["workload"]["name"])
        self.service_config = ServiceConfig(**cfg.get("service", {}))

    def request(self, p: loadgen.Planned, deadline_s: float, rid: str):
        from repro.serving import SimRequest
        return SimRequest(frac=self.frac, r=p.r, m=int(self.cfg["m"]),
                          steps=p.steps, workload=self.workload,
                          seed=p.seed, snapshot_every=p.snapshot_every,
                          deadline_s=deadline_s, rid=rid)

    def live_cells(self, r: int) -> int:
        return len(self.cfg["fractal"]["positions"]) ** r


@dataclasses.dataclass
class Sent:
    """One request of the window, as its client saw it."""

    planned: loadgen.Planned
    client: int
    done: float = math.nan      # monotonic time its answer came back
    result: object = None

    @property
    def ok(self) -> bool:
        return self.result is not None and self.result.status == "ok"

    @property
    def r(self) -> int:
        return self.planned.r

    @property
    def steps(self) -> int:
        return self.planned.steps


class Sample:
    """The requests the reference checks: one completed request of each
    client, drawn uniformly from the seed while the window runs (a
    reservoir of one per client, each with its own generator, so the
    draw does not hang on how clients interleave). The answers of the
    others are dropped as they come back, so the host holds one state
    per client, not every state of the window."""

    def __init__(self, seed: int, clients: int):
        self.rng = [np.random.default_rng([int(seed) % 2 ** 64, 11, c])
                    for c in range(clients)]
        self.seen = [0] * clients
        self.kept = [None] * clients

    def offer(self, s: Sent) -> None:
        c = s.client
        drop = s
        if s.ok:
            self.seen[c] += 1
            if self.rng[c].random() * self.seen[c] < 1.0:
                drop, self.kept[c] = self.kept[c], s
        if drop is not None and drop.result is not None:
            drop.result = dataclasses.replace(drop.result, state=None,
                                              snapshots=[])

    def requests(self) -> list:
        return [s for s in self.kept if s is not None]


async def _submit(svc, req):
    from repro.serving.types import AdmissionError, SimResult
    try:
        return await svc.submit(req)
    except AdmissionError as e:
        return SimResult(rid=req.rid, status="rejected", error=e.reason)


# ------------------------------------------------------------ set-up
async def warm_up(svc, prog: Program, traffic: dict, jax,
                  t_start: float) -> dict:
    """Compile and run, through the service, every (depth, batch size)
    the mix can launch. Returns {depth: step program name}."""
    sizes = loadgen.warm_sizes(traffic, prog.service_config.max_batch)
    names = {}
    for r in loadgen.depths(traffic):
        p = loadgen.Planned(0, r, 1, 0, 0)
        req = prog.request(p, 600.0, f"warm-{r}")
        spec = req.bucket
        eng = svc.runner.engine_for(spec, frac=prog.frac,
                                    workload=prog.workload)
        shape = jax.eval_shape(eng.init_random, 0)
        compiled = svc.runner.compile_run(
            spec, jax.ShapeDtypeStruct((sizes[-1],) + shape.shape,
                                       shape.dtype),
            donate=True, frac=prog.frac, workload=prog.workload)
        names[r] = re.search(r"HloModule\s+([^\s,]+)",
                             compiled.as_text()).group(1)
        p_tiles = (eng.layout.macro_tiles(spec.fusion_k, p=spec.macro_p)[0]
                   if spec.is_mxu else None)
        log(f"[spec] r={r} kind={spec.kind} k={spec.fusion_k} P={p_tiles} "
            f"step_program={names[r]} spec={spec}")
        for b in sizes:
            reqs = [prog.request(loadgen.Planned(j, r, 1, 0, j), 600.0,
                                 f"warm-{r}-{b}-{j}") for j in range(b)]
            res = await asyncio.gather(*(_submit(svc, q) for q in reqs))
            bad = [x.status for x in res if x.status != "ok"]
            if bad:
                raise RuntimeError(f"warm-up r={r} batch={b}: {bad}")
            log(f"[setup] r={r} batch={b} warm at "
                f"{time.monotonic() - t_start:.3f}s")
    return names


# ------------------------------------------------------------- window
async def closed_loop(svc, prog, traffic, seed, seconds):
    """Each client sends its next request when its last one is back,
    until `seconds` have passed; the window ends when the last request
    in flight is back, or `LATE_S` after the close for requests that
    never come back. Returns (t0, t1, every request, the sample, whether
    some never came back)."""
    clock = time.perf_counter
    gens = loadgen.closed_requests(traffic, seed)
    deadline = float(traffic["deadline_s"])
    sample = Sample(seed, len(gens))
    t0 = clock()
    sent = []

    async def client(c, gen):
        while clock() - t0 < seconds:
            p = next(gen)
            s = Sent(p, c)
            sent.append(s)
            s.result = await _submit(svc, prog.request(p, deadline,
                                                       f"w{p.idx}"))
            s.done = clock()
            sample.offer(s)

    tasks = [asyncio.ensure_future(client(c, g))
             for c, g in enumerate(gens)]
    _, stalled = await asyncio.wait(tasks, timeout=seconds + LATE_S)
    for t in stalled:
        t.cancel()
    return t0, clock(), sent, sample.requests(), bool(stalled)


# ------------------------------------------------------ end to end
def end_to_end(cell, prog, sent, t0, t1, setup_s, peak) -> dict:
    out = {}
    names = {m["name"]: m for m in cell.end_to_end}
    if "setup_s" in names:
        out["setup_s"] = setup_s
    if "cell_updates_per_s" in names:
        work = sum(prog.live_cells(s.r) * s.steps for s in sent if s.ok)
        out["cell_updates_per_s"] = work / (t1 - t0)
    if "peak_hbm_GB" in names:
        out["peak_hbm_GB"] = peak / 1e9
    return {k: {"value": v, "unit": names[k]["unit"]} for k, v in out.items()}


# -------------------------------------------------------- correctness
def unanswered(sent) -> dict:
    return {"value": sum(1 for s in sent if s.result is None), "limit": 0,
            "rule": "<="}


def check(cell, sent, sample) -> dict:
    """Compare the sample's requests (`Sample`) with the plain reference,
    every final state and every snapshot. Returns {number: {"value",
    "limit", "rule"}}."""
    from chipbench.reference import domain, rule_for
    rule = rule_for(cell.config)
    frac = domain.Fractal.from_config(cell.config)
    errored = sum(1 for s in sent if s.result is not None
                  and s.result.status == "failed")
    worst = 0.0
    plans = {}
    m = int(cell.config["m"])
    t = time.perf_counter()
    for s in sample:
        p = s.planned
        key = (p.r, p.steps)
        if key not in plans:
            plans[key] = domain.plan_tiles(frac, p.r, m, p.steps)
        states = dict(s.result.snapshots)
        states[p.steps] = s.result.state
        worst = max(worst, domain.compare_states(plans[key], rule, p.seed,
                                                 states))
    log(f"[check] compared {len(sample)} requests "
        f"({sum(len(s.result.snapshots) for s in sample)} snapshots) in "
        f"{time.perf_counter() - t:.3f}s against the plain reference")
    return {
        rule.number: {"value": worst,
                      "limit": float(cell.config["check_limit"]),
                      "rule": "<="},
        "errored_requests": {"value": errored, "limit": 0, "rule": "<="},
        "unanswered_requests": unanswered(sent),
        "requests_compared": {"value": len(sample), "limit": 1,
                              "rule": ">="},
    }


def passes(numbers: dict) -> bool:
    for n in numbers.values():
        v, lim = n["value"], n["limit"]
        if not (v <= lim if n["rule"] == "<=" else v >= lim):
            return False
    return True


# -------------------------------------------------------------- trace
def start_trace(jax):
    from jax.profiler import ProfileOptions
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    where = tempfile.mkdtemp(prefix="chipbench-trace-")
    jax.profiler.start_trace(where, profiler_options=opts)
    return where


def reduce_trace(where, step_programs) -> dict:
    from chipbench import trace_reduce
    files = [os.path.join(d, f) for d, _, fs in os.walk(where) for f in fs
             if f.endswith(".xplane.pb")]
    try:
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under {where}")
        return trace_reduce.reduce(trace_reduce.read_file(files[0]),
                                   step_programs)
    finally:
        shutil.rmtree(where, ignore_errors=True)


# ---------------------------------------------------------------- run
async def serve(cell, prog, seed, seconds, trace, t_start, jax):
    from repro import obs
    from repro.serving import FractalService
    from repro.workloads.runner import BatchedRunner
    svc = FractalService(prog.service_config, runner=BatchedRunner())
    await svc.start()
    log(f"[setup] service started at {time.monotonic() - t_start:.3f}s")
    try:
        programs = await warm_up(svc, prog, cell.traffic, jax, t_start)
        gc.collect()
        setup_s = time.monotonic() - t_start
        log(f"[setup] setup_s={setup_s:.3f}")
        where = None
        if trace:
            obs.enable()
            obs.reset()
            where = start_trace(jax)
        with jax.profiler.TraceAnnotation("bench.window"):
            t0, t1, sent, sample, stalled = await closed_loop(
                svc, prog, cell.traffic, seed, seconds)
        if trace:
            jax.profiler.stop_trace()
        peak = peak_bytes(jax)
        snapshot = obs.default_registry().snapshot() if trace else {}
    finally:
        try:
            await asyncio.wait_for(svc.stop(), STOP_S)
        except asyncio.TimeoutError:
            log(f"[window] the service did not stop within {STOP_S}s")
    return SimpleNamespace(sent=sent, t0=t0, t1=t1, sample=sample,
                           stalled=stalled, peak=peak,
                           setup_s=setup_s, programs=programs, where=where,
                           obs=snapshot)


def run_cell(cell, seed: int, seconds: float, trace: bool, t_start: float,
             need_tpu: bool = True) -> dict:
    if cell.traffic.get("loop") != "closed":
        raise cells.CellError(f"{cell.name}: the harness drives closed "
                              f"loops only, the mix says "
                              f"{cell.traffic.get('loop')!r}")
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    peaks = cells.load_json(cell.root / PEAKS)
    if need_tpu:
        device = device_info(jax, cell.chips, peaks)
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(jax.devices())}
    log(f"[device] platform={device['platform']} kind={device['kind']} "
        f"count={device['count']} jax={jax.__version__}")
    prog = Program(cell.config)
    run = asyncio.run(serve(cell, prog, seed, seconds, trace, t_start, jax))
    sent = run.sent
    log(f"[window] {run.t1 - run.t0:.3f}s, {len(sent)} requests, "
        f"{sum(s.ok for s in sent)} ok, peak_bytes_in_use={run.peak}")
    log("[window] answers back at " + " ".join(
        f"{s.done - run.t0:.3f}" for s in sorted(sent, key=lambda s: s.done)))
    if run.stalled:
        # a request that never came back fails the run; the reference,
        # on a device the program may still hold, is not run
        log(f"[window] {unanswered(sent)['value']} requests did not come "
            f"back within {LATE_S}s of the close")
        numbers = {"unanswered_requests": unanswered(sent)}
    else:
        # the reference runs with the program's device state freed
        jax.clear_caches()
        gc.collect()
        numbers = check(cell, sent, run.sample)
    correct = passes(numbers)
    device["memory_peak_bytes"] = run.peak
    out = {"correct": correct, "attempted": len(sent),
           "failed": sum(not s.ok for s in sent)}
    if trace:
        red = reduce_trace(run.where, set(run.programs.values()))
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        ctx = SimpleNamespace(
            trace=red, obs=run.obs, requests=sent, cell=cell,
            peaks=peaks[device["kind"]] if device["kind"] in peaks else None,
            live_cells=prog.live_cells,
            channels=int(cell.config["channels"]),
            itemsize=np.dtype(cell.config["state_dtype"]).itemsize)
        metrics = {}
        for m in cell.per_layer:
            v = cell.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        out["metrics"] = metrics
        out["breakdown"] = {"device_ops": red["top_ops"],
                            "idle_gaps": red["idle_gaps"]}
        log(f"[trace] modules {json.dumps(red['module_s'])}")
    else:
        out["metrics"] = end_to_end(cell, prog, sent, run.t0, run.t1,
                                    run.setup_s, run.peak)
    out["device"] = device
    out["check"] = numbers
    return out


def main(args, root, t_start) -> int:
    try:
        bench = cells.load_benchmark(root)
        cell = cells.resolve(root, bench, args.workload)
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       t_start)
    except (DeviceError, cells.CellError) as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 2
    for name, n in out["check"].items():
        print(f"[check] {name} = {n['value']!r} (limit {n['rule']} "
              f"{n['limit']!r})", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    if out["check"]["unanswered_requests"]["value"]:
        # the service's worker threads may never end; leave without
        # waiting for them
        sys.stderr.flush()
        os._exit(0)
    return 0
