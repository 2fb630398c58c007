"""The continuous-batching service on its happy paths: admission
control, bucketed batching onto one compiled engine, continuous joins,
deadlines, snapshots, donation parity, circuit breaker and the serve.*
telemetry surface. The failure paths live in tests/test_chaos.py."""
import asyncio
import time

import numpy as np
import pytest

from repro import obs
from repro.core import fractals
from repro.core.stencil import make_engine
from repro.runtime.fault import Fault, FaultInjector
from repro.serving import (AdmissionError, CircuitBreaker, FractalService,
                           ServiceConfig, SimRequest, SimResult)
from repro.workloads import HEAT, LIFE, BatchedRunner

FRAC = fractals.SIERPINSKI


def _cfg(**kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("backoff_base_s", 0.01)
    kw.setdefault("hang_threshold_s", 5.0)
    return ServiceConfig(**kw)


def _reqs(n, steps=12, snapshot_every=0, prefix="t", **kw):
    return [SimRequest(frac=FRAC, r=4, steps=steps, m=1, seed=s,
                       snapshot_every=snapshot_every,
                       rid=f"{prefix}-{s}", **kw)
            for s in range(n)]


def _ref_states(n, steps, wl=LIFE):
    eng = make_engine("block", FRAC, 4, 1, workload=wl)
    return [np.asarray(eng.run(eng.init_random(s), steps))
            for s in range(n)]


# ------------------------------------------------------------ happy path
def test_serve_matches_direct_engine_run():
    svc = FractalService(_cfg())
    res = svc.serve(_reqs(3, steps=12, prefix="direct"))
    refs = _ref_states(3, 12)
    for i, r in enumerate(res):
        assert r.ok and r.steps_done == 12
        np.testing.assert_array_equal(refs[i], r.state)


def test_requests_share_one_compiled_engine():
    runner = BatchedRunner()
    svc = FractalService(_cfg(max_batch=8), runner=runner)
    res = svc.serve(_reqs(6, steps=8, prefix="share"))
    assert all(r.ok for r in res)
    assert runner.stats.builds == 1  # six requests, one bucket, one build


def test_mixed_buckets_route_to_distinct_engines():
    runner = BatchedRunner()
    svc = FractalService(_cfg(), runner=runner)
    reqs = _reqs(2, steps=6, prefix="life") + [
        SimRequest(frac=FRAC, r=4, steps=6, m=1, workload=HEAT,
                   seed=s, rid=f"heat-{s}") for s in range(2)]
    res = svc.serve(reqs)
    assert all(r.ok for r in res)
    assert runner.stats.builds == 2  # one per (workload) bucket
    heat_ref = make_engine("block", FRAC, 4, 1, workload=HEAT)
    ref = np.asarray(heat_ref.run(heat_ref.init_random(0), 6))
    np.testing.assert_allclose(ref, res[2].state, rtol=1e-6, atol=1e-6)


def test_snapshots_at_cadence_and_bit_exact():
    svc = FractalService(_cfg())
    res = svc.serve(_reqs(2, steps=12, snapshot_every=4, prefix="snap"))
    eng = make_engine("block", FRAC, 4, 1, workload=LIFE)
    for seed, r in enumerate(res):
        assert [s for s, _ in r.snapshots] == [4, 8]
        state = eng.init_random(seed)
        for _, snap in r.snapshots:
            state = eng.run(state, 4)  # advance to the next boundary
            np.testing.assert_array_equal(np.asarray(state), snap)


def test_heterogeneous_step_counts_in_one_bucket():
    svc = FractalService(_cfg(max_batch=8))
    reqs = [SimRequest(frac=FRAC, r=4, steps=st, m=1, seed=i,
                       rid=f"het-{i}")
            for i, st in enumerate((5, 9, 16))]
    res = svc.serve(reqs)
    eng = make_engine("block", FRAC, 4, 1, workload=LIFE)
    for i, (st, r) in enumerate(zip((5, 9, 16), res)):
        assert r.ok and r.steps_done == st
        ref = np.asarray(eng.run(eng.init_random(i), st))
        np.testing.assert_array_equal(ref, r.state)


def test_continuous_join_mid_flight():
    """A request submitted while its bucket is already running joins at
    a segment boundary instead of waiting for a full drain."""
    async def go():
        svc = FractalService(_cfg(max_batch=8, max_segment_steps=2))
        await svc.start()
        try:
            first = asyncio.ensure_future(
                svc.submit(SimRequest(frac=FRAC, r=4, steps=40, m=1,
                                      seed=0, rid="join-0")))
            await asyncio.sleep(0.05)  # let the bucket start
            late = await svc.submit(SimRequest(frac=FRAC, r=4, steps=8,
                                               m=1, seed=1, rid="join-1"))
            return await first, late
        finally:
            await svc.stop()
    r0, r1 = asyncio.run(go())
    assert r0.ok and r0.steps_done == 40
    assert r1.ok and r1.steps_done == 8
    eng = make_engine("block", FRAC, 4, 1, workload=LIFE)
    np.testing.assert_array_equal(
        np.asarray(eng.run(eng.init_random(1), 8)), r1.state)


def test_stop_returns_with_a_finished_task_still_registered():
    """A bucket task that has returned, with its done callback still
    queued, is in ``_tasks`` when stop() starts: stop() must yield to
    the loop so the callback removes it, not spin in a gather that
    completes without yielding."""
    async def go():
        svc = FractalService(_cfg())
        await svc.start()
        bucket = _reqs(1)[0].bucket
        svc._running.add(bucket)
        # nothing is pending, so the bucket coroutine returns at once
        task = asyncio.get_running_loop().create_task(
            svc._run_bucket(bucket))
        svc._tasks.add(task)
        task.add_done_callback(svc._on_task_done(bucket))
        await asyncio.sleep(0)  # the task runs; its callback is queued
        assert task.done() and task in svc._tasks
        await svc.stop()
        assert not svc._tasks and not svc._running
    asyncio.run(go())


# ------------------------------------------------------------- admission
def test_queue_full_rejects_with_retry_after():
    async def go():
        svc = FractalService(_cfg(max_queue=2))
        await svc.start()
        try:
            svc._queued = 2  # saturate the queue deterministically
            with pytest.raises(AdmissionError) as ei:
                await svc.submit(SimRequest(frac=FRAC, r=4, steps=4,
                                            m=1, seed=0, rid="qf-0"))
            assert ei.value.reason == "queue_full"
            assert ei.value.retry_after_s > 0
        finally:
            svc._queued = 0
            await svc.stop()
    asyncio.run(go())


def test_deadline_times_out_long_request():
    svc = FractalService(_cfg(max_segment_steps=1))
    # a first request builds and compiles the bucket's engine, so that the
    # deadline runs against steps, not against a cold build on a busy host
    assert svc.serve(_reqs(1, steps=1, prefix="dl-warm"))[0].ok
    res = svc.serve([SimRequest(frac=FRAC, r=4, steps=100000, m=1, seed=0,
                                deadline_s=0.2, rid="dl-0")])
    assert res[0].status == "timeout"
    assert 0 < res[0].steps_done < 100000


def test_submit_before_start_raises():
    svc = FractalService(_cfg())
    with pytest.raises(RuntimeError):
        asyncio.run(svc.submit(_reqs(1)[0]))


# -------------------------------------------------------- circuit breaker
def test_circuit_breaker_state_machine():
    t = [0.0]
    br = CircuitBreaker(threshold=2, cooldown_s=1.0, clock=lambda: t[0])
    assert br.state == "closed" and br.allow()
    br.record_failure()
    assert br.state == "closed"
    br.record_failure()
    assert br.state == "open" and not br.allow()
    assert br.retry_after() == pytest.approx(1.0)
    t[0] = 1.5
    assert br.state == "half-open" and br.allow()  # the probe
    br.record_failure()  # half-open probe fails -> reopen immediately
    assert br.state == "open"
    t[0] = 3.0
    assert br.allow()
    br.record_success()
    assert br.state == "closed"


def test_breaker_sheds_load_after_sustained_failure():
    inj = FaultInjector([Fault(kind="exception", at_segment=i)
                         for i in range(8)])
    svc = FractalService(
        _cfg(max_retries=1, breaker_threshold=2,
             breaker_cooldown_s=30.0), injector=inj)
    res = svc.serve(_reqs(1, steps=8, prefix="brk"))
    assert res[0].status == "failed"
    assert svc.breaker.state == "open"
    # breaker open -> admission rejects with retry-after, not collapse
    res2 = svc.serve(_reqs(1, steps=8, prefix="brk2"))
    assert res2[0].status == "rejected"
    assert res2[0].error == "breaker_open"
    assert res2[0].retry_after_s > 0


# ------------------------------------------------------------- telemetry
def test_serve_metrics_emitted():
    with obs.enabled_scope(True) as reg:
        obs.reset()
        svc = FractalService(_cfg())
        res = svc.serve(_reqs(3, steps=8, snapshot_every=4,
                              prefix="met"))
        assert all(r.ok for r in res)
        assert reg.counter("serve.admitted", kind="block").value == 3
        assert reg.counter("serve.completed", kind="block").value == 3
        assert reg.counter("serve.joins", kind="block").value == 3
        assert reg.counter("serve.batches", kind="block").value >= 1
        assert reg.counter("serve.segments", kind="block").value >= 2
        lat = reg.histogram("serve.latency_seconds", kind="block",
                            status="ok")
        assert lat.count == 3
        assert reg.gauge("serve.queue_depth").value == 0


def test_result_latency_accounting():
    svc = FractalService(_cfg())
    t0 = time.monotonic()
    res = svc.serve(_reqs(1, steps=8, prefix="lat"))
    wall = time.monotonic() - t0
    assert 0 < res[0].latency_s <= wall + 0.1
    assert 0 <= res[0].queue_wait_s <= res[0].latency_s


# ------------------------------------------------------------- misc types
def test_request_validation():
    with pytest.raises(ValueError):
        SimRequest(frac=FRAC, r=4, steps=0)
    with pytest.raises(ValueError):
        SimRequest(frac=FRAC, r=4, steps=4, snapshot_every=-1)


def test_result_ok_property():
    assert SimResult(rid="x", status="ok").ok
    assert not SimResult(rid="x", status="failed").ok
