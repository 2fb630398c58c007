"""The chaos matrix: every injected fault class — in-step exception,
watchdog-detected hang, SIGTERM preemption, corrupted/truncated
checkpoint — must recover AUTOMATICALLY with bit-exact CA results vs an
uninterrupted run. Also covers the FaultInjector harness itself and the
recovery telemetry the CI chaos job uploads."""
import signal
import threading

import numpy as np
import pytest

from repro import obs
from repro.checkpoint.manager import CheckpointManager
from repro.core import fractals
from repro.core.stencil import make_engine
from repro.runtime.fault import (Fault, FaultInjector, InjectedFault,
                                 PreemptionHandler, damage_checkpoint)
from repro.serving import FractalService, ServiceConfig, SimRequest
from repro.workloads import LIFE

FRAC = fractals.SIERPINSKI
STEPS = 24
N = 3


@pytest.fixture(scope="module")
def refs():
    """Uninterrupted ground truth, one per seed."""
    eng = make_engine("block", FRAC, 4, 1, workload=LIFE)
    return [np.asarray(eng.run(eng.init_random(s), STEPS))
            for s in range(N)]


def _cfg(tmp_path, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("backoff_base_s", 0.01)
    kw.setdefault("backoff_cap_s", 0.05)
    kw.setdefault("hang_threshold_s", 1.0)
    kw.setdefault("ckpt_dir", str(tmp_path / "ckpts"))
    return ServiceConfig(**kw)


def _reqs(prefix):
    return [SimRequest(frac=FRAC, r=4, steps=STEPS, m=1, seed=s,
                       snapshot_every=8, rid=f"{prefix}-{s}")
            for s in range(N)]


def _assert_bit_exact(res, refs):
    for i, r in enumerate(res):
        assert r.status == "ok", (r.rid, r.status, r.error)
        assert r.steps_done == STEPS
        np.testing.assert_array_equal(refs[i], r.state)


# --------------------------------------------------------- fault classes
def test_in_step_exception_recovers_bit_exact(tmp_path, refs):
    inj = FaultInjector([Fault(kind="exception", at_segment=1)])
    svc = FractalService(_cfg(tmp_path), injector=inj)
    res = svc.serve(_reqs("exc"))
    assert inj.all_fired()
    _assert_bit_exact(res, refs)
    assert all(r.recoveries >= 1 for r in res)
    assert all(r.retries >= 1 for r in res)


def test_watchdog_hang_restarts_engine_bit_exact(tmp_path, refs):
    inj = FaultInjector([Fault(kind="stall", at_segment=1, stall_s=2.5)])
    svc = FractalService(_cfg(tmp_path), injector=inj)
    res = svc.serve(_reqs("hang"))
    assert inj.all_fired()
    _assert_bit_exact(res, refs)
    assert svc.watchdog.hangs == 1  # detected, killed, restarted


def test_sigterm_preemption_drains_then_resumes_bit_exact(tmp_path,
                                                          refs):
    cfg = _cfg(tmp_path)
    inj = FaultInjector(
        [Fault(kind="preempt", at_segment=2, via_signal=True)])
    svc = FractalService(cfg, injector=inj)
    res = svc.serve(_reqs("pre"), install_signals=True)
    # drained: checkpointed mid-run, nothing lost, nothing wedged
    assert all(r.status == "preempted" for r in res)
    assert all(0 < r.steps_done < STEPS for r in res)
    # the trap was uninstalled on stop (satellite: handler restore)
    assert signal.getsignal(signal.SIGTERM) == signal.SIG_DFL
    # resume: same rids on a fresh service pick up the checkpoints
    svc2 = FractalService(_cfg(tmp_path))
    res2 = svc2.serve(_reqs("pre"))
    _assert_bit_exact(res2, refs)
    assert all(r.steps_done == STEPS for r in res2)


def test_programmatic_preemption_without_signals(tmp_path, refs):
    inj = FaultInjector([Fault(kind="preempt", at_segment=2)])
    svc = FractalService(_cfg(tmp_path), injector=inj)
    res = svc.serve(_reqs("ppre"))  # injector uses handler.request()
    assert all(r.status == "preempted" for r in res)
    res2 = FractalService(_cfg(tmp_path)).serve(_reqs("ppre"))
    _assert_bit_exact(res2, refs)


@pytest.mark.parametrize("mode", ["corrupt", "truncate"])
def test_damaged_checkpoint_falls_back_to_previous_step(tmp_path, refs,
                                                        mode):
    """Damage the newest checkpoint, then crash: recovery must fall
    back to the previous intact step and still finish bit-exact."""
    inj = FaultInjector([Fault(kind=mode, at_segment=1),
                         Fault(kind="exception", at_segment=2)])
    svc = FractalService(_cfg(tmp_path), injector=inj)
    res = svc.serve(_reqs(f"dmg-{mode}"))
    assert inj.all_fired()
    _assert_bit_exact(res, refs)


def test_composed_chaos_run(tmp_path, refs):
    """Everything at once, in sequence: exception, hang, corruption —
    one run survives the full matrix and stays bit-exact."""
    inj = FaultInjector([
        Fault(kind="exception", at_segment=1),
        Fault(kind="stall", at_segment=3, stall_s=2.0),
        Fault(kind="corrupt", at_segment=4),
        Fault(kind="exception", at_segment=5),
    ])
    svc = FractalService(_cfg(tmp_path, max_segment_steps=4),
                         injector=inj)
    res = svc.serve(_reqs("all"))
    assert inj.all_fired()
    _assert_bit_exact(res, refs)


def test_chaos_without_checkpoints_recomputes_from_seed(refs):
    """No durable dir at all: recovery falls back to recompute-from-
    seed and still lands bit-exact (slower, never wrong)."""
    inj = FaultInjector([Fault(kind="exception", at_segment=1)])
    svc = FractalService(
        ServiceConfig(max_batch=4, backoff_base_s=0.01,
                      hang_threshold_s=5.0, ckpt_dir=None),
        injector=inj)
    res = svc.serve(_reqs("nock"))
    _assert_bit_exact(res, refs)


# ------------------------------------------------------ recovery metrics
def test_recovery_metrics_surface(tmp_path, refs):
    """The counters the CI chaos job uploads: injected == recovered
    arithmetic is checkable from telemetry alone."""
    with obs.enabled_scope(True) as reg:
        obs.reset()
        inj = FaultInjector([Fault(kind="exception", at_segment=1),
                             Fault(kind="stall", at_segment=3,
                                   stall_s=2.0)])
        svc = FractalService(_cfg(tmp_path), injector=inj)
        res = svc.serve(_reqs("met"))
        _assert_bit_exact(res, refs)
        assert reg.counter("chaos.injected", kind="exception").value == 1
        assert reg.counter("chaos.injected", kind="stall").value == 1
        assert reg.counter("serve.retries", kind="block").value >= 1
        assert reg.counter("serve.restarts", kind="block").value == 1
        assert reg.counter("serve.recoveries", kind="block").value == 2
        rec = reg.histogram("serve.recovery_seconds", kind="block")
        assert rec.count == 2


# ------------------------------------------------------- injector harness
def test_injector_fires_each_fault_once():
    inj = FaultInjector([Fault(kind="exception", at_segment=0)])
    with pytest.raises(InjectedFault):
        inj.in_step(0)
    inj.in_step(1)  # already fired: no second raise
    assert inj.all_fired()
    assert inj.log == [(0, "exception", "raise")]


def test_injector_preempt_requires_route():
    inj = FaultInjector([Fault(kind="preempt", at_segment=0)])
    with pytest.raises(RuntimeError):
        inj.at_boundary(0)
    h = PreemptionHandler(install=False)
    inj2 = FaultInjector([Fault(kind="preempt", at_segment=0)],
                         handler=h)
    inj2.at_boundary(0)
    assert h.requested


def test_injector_claim_is_atomic_under_hammer():
    """8 threads race every hook call: each scheduled fault must fire
    EXACTLY once (the claim — scan, mark fired, log, count — is atomic
    under the injector's lock; without it two threads could both raise
    the same fault, double-counting chaos.injected)."""
    n_faults, n_threads = 50, 8
    with obs.enabled_scope(True) as reg:
        obs.reset()
        inj = FaultInjector([Fault(kind="exception", at_segment=s)
                             for s in range(n_faults)])
        barrier = threading.Barrier(n_threads)
        raises = [0] * n_threads

        def worker(i):
            # segments advance in lockstep so exactly one fault is due
            # per round — the contention is WITHIN each round, where
            # all 8 threads hit the same due fault at once
            for seg in range(n_faults):
                barrier.wait()
                try:
                    inj.in_step(seg)
                except InjectedFault:
                    raises[i] += 1

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sum(raises) == n_faults  # once each, never twice
        assert len(inj.log) == n_faults
        assert inj.all_fired()
        c = reg.counter("chaos.injected", kind="exception")
        assert c.value == n_faults


def test_dist_engine_rows_survive_chaos_bit_exact(tmp_path, refs):
    """dist-* rows route through the same recovery state machine:
    crash + damaged checkpoint on a dist-block request must restore
    from the sharded checkpoint (mesh-independent dense state) and
    finish bit-exact vs the single-device block reference."""
    inj = FaultInjector([Fault(kind="exception", at_segment=1),
                         Fault(kind="corrupt", at_segment=2),
                         Fault(kind="exception", at_segment=3)])
    svc = FractalService(_cfg(tmp_path), injector=inj)
    reqs = [SimRequest(frac=FRAC, r=4, steps=STEPS, m=1, seed=s,
                       kind="dist-block", snapshot_every=8,
                       rid=f"dchaos-{s}")
            for s in range(N)]
    res = svc.serve(reqs)
    assert inj.all_fired()
    _assert_bit_exact(res, refs)
    assert all(r.recoveries >= 1 for r in res)


def test_fault_kind_validated():
    with pytest.raises(ValueError):
        Fault(kind="meteor")


def test_damage_checkpoint_is_detectable(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"a": np.arange(16.0)})
    n = damage_checkpoint(str(tmp_path / "step_00000001"),
                          mode="corrupt")
    assert n == 1
    from repro.checkpoint.manager import CheckpointCorruptError
    with pytest.raises(CheckpointCorruptError):
        mgr.restore({"a": np.zeros(16)})
