"""A whole run on the CPU at tiny sizes, device check aside: sound, it
is correct; with the timed path broken underneath (a step that returns
its state unchanged, half of the batch left unstepped, an answer
altered where it is produced), `correct` comes out false. And the
controls (the reference in bfloat16, or with the hole mask skipped)
read above each configuration's limit. A one-chip cell has no exchange
between chips to leave out."""
import pytest

from chipbench import cells, control, harness
from chipbench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.build(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def shared_runner():
    """One runner for every run of this module, so that each batch shape
    compiles once."""
    from repro.workloads.runner import BatchedRunner
    return BatchedRunner()


def _run(root, name, shared_runner, monkeypatch, seconds=0.5):
    import repro.workloads.runner as runner_mod
    monkeypatch.setattr(runner_mod, "BatchedRunner", lambda: shared_runner)
    cell = cells.resolve(root, cells.load_benchmark(root), name)
    return harness.run_cell(cell, 2 ** 31 + 77, seconds, False, 0.0,
                            need_tpu=False)


def _unchanged(real):
    def run(self, spec, states, steps, **kw):
        return states
    return run


def _half_left_out(real):
    def run(self, spec, states, steps, **kw):
        kw["donate"] = False
        out = real(self, spec, states=states, steps=steps, **kw)
        keep = states.shape[0] // 2
        return out.at[keep:].set(states[keep:])
    return run


FAULTS = {"state_unchanged": _unchanged, "half_batch_left_out":
          _half_left_out}


@pytest.mark.parametrize("name", ["tiny-life-batch", "tiny-gs-batch"])
def test_sound_run_is_correct(root, name, shared_runner, monkeypatch):
    out = _run(root, name, shared_runner, monkeypatch)
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", ["tiny-life-batch", "tiny-gs-batch"])
def test_broken_step_is_not_correct(root, name, fault, shared_runner,
                                    monkeypatch):
    from repro.workloads.runner import BatchedRunner
    monkeypatch.setattr(BatchedRunner, "run",
                        FAULTS[fault](BatchedRunner.run))
    out = _run(root, name, shared_runner, monkeypatch)
    assert not out["correct"], out["check"]


@pytest.mark.parametrize("name", ["tiny-life-batch", "tiny-gs-batch"])
def test_altered_answer_is_not_correct(root, name, shared_runner,
                                       monkeypatch):
    from repro.serving.service import FractalService
    real = FractalService._host_state

    def altered(self, req, state):
        host = real(self, req, state).copy()
        host.reshape(-1)[host.size // 2] += 1
        return host

    monkeypatch.setattr(FractalService, "_host_state", altered)
    out = _run(root, name, shared_runner, monkeypatch)
    assert not out["correct"], out["check"]


@pytest.mark.parametrize("cfg, r, steps", [(tiny.LIFE, 6, 8),
                                           (tiny.GRAY_SCOTT, 3, 6)])
def test_control_reads_above_the_limit(cfg, r, steps):
    got = control.control_readings(cfg, r, steps, [1, 2, 2 ** 31 - 2])
    assert min(got) > cfg["check_limit"], got


def test_answer_that_never_comes_is_not_correct(root, shared_runner,
                                                monkeypatch):
    import time

    from repro.workloads.runner import BatchedRunner
    real = BatchedRunner.run
    calls = []

    def stuck(self, spec, states, steps, **kw):
        calls.append(steps)
        if len(calls) > 2:          # past the warm-up: the window's step
            time.sleep(3.0)
        return real(self, spec, states=states, steps=steps, **kw)

    monkeypatch.setattr(BatchedRunner, "run", stuck)
    monkeypatch.setattr(harness, "LATE_S", 0.5)
    monkeypatch.setattr(harness, "STOP_S", 0.5)
    out = _run(root, "tiny-life-batch", shared_runner, monkeypatch,
               seconds=0.2)
    assert not out["correct"], out["check"]
    assert out["check"]["unanswered_requests"]["value"] > 0
    assert list(out)[-1] == "check"
