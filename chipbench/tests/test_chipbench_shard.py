"""The four-chip cell's parts on the CPU: a tiny sharded cell run whole
through the harness on four placeholder devices comes out correct, and
`step_hbm_share.shard` counts device 0's shard from the program's gauge,
and reads nothing where the program reports none."""
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import cells

REPO = Path(__file__).resolve().parents[2]
PEAKS = cells.load_json(REPO / "chipbench" / "peaks.json")


def _ctx(gauges, step_s=2.0, done=3):
    q = SimpleNamespace(ok=True, r=20, steps=32)
    return SimpleNamespace(
        trace={"step_s": step_s}, requests=[q] * done + [
            SimpleNamespace(ok=False, r=20, steps=32)],
        obs={"gauges": gauges}, channels=1, itemsize=1,
        peaks=PEAKS["TPU v5 lite"])


def _gauge(shard, value):
    return {"type": "gauge", "name": "dist.shard_live_cells",
            "labels": {"shard": shard}, "value": value}


def test_shard_reader_counts_device_0():
    read = cells.load_reader(REPO, "step_hbm_share.shard")
    live = 871_000_000
    ctx = _ctx([_gauge("1", 1), _gauge("0", live), _gauge("2", 5)])
    want = 100 * 3 * 2 * live * 32 / (2.0 * 819e9)
    assert read(ctx) == pytest.approx(want)


@pytest.mark.parametrize("ctx", [
    _ctx([]),                           # a program without the gauge
    _ctx([_gauge("1", 7)]),             # no shard 0
    _ctx([_gauge("0", 7)], step_s=0.0),
    _ctx([_gauge("0", 7)], done=0),
], ids=["no-gauge", "no-shard-0", "no-step-time", "none-done"])
def test_shard_reader_reads_nothing_without_its_inputs(ctx):
    assert cells.load_reader(REPO, "step_hbm_share.shard")(ctx) is None


def test_shard_reader_without_trace():
    ctx = _ctx([_gauge("0", 7)])
    ctx.trace = None
    assert cells.load_reader(REPO, "step_hbm_share.shard")(ctx) is None


def test_tiny_four_chip_cell_is_correct():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO), str(REPO / "src"), env.get("PYTHONPATH", "")])
    out = subprocess.run(
        [sys.executable, str(Path(__file__).parent / "_shard_cell_check.py")],
        env=env, capture_output=True, text=True, timeout=200, cwd=REPO)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "SHARD_CELL_OK" in out.stdout
