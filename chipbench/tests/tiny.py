"""A benchmark root with tiny cells, built in a temporary directory from
data files only: the shipped peaks table and metric readers, and small
configurations and mixes that run on the CPU in seconds, with every
shipped per-layer reader in use."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]       # chipbench/

LIFE = {
    "fractal": {"name": "sierpinski", "s": 2,
                "positions": [[0, 0], [0, 1], [1, 1]]},
    "workload": {"name": "life", "rule": "life", "born": [3],
                 "survive": [2, 3]},
    "m": 2, "state_dtype": "uint8", "channels": 1,
    "service": {"max_batch": 2, "hang_threshold_s": 60.0},
    "check_limit": 0,
}
GRAY_SCOTT = {
    "fractal": {"name": "carpet", "s": 3,
                "positions": [[0, 0], [1, 0], [2, 0], [0, 1], [2, 1],
                              [0, 2], [1, 2], [2, 2]]},
    "workload": {"name": "gray-scott", "rule": "gray-scott", "du": 1.0,
                 "dv": 0.5, "feed": 0.055, "kill": 0.062, "w_orth": 0.2,
                 "w_diag": 0.05, "dt": 1.0},
    "m": 1, "state_dtype": "float32", "channels": 2,
    "service": {"max_batch": 2, "hang_threshold_s": 60.0},
    "check_limit": 1e-5,
}
TRAFFIC = {
    "tiny-closed-life": {"loop": "closed", "clients": 2, "r": {"7": 1},
                         "steps": {"8": 1}, "snapshot_every": {"4": 1},
                         "deadline_s": 120.0},
    "tiny-closed-gs": {"loop": "closed", "clients": 2, "r": {"4": 1},
                       "steps": {"6": 1}, "snapshot_every": {"0": 1},
                       "deadline_s": 120.0},
}
CELLS = [("tiny-life-batch", "tiny-life", "tiny-closed-life"),
         ("tiny-gs-batch", "tiny-gs", "tiny-closed-gs")]


def build(root: Path) -> Path:
    """Write a benchmark root under `root` and return it."""
    root = Path(root)
    (root / "chipbench" / "configs").mkdir(parents=True)
    (root / "chipbench" / "traffic").mkdir()
    shutil.copy(HERE / "peaks.json", root / "chipbench" / "peaks.json")
    shutil.copytree(HERE / "metrics", root / "chipbench" / "metrics")
    for name, cfg in (("tiny-life", LIFE), ("tiny-gs", GRAY_SCOTT)):
        (root / "chipbench" / "configs" / f"{name}.json").write_text(
            json.dumps(cfg))
    for name, mix in TRAFFIC.items():
        (root / "chipbench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    both = ["tiny-life-batch", "tiny-gs-batch"]
    bench = {
        "command": ["python3", "chipbench/run.py"], "paths": ["chipbench"],
        "run_seconds": 1,
        "configs": [
            {"name": n, "source": "test", "file":
             f"chipbench/configs/{n}.json", "reduced": [], "why": "test"}
            for n in ("tiny-life", "tiny-gs")],
        "workloads": [
            {"name": c, "config": cfg, "traffic": mix, "chips": 1,
             "why": "test"} for c, cfg, mix in CELLS],
        "end_to_end": [
            _metric("setup_s", "s"),
            _metric("cell_updates_per_s", "cell-updates/s", both),
            _metric("peak_hbm_GB", "GB", both)],
        "per_layer": [
            _metric(n, "%", both, "cell_updates_per_s")
            for n in ("device_idle_share.batch", "step_hbm_share")],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _metric(name, unit, workloads=None, moves=None):
    m = {"name": name, "unit": unit, "better": "lower"}
    if workloads is not None:
        m["workloads"] = workloads
    if moves is not None:
        m["moves"] = moves
    return m
