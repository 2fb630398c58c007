"""Subprocess body of `test_chipbench_shard.py`: a tiny four-chip cell
run whole through `harness.run_cell` on four placeholder CPU devices
(the flag must precede any JAX import). A device capacity under one
row's working set makes the program's own `'auto'` rule shard the
domain; the run must come out `correct`."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

from chipbench import cells, harness, loadgen  # noqa: E402
from chipbench.tests import tiny  # noqa: E402

CELL = "tiny-life-4chip"
MIX = {"loop": "closed", "clients": 1, "r": {"8": 1}, "steps": {"7": 1},
       "snapshot_every": {"5": 1}, "deadline_s": 120.0}


def main():
    import jax
    from repro.tuning import spec
    assert jax.device_count() == 4, jax.devices()
    # a row at r=8, m=4 is 3^4 blocks of 16 x 16 u8 cells, 20736 B, and
    # needs 4.25 times that on the block path
    spec.device_capacity = lambda: (50_000, jax.local_device_count())
    root = tiny.build(Path(tempfile.mkdtemp(prefix="chipbench-4chip-")))
    cfg = dict(tiny.LIFE, m=4)
    (root / "chipbench" / "configs" / "tiny-life-m4.json").write_text(
        json.dumps(cfg))
    (root / "chipbench" / "traffic" / "tiny-c1.json").write_text(
        json.dumps(MIX))
    bench = cells.load_benchmark(root)
    bench["configs"].append({"name": "tiny-life-m4", "source": "test",
                             "file": "chipbench/configs/tiny-life-m4.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-life-m4",
                               "traffic": "tiny-c1", "chips": 4,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cells.resolve(root, cells.load_benchmark(root), CELL)
    req = harness.Program(cell.config).request(
        loadgen.Planned(0, 8, 7, 5, 0), 60.0, "probe")
    assert req.bucket.kind == "dist-block", req.bucket
    assert req.bucket.mesh_shape == (4,), req.bucket
    out = harness.run_cell(cell, 2 ** 31 + 5, 1.0, False, 0.0,
                           need_tpu=False)
    print(json.dumps(out), flush=True)
    assert out["correct"], out["check"]
    assert out["check"]["requests_compared"]["value"] >= 1
    assert out["attempted"] >= 1 and out["failed"] == 0
    print("SHARD_CELL_OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
