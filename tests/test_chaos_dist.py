"""The DISTRIBUTED chaos matrix on a real 8-shard mesh: every
shard-level fault class (per-shard exception, stalled fused launch,
device loss + elastic 8->4 reshard, corrupted halo band, damaged
sharded checkpoint) must recover bit-exact vs an uninterrupted
single-device run. The matrix itself lives in
benchmarks/chaos_dist_bench.py — the same script the CI chaos-dist
gate runs — so the scenarios, parity assertions and recovery-time
arithmetic are written once.

Runs in a subprocess so --xla_force_host_platform_device_count never
leaks into this process (smoke tests must see 1 device); the in-process
single-device recovery tests are in test_elastic_dist.py."""
import os
import pathlib
import subprocess
import sys


def test_chaos_matrix_recovers_on_8_device_mesh(tmp_path):
    repo = pathlib.Path(__file__).resolve().parents[1]
    script = repo / "benchmarks" / "chaos_dist_bench.py"
    env = dict(os.environ)
    env["PYTHONPATH"] = str(repo / "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    out_json = tmp_path / "BENCH_chaos_dist.json"
    out = subprocess.run(
        [sys.executable, str(script), "--smoke",
         "--max-recovery-s", "60", "--out", str(out_json)],
        env=env, capture_output=True, text=True, timeout=80)
    assert out.returncode == 0, (
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}")
    assert "CHAOS_DIST_OK" in out.stdout
    assert out_json.exists()  # the recovery-metrics artifact
