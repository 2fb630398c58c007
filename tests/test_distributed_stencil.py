"""Multi-device (8 placeholder CPU devices) distributed compact stencil
SMOKE: k-fused strip halo exchange + shard-local kernels vs the
single-device engine on a real 8-shard mesh. The full parity matrix
(workload x k x kind) is in-process in test_distributed_fused.py.

Runs in a subprocess so --xla_force_host_platform_device_count never leaks
into this process (smoke tests must see 1 device)."""
import os
import pathlib
import subprocess
import sys


def test_distributed_engine_matches_single_device():
    script = pathlib.Path(__file__).parent / "_distributed_check.py"
    env = dict(os.environ)
    repo = pathlib.Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = str(repo / "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=170)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "DISTRIBUTED_OK" in out.stdout
