"""GPipe pipeline parallelism over the pod axis (subprocess: 4 devices)."""
import os
import pathlib
import subprocess
import sys


def test_pipeline_matches_sequential():
    script = pathlib.Path(__file__).parent / "_pp_check.py"
    env = dict(os.environ)
    repo = pathlib.Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = str(repo / "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=30)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "PP_OK" in out.stdout
