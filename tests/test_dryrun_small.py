"""Dry-run plumbing on a small mesh (subprocess, 8 devices): build_cell ->
lower -> compile -> roofline extraction for every step kind and family."""
import os
import pathlib
import subprocess
import sys


def test_dryrun_machinery_small_mesh():
    script = pathlib.Path(__file__).parent / "_dryrun_small_check.py"
    env = dict(os.environ)
    repo = pathlib.Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = str(repo / "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=90)
    assert out.returncode == 0, \
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-4000:]}"
    assert "DRYRUN_SMALL_OK" in out.stdout


def test_full_matrix_results_exist_and_pass():
    """The committed full-matrix results (68 cells x 2 meshes) all ok."""
    import json
    path = pathlib.Path(__file__).resolve().parents[1] / \
        "dryrun_results.jsonl"
    if not path.exists():
        import pytest
        pytest.skip(
            "full matrix not yet run (python -m repro.launch.dryrun --all)")
    rows = [json.loads(line) for line in open(path)]
    ok = [r for r in rows if r.get("ok")]
    assert len(ok) >= 68, f"only {len(ok)} passing cells"
    meshes = {r["mesh"] for r in ok}
    assert {"16x16", "2x16x16"} <= meshes
