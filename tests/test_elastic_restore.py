"""Elastic rescale: a 1-device checkpoint restores onto an 8-device mesh
(new shardings via the put() hook) and training continues."""
import os
import pathlib
import subprocess
import sys

from repro import configs
from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import SyntheticMarkov
from repro.launch.train import train
from repro.optim import adamw


def test_one_device_checkpoint_restores_on_eight(tmp_path):
    # phase 1: train 4 steps on THIS (1-device) process and checkpoint
    cfg = configs.get_smoke_config("smollm-135m")
    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=4)
    data = SyntheticMarkov(vocab=cfg.vocab, seq_len=16, global_batch=4,
                           seed=3)
    train(cfg, opt_cfg, data, steps=4, ckpt_dir=str(tmp_path),
          ckpt_every=4, log_every=0)
    assert CheckpointManager(str(tmp_path)).latest_step() == 4

    # phase 2: restore in an 8-device subprocess with mesh shardings
    script = pathlib.Path(__file__).parent / "_elastic_check.py"
    env = dict(os.environ)
    repo = pathlib.Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = str(repo / "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    out = subprocess.run([sys.executable, str(script), str(tmp_path)],
                         env=env, capture_output=True, text=True,
                         timeout=100)
    assert out.returncode == 0, \
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-4000:]}"
    assert "ELASTIC_OK" in out.stdout
