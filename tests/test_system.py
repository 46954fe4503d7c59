"""End-to-end behaviour tests for the full system."""
import subprocess
import sys
import os

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))


def test_train_driver_loss_decreases(tmp_path):
    """Full stack: pipeline -> train step -> ckpt -> recovery, via CLI."""
    from repro.launch.train import main
    out = main(["--arch", "smollm-135m", "--smoke", "--steps", "16",
                "--seq-len", "64", "--batch", "4", "--lr", "3e-3",
                "--ckpt-dir", str(tmp_path), "--ckpt-every", "5",
                "--inject-failure-at", "8"])
    losses = out["losses"]
    assert losses[-1] < losses[0]
    # a checkpoint was committed and recovery replayed steps
    assert any(d.startswith("step_") for d in os.listdir(tmp_path))


def test_train_with_microbatching_and_compression(tmp_path):
    from repro.launch.train import main
    out = main(["--arch", "granite-moe-1b-a400m", "--smoke",
                "--steps", "10", "--seq-len", "32", "--batch", "4",
                "--microbatches", "2", "--grad-compression", "bf16"])
    assert out["losses"][-1] < out["losses"][0] * 1.1


def test_serve_driver_runs():
    from repro.launch.serve import main
    out = main(["--arch", "smollm-135m", "--smoke", "--batch", "2",
                "--prompt-len", "6", "--gen", "6"])
    assert out["tokens"].shape == (2, 6)
    assert out["logits"].shape == (2, 1, 256)
    assert np.isfinite(np.asarray(out["logits"], dtype=np.float32)).all()


@pytest.mark.slow
def test_dryrun_cell_subprocess():
    """The real multi-pod dry-run path (512 placeholder devices) in a
    subprocess so the 512-device jax init never leaks into this process."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "granite-moe-1b-a400m", "--shape", "decode_32k", "--mesh",
         "both"],
        env=ENV, cwd=REPO, capture_output=True, text=True, timeout=1200)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("OK") == 2