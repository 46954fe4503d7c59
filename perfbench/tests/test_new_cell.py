"""A new cell arrives as new files plus list entries in ``BENCHMARK.json``.

In a copy of the benchmark, a third cell gets a configuration, a traffic
mix, a CPU test file and an architecture (SmolLM's code under a new name,
with a fault of its own), all new files; the unchanged harness runs it
sound, with its faults and with its control. Without its test file, its
tests fail naming that file."""
import json
import os
import shutil
import subprocess
import sys

from perfbench.lib import harness

CELL, CONFIG, TRAFFIC = "copycat.train", "copycat-135m", "copycat-b8-s2048"

ARCH = '''"""SmolLM's architecture renamed, with a fault of its own."""
from ..lib import faults
from ..refs import copycat as reference  # noqa: F401
from .llama import (LAYERS, SCOPES, model_config, param_shapes,  # noqa: F401
                    train_flops_per_token)

FAULTS = {"copycat_half_batch": faults.train_half_batch}
'''

REFERENCE = '''"""The Llama reference under another name."""
from .llama import logits, train  # noqa: F401
'''


def make_checkout(root):
    """The benchmark's files, the program by a link, and the new cell."""
    shutil.copytree(harness.BENCH_DIR, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    (root / "src").symlink_to(harness.ROOT / "src")
    bench = harness.load_benchmark()
    smollm = harness.load_json(harness.BENCH_DIR / "configs"
                               / "smollm-135m.json")
    traffic = harness.load_json(harness.BENCH_DIR / "traffic"
                                / "train-b8-s2048.json")
    sizes = harness.load_json(harness.BENCH_DIR / "tests" / "cells"
                              / "smollm.train.json")
    new = {
        f"perfbench/configs/{CONFIG}.json": dict(
            smollm, name=CONFIG, reference="perfbench/refs/copycat.py"),
        f"perfbench/traffic/{TRAFFIC}.json": traffic,
        f"perfbench/tests/cells/{CELL}.json": dict(
            sizes, faults=["train_unchanged", "copycat_half_batch"],
            fault_module="perfbench.archs.copycat"),
    }
    for path, data in new.items():
        (root / path).write_text(json.dumps(data, indent=1))
    (root / "perfbench/archs/copycat.py").write_text(ARCH)
    (root / "perfbench/refs/copycat.py").write_text(REFERENCE)
    bench["configs"].append({
        "name": CONFIG, "source": "https://huggingface.co/HuggingFaceTB/"
        "SmolLM-135M", "file": f"perfbench/configs/{CONFIG}.json",
        "reduced": [], "why": "SmolLM under another name"})
    bench["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1,
        "why": "the training cell again, through new files"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "smollm.train" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))


TESTS = ["perfbench/tests/test_cells.py", "perfbench/tests/test_control.py",
         "perfbench/tests/test_hlo_scopes.py"]


def pytest_in(root):
    """The cell's tests, run in ``root``'s copy of the benchmark."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "--tb=short", *TESTS, "-k", CELL],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)


def test_a_cell_of_new_files_runs_through_the_unchanged_harness(tmp_path):
    root = tmp_path / "checkout"
    make_checkout(root)
    for path in ("kinds", "lib", "metrics", "run.py"):
        a, b = harness.BENCH_DIR / path, root / "perfbench" / path
        assert (a.is_file() and a.read_bytes() == b.read_bytes()) or all(
            f.read_bytes() == (b / f.name).read_bytes()
            for f in a.glob("*.py"))
    p = pytest_in(root)
    out = p.stdout + p.stderr
    # sound, two faults, the control, and its step's layers
    assert p.returncode == 0 and "5 passed" in out, out[-4000:]

    cell_file = root / "perfbench" / "tests" / "cells" / f"{CELL}.json"
    cell_file.unlink()
    p = pytest_in(root)
    out = p.stdout + p.stderr
    assert p.returncode != 0 and "4 failed" in out, out[-4000:]
    assert str(cell_file) in out, out[-4000:]
