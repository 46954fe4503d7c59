"""Each cell end to end at a tiny size on the CPU: the harness's look for a
chip is skipped, the rest of a run is driven. A sound run is correct; a run
with a fault planted in the timed path is not."""
import json
import os
import subprocess
import sys
import time

import pytest

from perfbench.lib import harness
from perfbench.tests import cellfiles

BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def run(workload, seconds=3.0, seed=2**31 + 77):
    return harness.run_cell(BENCH, workload, seed, seconds, False,
                            time.perf_counter(), require_tpu=False,
                            overrides=cellfiles.overrides(workload))


@pytest.mark.parametrize("workload", CELLS)
def test_a_sound_run_is_correct(workload):
    r = run(workload)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["window_compiles"] == 0
    names = {m["name"] for m in harness.e2e_metrics(BENCH, workload)}
    assert set(r["metrics"]) == names and "setup_s" in names
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "checks"


def test_the_job_takes_the_volume_again_after_its_last_tile():
    """Eight tiles, three workers: the window outlasts a pass."""
    small = {"config": {"volume_shape": [256, 256, 32]},
             "traffic": cellfiles.load("em.detect")["traffic"]}
    r = harness.run_cell(BENCH, "em.detect", 5, 3.0, False, time.perf_counter(),
                         require_tpu=False, overrides=small)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 8


@pytest.mark.parametrize("workload,fault", cellfiles.pairs(BENCH))
def test_a_fault_in_the_timed_path_is_not_correct(workload, fault):
    with cellfiles.fault(BENCH, workload, fault)():
        r = run(workload)
    assert not r["correct"], r["checks"]


def test_every_cell_lists_the_faults_it_can_have():
    """Each cell's file names at least one fault, and every name is
    found."""
    for cell in CELLS:
        names = cellfiles.load(cell)["faults"]
        assert names, cell
        for name in names:
            assert callable(cellfiles.fault(BENCH, cell, name))


def test_a_cell_without_its_test_file_names_the_file():
    with pytest.raises(FileNotFoundError) as e:
        cellfiles.load("no.such.cell")
    assert str(cellfiles.path("no.such.cell")) in str(e.value)


def test_no_chip_is_refused():
    with pytest.raises(harness.NoChip):
        harness.device_info(1, require_tpu=True)
    with pytest.raises(harness.NoChip):
        harness.device_info(4, require_tpu=False)


def test_the_command_exits_without_a_result_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(harness.BENCH_DIR / "run.py"),
                        "--workload", "em.detect", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 3 and p.stdout.strip() == ""


def test_benchmark_json_names_files_that_exist():
    for c in BENCH["configs"]:
        assert (harness.ROOT / c["file"]).is_file()
    for w in BENCH["workloads"]:
        cell, _, _, traffic = harness.find_cell(BENCH, w["name"])
        assert (harness.BENCH_DIR / "kinds" / f"{traffic['kind']}.py").is_file()
        for m in harness.layer_metrics(BENCH, w["name"]):
            assert callable(harness.load_reader(m["name"]))
    json.dumps(BENCH)
