"""The control fails: the reference put in the program's place one
precision below the configuration's (float8 products where the program
computes in bfloat16) reads over a limit of ``correct``, while the program
reads under every limit. At sizes a CPU test run holds; the chip readings
at the cells' own sizes, from which the limits were set, are in PERF.md."""
import pytest

from perfbench.lib import harness
from perfbench.tests import cellfiles

BENCH = harness.load_benchmark()


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_the_control_reads_over_a_limit_and_the_program_under_all(workload):
    _, _, config, traffic = harness.find_cell(BENCH, workload)
    sizes = cellfiles.overrides(workload)
    config = {**config, **sizes["config"]}
    traffic = {**traffic, **sizes["traffic"]}
    driver = harness.load_kind(traffic["kind"]).Cell(config, traffic, 11)
    try:
        driver.setup()
        driver.window(4.0, traced=False)
        driver.release()
        got = driver.calibration(control=True)
    finally:
        driver.close()
    limits = traffic["limits"]
    assert all(v <= limits.get(k, 0) for k, v in got["program"].items()), got
    assert any(v > limits.get(k, 0) for k, v in got["control"].items()), got
