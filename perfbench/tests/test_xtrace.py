"""The reduction from a profiler trace to busy time, module time and the
breakdown: on hand-made planes, and on a trace recorded on a TPU v5e."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from perfbench.lib import xtrace

FIXTURE = Path(__file__).with_name("data") / "v5e_fixture.xplane.pb"


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur),
              stats=list(stats.items()))


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines.items()])


def fake_trace():
    # window 0..1000 ns; device busy 100-300 and 250-400 (overlap) and
    # 900-1100 (half outside); host span bench.write covers 400-800
    dev = plane("/device:TPU:0",
                **{"XLA Modules": [ev("jit_step(1)", 100, 300),
                                   ev("jit_step(1)", 900, 200)],
                   "XLA Ops": [ev("fusion.1", 100, 200, hlo_module="jit_step(1)"),
                               ev("fusion.2", 250, 150, hlo_module="jit_step(1)"),
                               ev("copy.3", 900, 200, hlo_module="jit_step(1)")]})
    host = plane("/host:CPU",
                 python=[ev("bench.window", 0, 1000), ev("bench.write", 400, 400),
                         ev("other", 0, 50)])
    return [host, dev]


def test_busy_is_the_union_inside_the_window():
    s = xtrace.reduce_profile(fake_trace())
    assert s.window_s == pytest.approx(1e-6)
    assert s.busy_s == pytest.approx(400e-9)       # 100-400 and 900-1000
    assert s.idle_pct == pytest.approx(60.0)
    assert s.devices == 1


def test_modules_and_ops_are_summed_by_name():
    s = xtrace.reduce_profile(fake_trace())
    assert s.module_seconds("step") == (pytest.approx(400e-9), 2)
    # fusion.2 starts inside fusion.1: each instant counts once, for the
    # operation that started last
    assert s.op_s["jit_step:fusion.1"] == pytest.approx(150e-9)
    assert s.op_s["jit_step:fusion.2"] == pytest.approx(150e-9)
    assert s.op_s["jit_step:copy.3"] == pytest.approx(100e-9)
    assert sum(s.op_s.values()) == pytest.approx(s.busy_s)


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    s = xtrace.reduce_profile(fake_trace())
    # gaps 0-100 and 400-900; bench.write covers most of the second, which
    # goes to it whole
    assert s.idle_by_host["bench.write"] == pytest.approx(500e-9)
    assert s.idle_by_host["host:no bench span"] == pytest.approx(100e-9)
    b = s.breakdown()
    assert b["idle_gaps"][0][0] == "bench.write"
    assert len(b["device_ops"]) == 3


def test_a_trace_without_a_window_or_a_device_is_refused():
    host = plane("/host:CPU", python=[ev("bench.window", 0, 10)])
    with pytest.raises(ValueError):
        xtrace.reduce_profile([host])
    with pytest.raises(ValueError):
        xtrace.reduce_profile([fake_trace()[1]])


def test_recorded_v5e_trace():
    """Recorded by ``make_trace_fixture.py`` on one TPU v5e: two calls of a
    jitted program with a 50 ms host span between them."""
    s = xtrace.reduce_file(FIXTURE)
    assert s.devices == 1
    assert 0 < s.busy_s < s.window_s
    # the device's clock sits about a millisecond off the host's, so the
    # first call may fall just outside the window
    _, calls = s.module_seconds("work")
    assert calls in (1, 2)
    assert s.idle_by_host.get("bench.host", 0) > 0.04
    ops = dict(s.breakdown()["device_ops"])
    assert ops and all(name.startswith("jit_work:") for name in ops)
    assert all(" = " not in name for name in ops)
