"""The harness: finds a cell's files by name and runs it once.

``BENCHMARK.json`` names each cell's configuration and traffic mix. The
configuration's file is the one its entry names; the traffic mix is
``traffic/<traffic>.json``, whose ``kind`` names the driver
``kinds/<kind>.py``; each per-layer metric is read by
``metrics/<metric name>.py``. Adding a cell, a configuration or a metric
adds files and entries and edits none.

A driver module defines ``Cell(config, traffic, seed)`` with
``setup()``, ``window(seconds, traced) -> Window``, ``release()`` (frees
the program's device state) and ``checks() -> [Check]``, and ``close()``.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Check:
    """One number compared with its limit: correct while value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Window:
    """What a measured window produced."""
    metrics: Dict[str, float]          # end-to-end values by metric name
    attempted: int
    failed: int


def load_json(path: Path) -> Dict:
    return json.loads(Path(path).read_text())


def load_benchmark() -> Dict:
    return load_json(ROOT / "BENCHMARK.json")


def find_cell(bench: Dict, workload: str):
    """(cell entry, configuration entry, configuration, traffic) by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[cell["config"]]
    config = load_json(ROOT / entry["file"])
    traffic = load_json(BENCH_DIR / "traffic" / f"{cell['traffic']}.json")
    return cell, entry, config, traffic


def e2e_metrics(bench: Dict, workload: str) -> List[Dict]:
    """End-to-end metrics this cell reports: those that list it, and those
    without a ``workloads`` key."""
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or workload in m["workloads"]]


def layer_metrics(bench: Dict, workload: str) -> List[Dict]:
    """Per-layer metrics this cell reports: those that list it, and those
    without a ``workloads`` key whose end-to-end metric it reports."""
    e2e = {m["name"] for m in e2e_metrics(bench, workload)}
    return [m for m in bench["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def device_info(chips: int, require_tpu: bool = True) -> Dict:
    import jax
    devices = jax.devices()
    d = devices[0]
    if require_tpu and d.platform != "tpu":
        raise NoChip(f"JAX's first device is {d.platform!r}, not a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX reports "
                     f"{len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(chips: int) -> Optional[int]:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def load_reader(name: str) -> Callable:
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_kind(kind: str):
    return importlib.import_module(f"perfbench.kinds.{kind}")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(bench: Dict, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, require_tpu: bool = True,
             overrides: Optional[Dict] = None) -> Dict:
    """Run one cell once; returns the result object (the driver's line).

    ``overrides`` (tests only) replaces parts of the configuration and the
    traffic: ``{"config": {...}, "traffic": {...}}``.
    """
    from . import roofline, xtrace
    from .clock import CompileClock

    cell, _, config, traffic = find_cell(bench, workload)
    if overrides:
        config = {**config, **overrides.get("config", {})}
        traffic = {**traffic, **overrides.get("traffic", {})}
    device = device_info(cell["chips"], require_tpu)
    log(f"process start to devices {time.perf_counter() - t_start:.2f} s")
    peak = roofline.peaks(device["kind"]) if require_tpu else None
    clock = CompileClock()
    driver = load_kind(traffic["kind"]).Cell(config, traffic, seed)
    try:
        driver.setup()
        setup_s = time.perf_counter() - t_start
        before = clock.snapshot()
        log(f"set-up {setup_s:.2f} s; compile {before.seconds:.2f} s in "
            f"{before.programs} programs (persistent cache: {before.hits} "
            f"hits, {before.written} written)")
        if trace:
            capture = xtrace.Capture()
            with capture:
                with xtrace.span("window"):
                    window = driver.window(seconds, traced=True)
        else:
            window = driver.window(seconds, traced=False)
        in_window = clock.snapshot() - before
        log(f"window {seconds} s: {in_window.programs} compiles inside it")
        mem = memory_peak_bytes(cell["chips"])
        driver.release()
        checks = driver.checks()
    finally:
        driver.close()

    if trace:
        summary = capture.summary
        ctx = {"driver": driver, "window": window, "trace": summary,
               "peak": peak, "config": config, "traffic": traffic,
               "seconds": seconds}
        metrics = {}
        for m in layer_metrics(bench, workload):
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(window.metrics, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in e2e_metrics(bench, workload)}
    device = dict(device, memory_peak_bytes=mem)
    result = {"correct": bool(checks) and all(c.ok for c in checks),
              "attempted": window.attempted, "failed": window.failed,
              "metrics": metrics, "device": device,
              "window_compiles": in_window.programs}
    if trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result
