"""Profiler capture and the reduction from an ``.xplane.pb`` to metrics.

Only the process that holds the chip can trace it, so the benchmark traces
its own window (``--trace 1``) and reduces the trace in the same process:

- busy seconds: the union of the device's operation intervals inside the
  window, averaged over the device planes;
- per-module device time: the ``XLA Modules`` events summed by name;
- per-operation device time: each instant counted once, for the innermost
  operation running then (a loop's ``while`` gets only what its body's
  operations leave uncovered), summed by ``module:op``;
- ``breakdown``: the device operations that took most time, and the idle
  time between operations attributed to the host span (``bench.*``
  annotations written by the benchmark's own wrappers) that covers most of
  each gap.

The window is the ``bench.window`` host annotation, on the trace's clock.
The device planes' timestamps may sit a millisecond or two off the host's
(seen on a v5e); that is far below the windows reduced here.
"""
from __future__ import annotations

import contextlib
import dataclasses
import re
import shutil
import tempfile
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_SUFFIX = re.compile(r"\(\d+\)$")


def module_name(name: str) -> str:
    """``jit_step(123)`` -> ``jit_step``."""
    return _SUFFIX.sub("", name)


def op_name(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` (an HLO instruction, as the
    TPU's trace names it) -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # mean over the device planes
    devices: int
    module_s: Dict[str, float]          # device seconds per module name
    module_calls: Dict[str, int]
    op_s: Dict[str, float]              # innermost device s per "module:op"
    idle_by_host: Dict[str, float]      # idle seconds per host span label

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def breakdown(self, top: int = 10) -> Dict[str, List[List]]:
        def best(d):
            return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                    [:top]]
        return {"device_ops": best(self.op_s),
                "idle_gaps": best(self.idle_by_host)}

    def module_seconds(self, fragment: str) -> Tuple[float, int]:
        """Device seconds and calls of every module whose name contains
        ``fragment``."""
        secs = sum(v for k, v in self.module_s.items() if fragment in k)
        calls = sum(v for k, v in self.module_calls.items() if fragment in k)
        return secs, calls


def _merge(iv: np.ndarray) -> np.ndarray:
    """Union of [start, end) intervals, sorted and disjoint."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for a, b in iv[1:]:
        if a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return np.asarray(out, dtype=np.float64)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def _covered(merged: np.ndarray, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Length of each [a_i, b_i) covered by the disjoint sorted ``merged``."""
    if len(merged) == 0:
        return np.zeros_like(a)
    starts, ends = merged[:, 0], merged[:, 1]
    cum = np.concatenate([[0.0], np.cumsum(ends - starts)])

    def upto(t):
        i = np.searchsorted(starts, t, side="right")   # intervals starting <= t
        full = cum[np.maximum(i - 1, 0)]
        part = np.where(i > 0, np.minimum(t, ends[np.maximum(i - 1, 0)])
                        - starts[np.maximum(i - 1, 0)], 0.0)
        return np.where(i > 0, full + part, 0.0)

    return upto(b) - upto(a)


def self_times(events) -> Dict[str, float]:
    """Length of time each ``(start, end, name)`` event is the innermost one
    running, summed by name: every covered instant counts once, for the
    event that started last of those covering it."""
    out: Dict[str, float] = {}
    stack: List[Tuple[float, str]] = []       # (end, name), by start
    t = -np.inf

    def run_until(limit: float) -> None:
        nonlocal t
        while stack and t < limit:
            end, name = stack[-1]
            if end <= t:
                stack.pop()
                continue
            upto = min(end, limit)
            out[name] = out.get(name, 0.0) + (upto - t)
            t = upto

    for a, b, name in sorted(events, key=lambda e: (e[0], -e[1])):
        run_until(a)
        t = a
        stack.append((b, name))
    run_until(np.inf)
    return out


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name


def reduce_profile(planes) -> TraceSummary:
    """Reduce the planes of a ``jax.profiler.ProfileData`` (or any objects
    with the same ``name`` / ``lines`` / ``events`` shape)."""
    host_spans: Dict[str, List[Tuple[float, float]]] = {}
    window: Optional[Tuple[float, float]] = None
    device_ops: List[Tuple[str, float, float, str]] = []   # plane-tagged
    modules: List[Tuple[float, float, str]] = []
    n_devices = 0
    for plane in planes:
        if _is_device(plane.name):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines and "XLA Modules" not in lines:
                continue
            n_devices += 1
            mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                           module_name(ev.name))
                          for ev in (lines["XLA Modules"].events
                                     if "XLA Modules" in lines else ()))
            modules += mods
            starts = np.asarray([m[0] for m in mods], np.float64)
            busy_line = lines.get("XLA Ops") or lines.get("XLA Modules")
            for ev in busy_line.events:
                a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                mod = module_name(str(dict(ev.stats).get("hlo_module", "")))
                i = int(np.searchsorted(starts, a, side="right")) - 1
                if not mod and i >= 0 and a < mods[i][1]:
                    mod = mods[i][2]
                name = op_name(ev.name)
                device_ops.append((plane.name, a, b,
                                   f"{mod}:{name}" if mod else name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith(SPAN_PREFIX):
                        continue
                    iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    if ev.name == WINDOW_SPAN:
                        window = iv
                    else:
                        host_spans.setdefault(ev.name, []).append(iv)
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    if n_devices == 0:
        raise ValueError("no device plane with XLA operations in the trace")
    w0, w1 = window
    window_s = (w1 - w0) * 1e-9

    busy_ns = 0.0
    gaps: List[np.ndarray] = []
    op_s: Dict[str, float] = {}
    by_plane: Dict[str, List[Tuple[float, float, str]]] = {}
    for plane, a, b, name in device_ops:
        lo, hi = max(a, w0), min(b, w1)
        if hi <= lo:
            continue
        by_plane.setdefault(plane, []).append((lo, hi, name))
    for events in by_plane.values():
        for name, ns in self_times(events).items():
            op_s[name] = op_s.get(name, 0.0) + ns * 1e-9
        merged = _merge(np.asarray([e[:2] for e in events], dtype=np.float64))
        busy_ns += float((merged[:, 1] - merged[:, 0]).sum())
        edges = np.concatenate([[w0], merged.ravel(), [w1]]).reshape(-1, 2)
        gaps.append(edges[edges[:, 1] > edges[:, 0]])
    busy_s = busy_ns * 1e-9 / n_devices

    module_s: Dict[str, float] = {}
    module_calls: Dict[str, int] = {}
    for a, b, name in modules:
        lo, hi = max(a, w0), min(b, w1)
        if hi <= lo:
            continue
        module_s[name] = module_s.get(name, 0.0) + (hi - lo) * 1e-9
        module_calls[name] = module_calls.get(name, 0) + 1

    idle: Dict[str, float] = {}
    all_gaps = (np.concatenate(gaps) if gaps
                else np.zeros((0, 2), np.float64))
    if len(all_gaps):
        labels = sorted(host_spans)
        cover = np.zeros((len(labels), len(all_gaps)))
        for i, label in enumerate(labels):
            merged = _merge(_clip(np.asarray(host_spans[label], np.float64),
                                  w0, w1))
            cover[i] = _covered(merged, all_gaps[:, 0], all_gaps[:, 1])
        length = all_gaps[:, 1] - all_gaps[:, 0]
        if labels:
            best = cover.argmax(axis=0)
            has = cover.max(axis=0) > 0
        else:
            best = np.zeros(len(all_gaps), int)
            has = np.zeros(len(all_gaps), bool)
        for j in range(len(all_gaps)):
            key = labels[best[j]] if has[j] else "host:no bench span"
            idle[key] = idle.get(key, 0.0) + length[j] * 1e-9 / n_devices
    return TraceSummary(window_s=window_s, busy_s=busy_s, devices=n_devices,
                        module_s=module_s, module_calls=module_calls,
                        op_s=op_s, idle_by_host=idle)


def reduce_file(path: Path) -> TraceSummary:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(str(path)).planes)


class Capture:
    """``with Capture() as cap: ...`` traces the block; ``cap.summary``
    holds the reduction afterwards. The trace lives in a temporary
    directory (under ``TMPDIR``) that is removed once reduced."""

    def __init__(self):
        self.summary: Optional[TraceSummary] = None

    def __enter__(self) -> "Capture":
        import jax
        self._dir = Path(tempfile.mkdtemp(prefix="perfbench-trace-"))
        jax.profiler.start_trace(str(self._dir))
        return self

    def __exit__(self, exc_type, *_):
        import jax
        jax.profiler.stop_trace()
        try:
            if exc_type is None:
                found = sorted(self._dir.rglob("*.xplane.pb"))
                if not found:
                    raise FileNotFoundError("the profiler wrote no .xplane.pb")
                self.summary = reduce_file(found[-1])
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return False


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """A host span in the profiler's trace (a no-op when nothing traces)."""
    import jax
    with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
        yield
