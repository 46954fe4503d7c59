"""Sampled spans along the detect-and-annotate path.

* the untraced path stays one ContextVar read: the shared null span, no
  CPU clock read, no profiler annotation;
* a sampled span records its thread's CPU time beside its wall time, and
  lands in the profiler's trace as a host event under its own name;
* a traced ``batch_write_objects`` on a replicated cluster records the
  batch, index, merge and per-node store spans with counters equal to
  what was written, and every node job's wait for a pool thread;
* ``connected_components`` counts its sweeps on the enclosing span when
  sampled, with labels as before.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import ClusterStore
from repro.core.annotations import Annotation, AnnotationProject
from repro.core.cuboid import DatasetSpec
from repro.obs import trace
from repro.vision.synapse_detector import connected_components, detect_synapses

SHAPE = (64, 64, 16)
CUBOID = (16, 16, 8)


def traced(fn, trace_id="t"):
    """Run ``fn`` under a sampled trace; returns (result, its spans)."""
    ring = trace.SpanRing(4096)
    with trace.activate(trace.TraceContext(trace_id, ring)):
        out = fn()
    return out, ring.spans_for(trace_id)


@pytest.fixture
def recorders(monkeypatch):
    calls = {"thread_time": 0, "annotation": []}
    real_thread_time = time.thread_time

    def thread_time():
        calls["thread_time"] += 1
        return real_thread_time()

    class Annotation_:
        def __init__(self, name, **kw):
            calls["annotation"].append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(time, "thread_time", thread_time)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation_)
    return calls


def test_an_untraced_span_is_the_shared_null(recorders):
    assert trace.current() is None
    with trace.span("write.merge") as meta:
        assert meta is None
        trace.annotate(voxels=1)  # no span open: nothing to write into
    assert trace.span("detect.device") is trace._NULL
    assert recorders == {"thread_time": 0, "annotation": []}


def test_a_sampled_span_records_cpu_time_and_opens_an_annotation(recorders):
    def work():
        with trace.span("detect.group") as meta:
            # busy long enough that a clock ticking every 10 ms moves
            t_end = time.perf_counter() + 0.1
            while time.perf_counter() < t_end:
                pass
            trace.annotate(sweeps=4)
        return meta

    meta, spans = traced(work)
    (s,) = spans
    assert s["name"] == "detect.group" and meta == {"sweeps": 4}
    assert 0 < s["cpu_s"] <= s["dur_s"] + 0.01
    assert recorders["thread_time"] == 2
    assert recorders["annotation"] == ["detect.group"]


def test_a_sampled_span_is_a_host_event_in_the_profile(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.activate(trace.TraceContext("p", trace.SpanRing(16))):
            with trace.span("annotate.batch"):
                jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    planes = jax.profiler.ProfileData.from_file(str(path)).planes
    host = {ev.name for p in planes if p.name.startswith("/host:")
            for line in p.lines for ev in line.events}
    assert "annotate.batch" in host


def test_bind_hands_the_pool_wait_to_the_jobs_first_span():
    import concurrent.futures as cf

    def job():
        with trace.span("node.fetch", node=0, queued_s=0.0):
            with trace.span("store.fetch"):
                pass

    def run():
        with cf.ThreadPoolExecutor(1) as pool:
            blocker = pool.submit(time.sleep, 0.05)
            fut = pool.submit(trace.bind(job))
            blocker.result()
            fut.result()

    _, spans = traced(run)
    by = {s["name"]: s for s in spans}
    assert by["node.fetch"]["meta"]["queued_s"] >= 0.03  # behind the sleep
    assert "queued_s" not in by["store.fetch"]["meta"]


def test_a_traced_batch_write_records_its_stages_and_counters():
    spec = DatasetSpec(name="spans", volume_shape=SHAPE, dtype="uint8",
                       base_cuboid=CUBOID)
    proj = AnnotationProject("p", spec, store_factory=lambda s: ClusterStore(
        s, n_nodes=4, replication=2))
    try:
        objs = []
        for k in range(3):
            vol = np.zeros((32, 32, 16), np.uint32)
            vol[4 * k:4 * k + 3, 2:5, 1:3] = 1  # 18 voxels each
            objs.append((Annotation(0, ann_type="synapse"), (16, 0, 0), vol))
        ids, spans = traced(lambda: proj.batch_write_objects(0, objs))
        names = [s["name"] for s in spans]
        by = {}
        for s in spans:
            by.setdefault(s["name"], []).append(s)
        (batch,) = by["annotate.batch"]
        assert batch["meta"] == {"object_voxels": 54}
        # one index update and one merge chunk per object; each merge
        # covers the whole 32x32x16 box the object was written as
        assert len(by["annotate.index"]) == 3
        assert [s["meta"] for s in by["write.merge"]] == [
            {"voxels": 32 * 32 * 16}] * 3
        # each chunk's store fans out to the nodes of its replica sets
        ids_of = {s["id"]: s["name"] for s in spans}
        stores = by["node.store"]
        assert len(stores) >= 3 * 2
        assert all(ids_of[s["parent"]] == "write.store" for s in stores)
        node_jobs = stores + by["node.fetch"]
        assert all(s["meta"]["queued_s"] >= 0 for s in node_jobs)
        assert all(s["cpu_s"] >= 0 for s in spans)
        # merge ends before its store starts: the two never nest
        assert all(ids_of.get(s["parent"]) != "write.merge" for s in spans)
        assert names[-1] == "annotate.batch"
        # the annotations written are the ones read back
        got = proj.read(0, (16, 0, 0), (48, 32, 16))
        assert sorted(np.unique(got)[1:].tolist()) == sorted(ids)
    finally:
        proj.store.close()


@pytest.mark.parametrize("n", [1, 2, 5, 16])
def test_connected_components_counts_its_sweeps(n):
    """A line of ``n`` voxels along x: the first propagation (before the
    loop) gives each voxel its lower neighbour's label; the smallest label,
    the line's first voxel's, then needs ``n - 2`` more sweeps to reach
    the far end and one to find nothing changed: ``n - 1`` sweeps
    (0 for one voxel, whose first propagation already is the fixpoint)."""
    mask = np.zeros((20, 6, 4), bool)
    mask[3:3 + n, 2, 1] = True
    first = np.ravel_multi_index((3, 2, 1), mask.shape) + 1
    want = np.where(mask, first, 0)

    lab = np.asarray(connected_components(jnp.asarray(mask)))  # untraced
    np.testing.assert_array_equal(lab, want)

    def run():
        with trace.span("detect.device") as meta:
            out = np.asarray(connected_components(jnp.asarray(mask)))
        return out, meta

    (lab, meta), _ = traced(run)
    np.testing.assert_array_equal(lab, want)
    assert meta == {"sweeps": max(n - 1, 0)}


def test_detect_synapses_records_device_and_group_spans():
    rng = np.random.default_rng(5)
    vol = rng.normal(100, 5, (64, 64, 16)).astype(np.float32)
    for x, y in [(10, 10), (40, 20), (20, 50)]:
        vol[x - 2:x + 3, y - 2:y + 3, 6:10] += 80
    (dets, labels), spans = traced(lambda: detect_synapses(vol))
    by = {s["name"]: s for s in spans}
    assert set(by) == {"detect.device", "detect.group"}
    assert by["detect.device"]["meta"]["sweeps"] > 0
    assert by["detect.group"]["meta"] == {} and len(dets) > 0
    # the same detections untraced
    dets_u, labels_u = detect_synapses(vol)
    np.testing.assert_array_equal(labels, labels_u)
    assert [d.n_voxels for d in dets] == [d.n_voxels for d in dets_u]
