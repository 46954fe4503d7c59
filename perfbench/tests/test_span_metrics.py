"""The readers of the program's spans in ``em.detect``, on a stub driver
whose window tiles carry hand-made span records."""
import dataclasses
from typing import Dict, List

import pytest

from perfbench.lib import harness


@dataclasses.dataclass
class Tile:
    spans: List[Dict]


class Driver:
    def __init__(self, tiles):
        self.tiles = tiles

    def window_tiles(self):
        return self.tiles


_ids = iter(range(1, 1 << 20))


def span(name, dur=0.0, cpu=0.0, parent=0, thread="detect-0", **meta):
    return {"trace": "t", "id": next(_ids), "parent": parent, "name": name,
            "t0": 0.0, "dur_s": dur, "cpu_s": cpu, "thread": thread,
            "meta": meta}


def tile_with_writes():
    """One tile of two objects (10 and 30 voxels): its cutout read, its
    detection and one batch of two whole-tile writes."""
    cut = span("node.fetch", 0.010, 0.002, thread="ocp-node_0", node=0,
               queued_s=0.004)
    dev = span("detect.device", 0.050, 0.010, sweeps=40)
    group = span("detect.group", 0.020, 0.020)
    batch = span("annotate.batch", 1.000, 0.300, object_voxels=40)
    out = [cut, dev, group]
    for _ in range(2):
        fetch = span("write.fetch", 0.100, 0.010, parent=batch["id"], runs=1)
        inline = span("node.fetch", 0.050, 0.020, parent=fetch["id"], node=1,
                      queued_s=0.0)  # ran in the worker's own thread
        merge = span("write.merge", 0.200, 0.150, parent=batch["id"],
                     voxels=1000)
        store = span("write.store", 0.150, 0.010, parent=batch["id"],
                     cuboids=16)
        nodes = [span("node.store", 0.070, 0.040, parent=store["id"],
                      thread=f"ocp-node_{n}", queued_s=0.030)
                 for n in (0, 1)]
        index = span("annotate.index", 0.080, 0.080, parent=batch["id"])
        out += [inline, fetch, merge] + nodes + [store, index]
    return out + [batch]


def tile_without_detections():
    return [span("node.fetch", 0.010, 0.002, thread="ocp-node_1", node=1,
                 queued_s=0.002),
            span("detect.device", 0.040, 0.010, sweeps=20),
            span("detect.group", 0.010, 0.010)]


def read(name, tiles):
    return harness.load_reader(name)({"driver": Driver(tiles)})


TILES = [Tile(tile_with_writes()), Tile(tile_without_detections()),
         Tile([])]  # began before the window was traced: left out

EXPECTED = {
    # two merges of 0.2 s in one tile, none in the other
    "detect.merge_ms": 1e3 * (0.4 + 0) / 2,
    "detect.index_ms": 1e3 * (0.16 + 0) / 2,
    "detect.group_ms": 1e3 * (0.02 + 0.01) / 2,
    # the cutout read's wait, the inline fetches' 0, four store jobs' 0.03
    "detect.node_queue_ms": 1e3 * ((0.004 + 4 * 0.030) + 0.002) / 2,
    # the batch's own thread once (its inline node.fetch is inside it), the
    # four pool-thread store jobs, and not the cutout read's node.fetch
    "detect.write_cpu_ms": 1e3 * ((0.300 + 4 * 0.040) + 0) / 2,
    "detect.write_amplification": 2 * 1000 / 40,
    "detect.cc_sweeps": (40 + 20) / 2,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_reader_gives_the_mean_per_tile_of_the_window(name):
    assert read(name, TILES) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_reader_finds_nothing_where_the_program_records_nothing(name):
    """A program without these spans and counters (the spans of ``write``
    only, with wall times): the reader gives None and does not raise."""
    old = [Tile([span("write.fetch", 0.1, runs=1),
                 span("node.fetch", 0.01, node=0),
                 span("write.store", 0.1, cuboids=16)]),
           Tile([])]
    for s in old[0].spans:
        del s["cpu_s"]
    assert read(name, old) is None
    assert read(name, []) is None


@pytest.mark.parametrize("name", ["detect.merge_ms", "detect.index_ms",
                                  "detect.write_cpu_ms",
                                  "detect.write_amplification"])
def test_a_window_without_detections_writes_nothing(name):
    assert read(name, [Tile(tile_without_detections())]) is None


def test_the_write_cpu_counts_each_second_once():
    """A node job that ran in the batch's own thread is inside the batch's
    CPU time already; one on a pool thread is not, and one outside the
    write path (the cutout's read) is no part of it."""
    batch = span("annotate.batch", 1.0, 0.5, objects=1, object_voxels=1)
    own = span("node.store", 0.1, 0.1, parent=batch["id"], thread="detect-0",
               queued_s=0.0)
    pool = span("node.store", 0.1, 0.2, parent=batch["id"],
                thread="ocp-node_3", queued_s=0.0)
    outside = span("node.fetch", 0.1, 0.4, thread="ocp-node_2", queued_s=0.0)
    got = read("detect.write_cpu_ms", [Tile([own, pool, outside, batch])])
    assert got == pytest.approx(1e3 * (0.5 + 0.2))


def test_every_reader_is_an_entry_of_the_detection_cell():
    bench = harness.load_benchmark()
    names = {m["name"] for m in harness.layer_metrics(bench, "em.detect")}
    assert set(EXPECTED) <= names
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in EXPECTED:
        assert entries[name]["source"] == "program_span"
        assert entries[name]["moves"] == "detect_mvox_s"
        assert entries[name]["workloads"] == ["em.detect"]
