"""Faults planted in the program's timed path, for the tests of the
comparison and for the chip readings of the training cell's numbers.

Each is a context manager that patches one program function for the
duration of a run; the benchmark's own runs never use them. A cell lists
its faults by name in its test file (``perfbench/tests/cells/``); ``find``
looks a name up in ``FAULTS`` here and in the ``FAULTS`` dicts of the
modules a cell brings.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Iterator, Sequence

import numpy as np


@contextlib.contextmanager
def _patched(obj, name: str, value) -> Iterator[None]:
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def train_half_batch():
    """The train step sees only the first half of each batch: the mean is
    taken over the rest."""
    import repro.train as train
    make = train.make_train_step

    def make_broken(*args, **kwargs):
        step = make(*args, **kwargs)

        def half(params, opt, batch):
            n = batch["tokens"].shape[0] // 2
            return step(params, opt, {k: v[:n] for k, v in batch.items()})
        return half
    return _patched(train, "make_train_step", make_broken)


def train_unchanged():
    """The train step returns the state it was given."""
    import repro.train as train
    make = train.make_train_step

    def make_broken(*args, **kwargs):
        step = make(*args, **kwargs)

        def same(params, opt, batch):
            _, _, metrics = step(params, opt, batch)
            return params, opt, metrics
        return same
    return _patched(train, "make_train_step", make_broken)


def detect_altered():
    """One voxel of every tile's first detection is moved off its object
    where ``detect_synapses`` produces it."""
    from repro.vision import synapse_detector as sd
    detect = sd.detect_synapses

    def broken(*args, **kwargs):
        dets, labels = detect(*args, **kwargs)
        where = np.flatnonzero(labels == 1)
        if where.size:
            labels = labels.copy()
            labels.ravel()[where[0]] = 0
        return dets, labels
    return _patched(sd, "detect_synapses", broken)


def detect_half_written():
    """``batch_write_objects`` stores only the first half of each batch but
    acknowledges every object."""
    from repro.core.annotations import AnnotationProject
    write = AnnotationProject.batch_write_objects

    def broken(self, r, objects, discipline="overwrite"):
        half = len(objects) // 2
        ids = write(self, r, objects[:half], discipline)
        for ann, _, _ in objects[half:]:
            ids.append(self.meta.create(ann).ann_id)
        return ids
    return _patched(AnnotationProject, "batch_write_objects", broken)


def detect_one_replica():
    """Annotation writes reach only the first member of each cuboid's
    replica set, and are acknowledged as before."""
    from repro.cluster import ClusterStore
    store = ClusterStore.store_cuboids

    def broken(self, r, blocks, channel=0):
        if self.spec.dtype != "uint32":  # the image volume is written whole
            return store(self, r, blocks, channel)
        by_node = {}
        for m, data in blocks.items():
            first = self.router.replica_set(r, m)[0]
            by_node.setdefault(first, {})[m] = data
        for node, part in by_node.items():
            self.nodes[node].store_cuboids(r, part, channel)
    return _patched(ClusterStore, "store_cuboids", broken)


def detect_no_exclusion():
    """``synapse_mask`` ignores the exclusion mask it is given."""
    from repro.vision import synapse_detector as sd
    mask = sd.synapse_mask

    def broken(vol, threshold=2.0, exclusion_mask=None):
        return mask(vol, threshold, None)
    return _patched(sd, "synapse_mask", broken)


FAULTS = {
    "train_half_batch": train_half_batch,
    "train_unchanged": train_unchanged,
    "detect_altered": detect_altered,
    "detect_half_written": detect_half_written,
    "detect_one_replica": detect_one_replica,
    "detect_no_exclusion": detect_no_exclusion,
}


def find(name: str, modules: Sequence = ()) -> Callable:
    """Fault ``name`` from ``FAULTS`` or from a ``FAULTS`` dict of one of
    ``modules``."""
    for table in [FAULTS] + [getattr(m, "FAULTS", {}) for m in modules]:
        if name in table:
            return table[name]
    raise KeyError(f"no fault {name!r} in perfbench/lib/faults.py or in "
                   f"{[m.__name__ for m in modules]}")
