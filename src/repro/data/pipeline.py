"""Morton-sharded training-data pipeline (the paper's cluster as an input
pipeline for LM training).

The corpus is a 2-d token grid (documents x positions) stored as Morton-
indexed cuboids (C1). Hosts own contiguous curve segments (C3), so each
host's reads are sequential (C7) while any global batch samples uniformly
from the corpus. Batch addressing is STATELESS (C2's REST analogue):
``batch_cuboids(step)`` is a pure function of (seed, step), so a restarted
or replacement host reproduces exactly its share of any batch — this is
what makes checkpoint/restart and elastic rescale trivial for the input
pipeline (no iterator state to persist).

Straggler mitigation: the curve is over-decomposed into work units; a
work-stealing queue lets fast workers absorb slow ones' units (the paper's
parallel-request doctrine, C8).
"""
from __future__ import annotations

import concurrent.futures as cf
import dataclasses
import queue
import threading
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from ..core import morton
from ..core.cuboid import DatasetSpec
from ..core.cutout import cutout, ingest
from ..core.store import CuboidStore


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    seq_len: int
    global_batch: int
    n_hosts: int = 1
    host_id: int = 0
    seed: int = 0
    prefetch: int = 2
    # over-decomposition factor for work stealing (units per worker)
    overdecompose: int = 4


class TokenStore:
    """Token corpus as a (docs, positions) uint32 grid over a CuboidStore."""

    def __init__(self, n_docs: int, doc_len: int,
                 cuboid: Tuple[int, int] = (64, 4096),
                 backend=None):
        self.spec = DatasetSpec(name="tokens",
                                volume_shape=(n_docs, doc_len),
                                dtype="uint32", base_cuboid=cuboid,
                                scaled_dims=())
        self.store = CuboidStore(self.spec, backend=backend)
        self.n_docs = n_docs
        self.doc_len = doc_len

    def ingest_corpus(self, tokens: np.ndarray, offset=(0, 0)) -> None:
        ingest(self.store, 0, tokens.astype(np.uint32), offset=offset)

    def read_rows(self, doc_lo: int, doc_hi: int, pos_lo: int,
                  pos_hi: int) -> np.ndarray:
        return cutout(self.store, 0, (doc_lo, pos_lo), (doc_hi, pos_hi))

    @property
    def grid(self):
        return self.spec.grid(0)


class DataPipeline:
    """Deterministic, stateless-addressed, prefetching batch pipeline."""

    def __init__(self, store: TokenStore, cfg: PipelineConfig):
        self.store = store
        self.cfg = cfg
        if store.doc_len < cfg.seq_len + 1:
            raise ValueError("doc_len must exceed seq_len (need labels)")
        self._rows_per_batch = cfg.global_batch
        self._q: "queue.Queue" = queue.Queue(maxsize=cfg.prefetch)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # ---- stateless batch addressing ------------------------------------
    def batch_rows(self, step: int) -> np.ndarray:
        """Document rows of global batch ``step`` — pure f(seed, step)."""
        rng = np.random.default_rng(
            np.random.SeedSequence([self.cfg.seed, step]))
        return rng.choice(self.store.n_docs, size=self._rows_per_batch,
                          replace=self.store.n_docs < self._rows_per_batch)

    def host_slice(self, step: int) -> np.ndarray:
        """The rows THIS host must produce (contiguous shard of the batch)."""
        rows = self.batch_rows(step)
        parts = morton.partition_curve(len(rows), self.cfg.n_hosts)
        lo, hi = parts[self.cfg.host_id]
        return rows[lo:hi]

    # ---- assembly with work stealing ------------------------------------
    def _assemble(self, rows: np.ndarray, n_workers: int = 2) -> np.ndarray:
        S = self.cfg.seq_len + 1  # +1: labels are next-token shifted
        out = np.zeros((len(rows), S), dtype=np.uint32)
        n_units = max(1, n_workers * self.cfg.overdecompose)
        units = np.array_split(np.arange(len(rows)), n_units)
        work: "queue.Queue" = queue.Queue()
        for u in units:
            if len(u):
                work.put(u)

        def worker():
            while True:
                try:
                    u = work.get_nowait()
                except queue.Empty:
                    return
                # visit docs in sorted order -> longer cutout runs (C7)
                order = np.argsort(rows[u], kind="stable")
                for k in order:
                    doc = int(rows[u[k]])
                    out[u[k]] = self.store.read_rows(doc, doc + 1, 0, S)[0]

        with cf.ThreadPoolExecutor(max_workers=n_workers) as ex:
            for f in [ex.submit(worker) for _ in range(n_workers)]:
                f.result()
        return out

    def get_batch(self, step: int) -> Dict[str, np.ndarray]:
        rows = self.host_slice(step)
        data = self._assemble(rows)
        return {"tokens": data[:, :-1].astype(np.int32),
                "labels": data[:, 1:].astype(np.int32)}

    # ---- prefetch (read path decoupled from the training loop, C4) ------
    def start(self, first_step: int = 0) -> None:
        def run():
            step = first_step
            while not self._stop.is_set():
                batch = self.get_batch(step)
                while not self._stop.is_set():
                    try:
                        self._q.put((step, batch), timeout=0.1)
                        break
                    except queue.Full:
                        continue
                step += 1

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()

    def next(self) -> Tuple[int, Dict[str, np.ndarray]]:
        return self._q.get()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
