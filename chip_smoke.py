"""Chip smoke: the data cluster's device path and SmolLM-135M on a TPU.

  python chip_smoke.py              # one chip, phases 0-5
  python chip_smoke.py --chips 4    # four chips: only the sharded paths

One process, phases in order; any failed comparison raises and the script
exits non-zero. The last line of standard output is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

0. device: refuse to run unless JAX's first device is a TPU.
1. cluster: a 4-node, replication-2 ``ClusterStore`` behind the HTTP front
   door (threads in this process) is loaded with a seeded 1 GiB EM-like
   volume by z-slab PUTs and answers cutouts, a batch and an xy tile; each
   answer is bit-identical to the numpy slice of the volume.
2. device cutouts: the cuboid-major array, built from the volume as read
   back through the front door, lives on the chip; the Pallas
   ``cutout_gather`` kernel (compiled, not interpreted) and
   ``distributed_cutout`` answer the same boxes bit-identically, and a
   ``distributed_write_cutout`` reads back.
3. synapse detection on four 512x512x16 tiles cut through the front door:
   connected-component labels equal the host CPU's for the same mask; the
   DoG response agrees with the CPU's within a stated tolerance.
4. SmolLM-135M serving at published widths through ``launch/serve.main``
   (batched, then continuous batching); cached decode agrees with the full
   forward pass.
5. SmolLM-135M training at published widths through ``launch/train.main``.

``--chips 4`` runs phases 0 and 1, then ``distributed_cutout`` /
``distributed_write_cutout`` over a 4-device ``data`` mesh (one curve
segment per chip) against ``cutout_gather`` on device 0, and 3 training
steps on the 2x2 mesh against the same steps on one device.

Data and weights come from ``--seed``; nothing outside the repository is
read. Wall times, compile times and bytes are printed as set-up lines.
"""
from __future__ import annotations

import argparse
import base64
import dataclasses
import importlib.metadata
import json
import statistics
import sys
import time
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.cluster import ClusterStore, VolumeService  # noqa: E402
from repro.core.cuboid import CuboidGrid, DatasetSpec  # noqa: E402
from repro.core.distributed import (distributed_cutout,  # noqa: E402
                                    distributed_write_cutout,
                                    pack_to_cuboids, shard_cuboids)
from repro.kernels.cutout_gather.ops import cutout_gather  # noqa: E402
from repro.launch import serve as serve_cli  # noqa: E402
from repro.launch import train as train_cli  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_data_mesh, make_device_mesh  # noqa: E402
from repro.serve.client import RetryingClient  # noqa: E402
from repro.serve.http_front import FrontDoor  # noqa: E402
from repro.vision.synapse_detector import (connected_components,  # noqa: E402
                                           detect_synapses,
                                           difference_of_gaussians,
                                           synapse_mask)

Box = Tuple[Tuple[int, ...], Tuple[int, ...]]
DATASET = "em"


@dataclasses.dataclass(frozen=True)
class Sizes:
    volume: Tuple[int, int, int]
    cuboid: Tuple[int, int, int]
    tile: Tuple[int, int, int]


# The paper's 128x128x16 cuboids over a 2048x2048x256 uint8 volume: 1 GiB
# in 4,096 cuboids.
FULL = Sizes((2048, 2048, 256), (128, 128, 16), (512, 512, 16))
# seconds the cluster load may take before z is halved
LOAD_BUDGET_S = 60.0


def fail(msg: str) -> None:
    raise AssertionError(msg)


def check(cond, msg: str) -> None:
    if not cond:
        fail(msg)


class CompileClock:
    """Seconds spent in backend compiles (a persistent-cache hit counts
    only its retrieval), persistent-cache hits, and entries written to the
    cache (JAX's "cache_misses" event fires on a write: a program that
    missed and compiled for longer than the cache's minimum)."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.hits = 0
        self.written = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.written += 1

    def snapshot(self):
        return (self.seconds, self.programs, self.hits, self.written)


class Phase:
    """Prints a phase's wall time and the compiles it triggered."""

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock = name, clock

    def __enter__(self):
        print(f"[{self.name}] start", flush=True)
        self.t0 = time.perf_counter()
        self.c0 = self.clock.snapshot()
        return self

    def __exit__(self, exc_type, *_):
        s, n, h, m = (b - a for a, b in zip(self.c0, self.clock.snapshot()))
        state = "ok" if exc_type is None else "FAILED"
        print(f"[{self.name}] {state}: wall {time.perf_counter() - self.t0:.2f} s,"
              f" compile {s:.2f} s in {n} programs"
              f" (persistent cache: {h} hits, {m} written)", flush=True)
        return False


# -------------------------------------------------------------- phase 0 ----

def phase_device(chips: int) -> Dict:
    """Refuse to run anywhere but on a TPU; print what is there."""
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        fail(f"phase 0: JAX's first device is {d.platform!r}, not a TPU; "
             f"this smoke runs on the chip only")
    check(len(devices) >= chips,
          f"phase 0: --chips {chips} but JAX reports {len(devices)} devices")
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    print(f"device: {d.device_kind} x{len(devices)} ({d.platform}); "
          f"jax {jax.__version__}, jaxlib "
          f"{importlib.metadata.version('jaxlib')}, libtpu {libtpu}",
          flush=True)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


# -------------------------------------------------------------- phase 1 ----

def make_volume(shape: Tuple[int, int, int], seed: int) -> np.ndarray:
    """EM-like uint8 volume: grey noise, synapse-sized bright blobs, one
    large bright structure (a vessel)."""
    rng = np.random.default_rng(seed)
    X, Y, Z = shape
    # grey N(100, 4) noise by inverse-CDF lookup of uniform bytes: a float
    # normal draw per voxel would dominate set-up at 1 GiB
    ppf = statistics.NormalDist(100.0, 4.0).inv_cdf
    grey = np.array([round(ppf((u + 0.5) / 256)) for u in range(256)],
                    np.uint8)
    vol = grey[rng.integers(0, 256, size=shape, dtype=np.uint8)]
    # one blob per 64x64x16 voxels; each a 9x9x5 anisotropic Gaussian stamp
    n = max(4, X * Y * Z // (64 * 64 * 16))
    r = np.array([4, 4, 2])
    centers = rng.integers(r, np.array(shape) - r, size=(n, 3))
    off = np.stack(np.meshgrid(*[np.arange(-k, k + 1) for k in r],
                               indexing="ij"), -1).reshape(-1, 3)
    stamp = 90.0 * np.exp(-(off[:, 0] ** 2 + off[:, 1] ** 2
                            + (2 * off[:, 2]) ** 2) / 9.0)
    flat_idx = np.ravel_multi_index(
        (centers[:, None, :] + off[None]).reshape(-1, 3).T, shape)
    cells, inverse = np.unique(flat_idx, return_inverse=True)
    added = np.bincount(inverse, weights=np.tile(stamp, n))
    flat = vol.reshape(-1)
    flat[cells] = np.clip(flat[cells] + added, 0, 255).astype(np.uint8)
    x0, x1 = int(X * 0.31), int(X * 0.70)
    y0, y1 = int(Y * 0.31), int(Y * 0.39)
    sub = vol[x0:x1, y0:y1]
    np.minimum(sub, 195, out=sub)
    sub += 60
    return vol


def query_boxes(sizes: Sizes) -> Dict[str, List[Box]]:
    """The boxes phase 1 asks the front door for (phase 2 asks the chip)."""
    X, Y, Z = sizes.volume
    cx, cy, cz = sizes.cuboid
    lo = (cx // 2 + 3, cy // 3 + 5, cz // 2 + 1)
    tile_lo = (X // 4 + 1, Y // 4 + 2, Z // 2 + 3)
    return {
        "aligned": [((cx, cy, cz), (3 * cx, 3 * cy, 3 * cz))],
        # crosses cuboid boundaries on every axis
        "unaligned": [(lo, (lo[0] + 2 * cx + 7, lo[1] + cy + 9,
                            lo[2] + cz + 5))],
        "batch": [((0, 0, 0), (cx, cy, cz)),
                  ((X - cx - 5, Y - 2 * cy + 3, Z - cz - 2), (X - 3, Y - 1, Z)),
                  ((X // 2 - 11, Y // 2 - 13, Z // 2 - 3),
                   (X // 2 + 17, Y // 2 + 19, Z // 2 + 5)),
                  ((5, Y // 3, 1), (2 * cx + 1, Y // 3 + cy + 2, cz + 2))],
        # an xy tile: one z plane
        "xy": [(tile_lo, (tile_lo[0] + min(512, X // 2),
                          tile_lo[1] + min(512, Y // 2), tile_lo[2] + 1))],
    }


def _box_path(lo, hi) -> str:
    return "/".join(f"{a},{b}" for a, b in zip(lo, hi))


def _slice(lo, hi):
    return tuple(slice(a, b) for a, b in zip(lo, hi))


class Cluster:
    """A replicated ClusterStore behind the HTTP front door, driven over
    the socket by the project's retrying client."""

    def __init__(self, sizes: Sizes, n_nodes: int = 4, replication: int = 2):
        self.spec = DatasetSpec(name=DATASET, volume_shape=sizes.volume,
                                dtype="uint8", n_resolutions=2,
                                base_cuboid=sizes.cuboid)
        self.store = ClusterStore(self.spec, n_nodes=n_nodes,
                                  replication=replication)
        service = VolumeService()
        service.add_dataset(DATASET, self.store)
        self.door = FrontDoor(service, host="127.0.0.1", port=0)

    def __enter__(self):
        self.door.start()
        self.client = RetryingClient(self.door.url, retries=3, timeout=600)
        return self

    def __exit__(self, *exc):
        self.door.close()
        self.store.close()
        return False

    def put(self, lo, data: np.ndarray) -> None:
        hi = tuple(a + s for a, s in zip(lo, data.shape))
        out = self.client.put_raw(
            f"/{DATASET}/cutout/0/{_box_path(lo, hi)}?sync=1",
            np.ascontiguousarray(data).tobytes())
        check(out.get("status") == 200, f"PUT {lo}..{hi}: {out}")

    def get(self, lo, hi, verb: str = "cutout") -> np.ndarray:
        status, headers, payload = self.client.get_raw(
            f"/{DATASET}/{verb}/0/{_box_path(lo, hi)}")
        check(status == 200, f"GET {verb} {lo}..{hi}: {status} {payload[:200]}")
        shape = tuple(int(s) for s in headers["X-Shape"].split(","))
        return np.frombuffer(payload, dtype=headers["X-Dtype"]).reshape(shape)

    def batch(self, boxes: List[Box]) -> List[np.ndarray]:
        out = self.client.post_json(
            f"/{DATASET}/batch/cutout",
            {"resolution": 0, "boxes": [[list(lo), list(hi)] for lo, hi in boxes]})
        check(out.get("status") == 200, f"batch: {out}")
        return [np.frombuffer(base64.b64decode(r["data"]), dtype=r["dtype"])
                .reshape(r["shape"]) for r in out["results"]]


def load(cluster: Cluster, vol: np.ndarray, sizes: Sizes,
         budget_s: float = LOAD_BUDGET_S) -> Sizes:
    """PUT the volume in z-slabs. Where the first slab's time projects the
    load past ``budget_s``, only the lower half in z is loaded; returns the
    sizes that were loaded."""
    cz = sizes.cuboid[2]
    n = vol.shape[2] // cz
    t0 = time.perf_counter()
    cluster.put((0, 0, 0), vol[:, :, :cz])
    if n > 1 and (time.perf_counter() - t0) * n > budget_s:
        n //= 2
        sizes = dataclasses.replace(sizes, volume=sizes.volume[:2] + (n * cz,))
        print(f"load cut: the first slab projects past {budget_s:.0f} s, so "
              f"z is halved to {sizes.volume}", flush=True)
    for z0 in range(cz, n * cz, cz):
        cluster.put((0, 0, z0), vol[:, :, z0:z0 + cz])
    dt = time.perf_counter() - t0
    mib = vol[:, :, :n * cz].nbytes / 2**20
    print(f"loaded {mib:.0f} MiB in {n} z-slab PUTs (sync) in {dt:.2f} s: "
          f"{mib / dt:.1f} MiB/s", flush=True)
    return sizes


def phase_cluster(cluster: Cluster, vol: np.ndarray, sizes: Sizes,
                  budget_s: float = LOAD_BUDGET_S):
    """Load through PUT z-slabs, then answer queries over the socket.
    Returns the sizes loaded, the boxes and the answers, {kind: [array per
    box]}, each checked bit-identical to numpy."""
    sizes = load(cluster, vol, sizes, budget_s)
    boxes = query_boxes(sizes)
    answers = {
        "aligned": [cluster.get(*b) for b in boxes["aligned"]],
        "unaligned": [cluster.get(*b) for b in boxes["unaligned"]],
        "batch": cluster.batch(boxes["batch"]),
        "xy": [cluster.get(*b, verb="xy") for b in boxes["xy"]],
    }
    for kind, got in answers.items():
        for (lo, hi), arr in zip(boxes[kind], got):
            want = vol[_slice(lo, hi)]
            if kind == "xy":
                want = want[:, :, 0]
            check(arr.dtype == want.dtype and np.array_equal(arr, want),
                  f"front door {kind} {lo}..{hi} differs from the volume")
    n = sum(len(v) for v in answers.values())
    print(f"front door: {n} boxes in 4 requests bit-identical to numpy",
          flush=True)
    return sizes, boxes, answers


# -------------------------------------------------------------- phase 2 ----

def read_back(cluster: Cluster, sizes: Sizes) -> np.ndarray:
    """The whole volume through GET /cutout, one cuboid layer at a time."""
    X, Y, Z = sizes.volume
    cz = sizes.cuboid[2]
    out = np.empty(sizes.volume, np.uint8)
    for z0 in range(0, Z, cz):
        out[:, :, z0:z0 + cz] = cluster.get((0, 0, z0), (X, Y, z0 + cz))
    return out


def place_cuboids(volume: np.ndarray, grid: CuboidGrid, devices):
    packed = shard_cuboids(pack_to_cuboids(volume, grid),
                           make_data_mesh(devices))
    jax.block_until_ready(packed)
    in_use = devices[0].memory_stats() or {}
    print(f"cuboid-major array on {len(devices)} device(s): {packed.shape} "
          f"{packed.dtype}, {packed.nbytes / 2**20:.1f} MiB logical; device 0 "
          f"bytes_in_use {in_use.get('bytes_in_use', 'not reported')}",
          flush=True)
    return packed


def device_answers(packed, grid: CuboidGrid, boxes: Dict[str, List[Box]],
                   how) -> Dict[str, List[np.ndarray]]:
    out = {}
    for kind, bs in boxes.items():
        got = [np.asarray(how(packed, grid, lo, hi)) for lo, hi in bs]
        out[kind] = [g[:, :, 0] for g in got] if kind == "xy" else got
    return out


def same_answers(got, want, what: str) -> None:
    for kind in want:
        for i, (g, w) in enumerate(zip(got[kind], want[kind])):
            check(g.dtype == w.dtype and np.array_equal(g, w),
                  f"{what}: {kind}[{i}] differs from the front door's answer")


def write_box(sizes: Sizes) -> Box:
    """An unaligned box for the distributed write."""
    cx, cy, cz = sizes.cuboid
    lo = (cx + 9, 2 * cy - 7, cz - 3)
    return lo, (lo[0] + cx + 5, lo[1] + cy // 2 + 11, lo[2] + cz // 2 + 6)


def check_write(packed, grid: CuboidGrid, volume: np.ndarray, sizes: Sizes,
                mesh, seed: int) -> None:
    """distributed_write_cutout of an unaligned box, read back with a
    one-cuboid margin so untouched neighbours are checked too."""
    lo, hi = write_box(sizes)
    patch = np.random.default_rng(seed + 1).integers(
        0, 256, size=[b - a for a, b in zip(lo, hi)], dtype=np.uint8)
    updated = distributed_write_cutout(packed, grid, lo, jnp.asarray(patch),
                                       mesh)
    mlo = tuple(max(0, a - c) for a, c in zip(lo, sizes.cuboid))
    mhi = tuple(min(v, b + c) for b, c, v in zip(hi, sizes.cuboid, volume.shape))
    want = volume[_slice(mlo, mhi)].copy()
    want[_slice([a - m for a, m in zip(lo, mlo)],
                [b - m for b, m in zip(hi, mlo)])] = patch
    # the gather is a one-device kernel: it reads a copy on the mesh's
    # first device (a no-op on one chip)
    first = mesh.devices.flat[0]
    for name, how in (("distributed_cutout",
                       lambda p: distributed_cutout(p, grid, mlo, mhi, mesh)),
                      ("cutout_gather on one device",
                       lambda p: cutout_gather(jax.device_put(p, first), grid,
                                               mlo, mhi))):
        got = np.asarray(how(updated))
        check(np.array_equal(got, want),
              f"{name} after distributed_write_cutout {lo}..{hi} differs")
    print(f"distributed_write_cutout {lo}..{hi} read back bit-identical "
          f"(margin {mlo}..{mhi})", flush=True)


def gather_is_compiled(packed, grid: CuboidGrid, box: Box) -> bool:
    """True when the gather lowers to a Mosaic kernel (not interpret mode)."""
    lo, hi = box
    text = jax.jit(lambda p: cutout_gather(p, grid, lo, hi)).lower(
        packed).as_text()
    return "tpu_custom_call" in text


def phase_device_cutouts(volume: np.ndarray, grid: CuboidGrid, sizes: Sizes,
                         boxes, answers, seed: int) -> bool:
    """Device-resident cutouts on one chip. Returns whether the gather ran
    as a compiled kernel."""
    devices = jax.devices()[:1]
    mesh = make_data_mesh(devices)
    packed = place_cuboids(volume, grid, devices)
    compiled = gather_is_compiled(packed, grid, boxes["unaligned"][0])
    same_answers(device_answers(packed, grid, boxes, cutout_gather), answers,
                 "cutout_gather")
    same_answers(device_answers(
        packed, grid, boxes,
        lambda p, g, lo, hi: distributed_cutout(p, g, lo, hi, mesh)),
        answers, "distributed_cutout")
    print("cutout_gather and distributed_cutout: every box bit-identical to "
          f"the front door (gather lowered to a Mosaic kernel: {compiled})",
          flush=True)
    check_write(packed, grid, volume, sizes, mesh, seed)
    return compiled


# -------------------------------------------------------------- phase 3 ----

# jnp.convolve runs at default precision. On the TPU that is one bf16 pass:
# both the operand and the weight are rounded to bf16 (unit roundoff
# u = 2^-9) in each of a blur's 3 separable passes, so one blur is off by at
# most 3 * 2u * max|vol| and the DoG, a difference of two blurs, by twice
# that. The CPU computes in f32, so that bound is the tolerance.
DOG_ULPS = 12 * 2.0 ** -9


def tile_origins(sizes: Sizes) -> List[Tuple[int, ...]]:
    fracs = [(0, 0, 0), (5 / 16, 5 / 16, 1 / 4), (0.53, 0.62, 0.5), (1, 1, 1)]
    return [tuple(min(int(f * v), v - t)
                  for f, v, t in zip(fr, sizes.volume, sizes.tile))
            for fr in fracs]


def phase_detection(cluster: Cluster, volume: np.ndarray, sizes: Sizes):
    """Detection on four tiles cut through the front door. Returns the
    per-tile detection counts and the largest DoG deviation from the CPU."""
    chip, host = jax.devices()[0], jax.devices("cpu")[0]
    counts, worst = [], 0.0
    for lo in tile_origins(sizes):
        hi = tuple(a + t for a, t in zip(lo, sizes.tile))
        tile = cluster.get(lo, hi)
        check(np.array_equal(tile, volume[_slice(lo, hi)]),
              f"tile {lo}..{hi} from the front door differs from the volume")
        x = tile.astype(np.float32)
        dog_dev = np.asarray(difference_of_gaussians(jax.device_put(x, chip)))
        dog_cpu = np.asarray(difference_of_gaussians(jax.device_put(x, host)))
        with jax.default_matmul_precision("highest"):
            dog_hi = np.asarray(difference_of_gaussians(jax.device_put(x, chip)))
        err = float(np.abs(dog_dev - dog_cpu).max())
        err_hi = float(np.abs(dog_hi - dog_cpu).max())
        atol = DOG_ULPS * float(x.max())
        print(f"tile {lo}: DoG max |device - cpu| {err:.4g} at default "
              f"precision, {err_hi:.4g} at 'highest' (tolerance {atol:.4g}, "
              f"DoG range {float(np.abs(dog_cpu).max()):.4g})", flush=True)
        check(err <= atol, f"tile {lo}: DoG deviates {err} > {atol}")
        worst = max(worst, err)

        _, mask = synapse_mask(jax.device_put(tile, chip))
        labels_dev = np.asarray(connected_components(mask))
        labels_cpu = np.asarray(connected_components(jax.device_put(mask, host)))
        check(np.array_equal(labels_dev, labels_cpu),
              f"tile {lo}: connected_components labels differ from the CPU's")
        dets, _ = detect_synapses(tile)
        counts.append(len(dets))
        print(f"tile {lo}: {int(np.asarray(mask).sum())} mask voxels, "
              f"{len(np.unique(labels_dev)) - 1} components (identical to "
              f"the CPU's), {len(dets)} synapse detections", flush=True)
    return counts, worst


# ---------------------------------------------------------- phases 4, 5 ----

# bf16 tolerance for cached decode vs the full forward pass: both paths
# compute the same function, but they round activations to bf16 (2^-8
# relative) at different points in each of the 30 layers; independent
# roundings accumulate like a random walk, about 2^-8 * sqrt(2 * 30) ~ 3%
# of the logit scale, and 2^-4 leaves twice that.
LOGIT_REL_TOL = 2.0 ** -4


def phase_serve(smoke: bool = False, batch: int = 8, prompt: int = 128,
                gen: int = 32, seed: int = 0) -> Dict:
    base = ["--arch", "smollm-135m", "--batch", str(batch),
            "--prompt-len", str(prompt), "--gen", str(gen)]
    base += ["--smoke"] if smoke else []
    out = serve_cli.main(base)
    tokens, logits = np.asarray(out["tokens"]), np.asarray(
        out["logits"], np.float32)
    check(tokens.shape == (batch, gen), f"served tokens {tokens.shape}")
    check(np.isfinite(logits).all(), "served logits are not finite")
    cont = serve_cli.main(base + ["--continuous"])
    fin = cont["finished"]
    check(len(fin) == 2 * batch + 1 and all(len(v) == gen for v in fin.values()),
          f"continuous batching finished {len(fin)} requests")

    # the same model and weights as the server: prefill + one cached decode
    # step against the full forward pass at the last position
    from repro.configs import get_config, get_smoke_config
    from repro.models import build_model, init_params
    cfg = (get_smoke_config if smoke else get_config)("smollm-135m")
    model = build_model(cfg)
    params = init_params(model.specs(), jax.random.key(0))
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab, size=(1, prompt + 1)), jnp.int32)
    _, cache = jax.jit(model.prefill, static_argnames="cache_len")(
        params, toks[:, :prompt], cache_len=prompt + 1)
    dec, _ = jax.jit(model.decode_step)(params, cache, toks[:, prompt:],
                                        jnp.int32(prompt))
    full, _ = jax.jit(model.forward)(params, toks)
    dec = np.asarray(dec[0, -1], np.float32)
    ref = np.asarray(full[0, -1], np.float32)
    check(np.isfinite(dec).all() and np.isfinite(ref).all(),
          "decode or forward logits are not finite")
    scale = float(np.abs(ref).max())
    err = float(np.abs(dec - ref).max())
    print(f"prefill+decode vs forward at position {prompt}: max |diff| "
          f"{err:.4g}, logit scale {scale:.4g} (tolerance "
          f"{LOGIT_REL_TOL * scale:.4g}); argmax {int(dec.argmax())} vs "
          f"{int(ref.argmax())}", flush=True)
    check(err <= LOGIT_REL_TOL * scale,
          f"cached decode deviates from forward: {err} > {LOGIT_REL_TOL * scale}")
    return {"decode_err": err, "logit_scale": scale,
            "occupancy": cont["occupancy"]}


def train_argv(smoke: bool, steps: int, batch: int, seq_len: int) -> List[str]:
    argv = ["--arch", "smollm-135m", "--steps", str(steps),
            "--batch", str(batch), "--seq-len", str(seq_len)]
    return argv + (["--smoke"] if smoke else [])


def phase_train(smoke: bool = False, steps: int = 5, batch: int = 8,
                seq_len: int = 2048) -> List[float]:
    losses = train_cli.main(train_argv(smoke, steps, batch, seq_len))["losses"]
    print("losses:", " ".join(f"{x:.4f}" for x in losses), flush=True)
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    return losses


# ------------------------------------------------------------ four chips ----

def phase_sharded_cutouts(volume: np.ndarray, grid: CuboidGrid, sizes: Sizes,
                          boxes, answers, seed: int) -> None:
    """distributed_cutout / _write over a 4-device data mesh, each device
    holding one curve segment (the paper's §4.1 partition), against
    cutout_gather on device 0 and the front door."""
    devices = jax.devices()[:4]
    mesh = make_data_mesh(devices)
    sharded = place_cuboids(volume, grid, devices)
    shards = sorted(s.device.id for s in sharded.addressable_shards)
    check(len(shards) == 4 and
          all(s.data.shape[0] == grid.n_cells // 4
              for s in sharded.addressable_shards),
          f"cuboid array is not split in 4 curve segments: {shards}")
    single = place_cuboids(volume, grid, devices[:1])
    gathered = device_answers(single, grid, boxes, cutout_gather)
    same_answers(gathered, answers, "cutout_gather on device 0")
    same_answers(device_answers(
        sharded, grid, boxes,
        lambda p, g, lo, hi: distributed_cutout(p, g, lo, hi, mesh)),
        answers, "distributed_cutout on 4 devices")
    print("distributed_cutout over 4 devices: every box bit-identical to "
          "cutout_gather on device 0 and to the front door", flush=True)
    del single
    check_write(sharded, grid, volume, sizes, mesh, seed)


# Loss tolerance between the 2x2 mesh and one device: the step is the same
# function, but the model axis splits the head, MLP and vocab contractions,
# so bf16 partial sums are added in another order (2^-8 relative each), and
# Adam's normalised update turns those differences in near-zero gradient
# components into full-size steps. Over 3 steps that stays well under 1%.
LOSS_REL_TOL = 1e-2


def phase_sharded_train(smoke: bool = False, steps: int = 3, batch: int = 8,
                        seq_len: int = 2048) -> Tuple[List[float], List[float]]:
    argv = train_argv(smoke, steps, batch, seq_len)
    mesh = make_device_mesh()
    check(dict(mesh.shape) == {"data": 2, "model": 2},
          f"mesh from 4 devices is {dict(mesh.shape)}, not 2x2")
    sharded = train_cli.main(argv)["losses"]
    single = train_cli.main(argv, mesh=make_device_mesh(jax.devices()[:1]))[
        "losses"]
    rel = max(abs(a - b) / abs(b) for a, b in zip(sharded, single))
    print(f"train 2x2 losses {sharded} vs 1 device {single}: max rel diff "
          f"{rel:.3g} (tolerance {LOSS_REL_TOL})", flush=True)
    check(all(np.isfinite(sharded)), f"non-finite loss on 2x2: {sharded}")
    check(rel <= LOSS_REL_TOL, f"2x2 and 1-device losses differ by {rel}")
    return sharded, single


# ------------------------------------------------------------------ main ----

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    clock = CompileClock()
    with Phase("phase 0 device", clock):
        device = phase_device(args.chips)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    with Cluster(FULL) as cluster:
        with Phase("phase 1 cluster", clock):
            volume = make_volume(FULL.volume, args.seed)
            sizes, boxes, answers = phase_cluster(cluster, volume, FULL)
            volume = volume[:, :, :sizes.volume[2]]
            readback = read_back(cluster, sizes)
            check(np.array_equal(readback, volume),
                  "volume read back through GET /cutout differs")
        grid = CuboidGrid(sizes.volume, sizes.cuboid)
        if args.chips == 1:
            with Phase("phase 2 device cutouts", clock):
                check(phase_device_cutouts(readback, grid, sizes, boxes,
                                           answers, args.seed),
                      "cutout_gather did not lower to a Mosaic kernel")
            with Phase("phase 3 detection", clock):
                phase_detection(cluster, volume, sizes)
        else:
            with Phase("phase 2 sharded cutouts, 4 chips", clock):
                phase_sharded_cutouts(readback, grid, sizes, boxes, answers,
                                      args.seed)
    del volume, readback
    if args.chips == 1:
        with Phase("phase 4 serve", clock):
            phase_serve(seed=args.seed)
        with Phase("phase 5 train", clock):
            phase_train()
    else:
        with Phase("phase 5 sharded train, 2x2 vs 1 chip", clock):
            phase_sharded_train()
    print(f"total wall {time.perf_counter() - t_start:.2f} s, compile "
          f"{clock.seconds:.2f} s in {clock.programs} programs (persistent "
          f"cache: {clock.hits} hits, {clock.written} written)", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
