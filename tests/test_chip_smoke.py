"""The chip smoke's phases at a tiny size on the CPU, its refusal to run
without a TPU, and the meshes built from the devices present.

On the CPU the Pallas kernels run in interpret mode and the "chip" is the
CPU device, so the comparisons here check control flow and data handling;
the smoke itself proves the compiled path on the chip.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from repro.core.cuboid import CuboidGrid  # noqa: E402

TINY = chip_smoke.Sizes((128, 128, 32), (32, 32, 8), (64, 64, 16))


def _child_env(**extra):
    return {"PYTHONPATH": os.path.join(REPO, "src"), "PATH": "/usr/bin:/bin",
            "JAX_PLATFORMS": "cpu", "HOME": os.environ.get("HOME", REPO),
            **extra}


@pytest.fixture(scope="module")
def loaded():
    vol = chip_smoke.make_volume(TINY.volume, seed=3)
    with chip_smoke.Cluster(TINY) as cluster:
        sizes, boxes, answers = chip_smoke.phase_cluster(cluster, vol, TINY)
        assert sizes == TINY
        yield cluster, vol, boxes, answers


def test_make_volume_is_seeded_and_em_like():
    a = chip_smoke.make_volume(TINY.volume, seed=1)
    assert a.dtype == np.uint8 and a.shape == TINY.volume
    np.testing.assert_array_equal(a, chip_smoke.make_volume(TINY.volume, 1))
    assert not np.array_equal(a, chip_smoke.make_volume(TINY.volume, 2))
    assert 95 < np.median(a) < 105          # grey background
    assert a.max() > 180                    # bright blobs and the vessel


def test_query_boxes_cross_cuboids_and_stay_inside():
    boxes = chip_smoke.query_boxes(TINY)
    assert [len(boxes[k]) for k in ("aligned", "unaligned", "batch", "xy")] == [
        1, 1, 4, 1]
    (lo, hi), = boxes["unaligned"]
    for a, b, c in zip(lo, hi, TINY.cuboid):
        assert a % c and b % c and b // c > a // c
    for lo, hi in sum(boxes.values(), []) + [chip_smoke.write_box(TINY)]:
        assert all(0 <= a < b <= v for a, b, v in zip(lo, hi, TINY.volume))


def test_cluster_phase_answers_match_volume(loaded):
    cluster, vol, boxes, answers = loaded
    (lo, hi), = boxes["xy"]
    np.testing.assert_array_equal(answers["xy"][0], vol[lo[0]:hi[0],
                                                        lo[1]:hi[1], lo[2]])
    np.testing.assert_array_equal(chip_smoke.read_back(cluster, TINY), vol)


def test_slow_load_halves_z_once():
    deep = chip_smoke.Sizes((128, 128, 64), TINY.cuboid, TINY.tile)
    vol = chip_smoke.make_volume(deep.volume, seed=4)
    with chip_smoke.Cluster(deep) as cluster:
        # answers are checked against the loaded half inside the phase
        sizes, boxes, _ = chip_smoke.phase_cluster(cluster, vol, deep,
                                                   budget_s=0.0)
        assert sizes.volume == (128, 128, 32)
        np.testing.assert_array_equal(chip_smoke.read_back(cluster, sizes),
                                      vol[:, :, :32])
    assert all(hi[2] <= 32 for _, hi in sum(boxes.values(), []))


def test_device_cutout_phase_on_cpu(loaded):
    _, vol, boxes, answers = loaded
    grid = CuboidGrid(TINY.volume, TINY.cuboid)
    # every comparison holds; interpret mode is not a compiled kernel
    assert chip_smoke.phase_device_cutouts(vol, grid, TINY, boxes, answers,
                                           seed=3) is False


def test_detection_phase_on_cpu(loaded):
    cluster, vol, _, _ = loaded
    counts, worst = chip_smoke.phase_detection(cluster, vol, TINY)
    assert len(counts) == 4 and worst == 0.0


def test_serve_and_train_phases_smoke_config():
    out = chip_smoke.phase_serve(smoke=True, batch=2, prompt=8, gen=4)
    assert out["decode_err"] <= chip_smoke.LOGIT_REL_TOL * out["logit_scale"]
    losses = chip_smoke.phase_train(smoke=True, steps=8, batch=4, seq_len=32)
    assert len(losses) == 8


def test_smoke_refuses_cpu_and_does_no_work():
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300,
                       cwd=REPO, env=_child_env())
    assert r.returncode != 0
    assert "not a TPU" in r.stderr
    assert "[phase 1" not in r.stdout and '"ok"' not in r.stdout


FOUR_DEVICES = r"""
import json, sys
sys.path.insert(0, {repo!r})
import jax
import chip_smoke
from repro.core.cuboid import CuboidGrid
from repro.launch import train
from repro.launch.mesh import make_device_mesh

mesh = make_device_mesh()
assert dict(mesh.shape) == {{"data": 2, "model": 2}}, mesh.shape
assert all(str(t) == "AxisType.Auto" for t in mesh.axis_types), mesh.axis_types
# one training step on the 2x2 mesh built from the devices present
train.main(["--arch", "smollm-135m", "--smoke", "--steps", "1",
            "--batch", "4", "--seq-len", "32"])

tiny = chip_smoke.Sizes((128, 128, 32), (32, 32, 8), (64, 64, 16))
vol = chip_smoke.make_volume(tiny.volume, 5)
with chip_smoke.Cluster(tiny) as cluster:
    _, boxes, answers = chip_smoke.phase_cluster(cluster, vol, tiny)
chip_smoke.phase_sharded_cutouts(vol, CuboidGrid(tiny.volume, tiny.cuboid),
                                 tiny, boxes, answers, 5)
sharded, single = chip_smoke.phase_sharded_train(smoke=True, steps=2,
                                                 batch=4, seq_len=32)
print(json.dumps({{"sharded": sharded, "single": single}}))
"""


def test_four_device_mesh_paths():
    """4 virtual CPU devices: the mesh from the devices present is 2x2 with
    Auto axes, a training step runs on it (no sharding-type error), and the
    smoke's four-chip phases hold at a tiny size."""
    r = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICES.format(repo=REPO)],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env=_child_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    assert "ShardingTypeError" not in r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert len(out["sharded"]) == len(out["single"]) == 2


def test_make_device_mesh_one_device():
    from repro.launch.mesh import make_device_mesh
    mesh = make_device_mesh(jax.devices()[:1])
    assert dict(mesh.shape) == {"data": 1, "model": 1}


def test_compile_cache_location(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; unset, the
    cache goes to the fixed in-checkout path, which git ignores."""
    from repro.launch.cache import REPO_CACHE_DIR, enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == "/elsewhere/cache"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert REPO_CACHE_DIR.parent == Path(REPO)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
