"""Compile the device path at real widths for a described TPU v5e.

Nothing runs: each program is lowered and compiled by the TPU compiler for
a chip that is described, not attached, so a kernel the chip would refuse
(a block shape off the tiling, an accumulator the MXU cannot hold, a
program that does not fit) fails here, with no chip. Interpret-mode tests
cannot see any of that.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.cuboid import CuboidGrid
from repro.kernels.cutout_gather.ops import cutout_gather
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_decode.ops import flash_decode
from repro.models import build_model
from repro.models.params import tree_map_specs
from repro.serve import make_serve_step
from repro.vision.synapse_detector import _connected_components_sweeps


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep such entries out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def test_cutout_gather_compiles_unaligned_box(one_chip):
    """The paper's 128x128x16 uint8 cuboids; a box spanning 4x4x4 of them,
    unaligned on every axis. The gather must read the 1 GiB cuboid-major
    array in place: no relayout copy of it may appear as temp memory."""
    grid = CuboidGrid((2048, 2048, 256), (128, 128, 16))
    packed = _sds((grid.n_cells, 128, 128, 16), "uint8", one_chip)
    lo, hi = (64, 192, 8), (448, 576, 56)
    compiled = jax.jit(
        lambda p: cutout_gather(p, grid, lo, hi, interpret=False)
    ).lower(packed).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == 384 * 384 * 48
    assert mem.temp_size_in_bytes < grid.n_cells * 128 * 128 * 16 // 64


def test_connected_components_compiles(one_chip):
    mask = _sds((512, 512, 16), "bool", one_chip)
    compiled = _connected_components_sweeps.lower(mask).compile()
    # the int32 labels, and the int32 sweep count in one 1 KiB padded buffer
    out = compiled.memory_analysis().output_size_in_bytes
    assert out == 512 * 512 * 16 * 4 + 1024


def test_smollm_serve_step_compiles(one_chip):
    """SmolLM-135M at published widths, B=8 against a 2,048-entry cache."""
    cfg = get_config("smollm-135m")
    model = build_model(cfg)

    def abstract(specs):
        return tree_map_specs(lambda s: _sds(s.shape, s.dtype, one_chip), specs)

    params = abstract(model.specs())
    cache = abstract(model.cache_specs(8, 2048))
    token = _sds((8, 1), "int32", one_chip)
    index = _sds((), "int32", one_chip)
    compiled = jax.jit(make_serve_step(model, cfg), donate_argnums=(1,)).lower(
        params, cache, token, index).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_flash_attention_compiles_smollm_widths(one_chip):
    q = _sds((8, 2048, 9, 64), "bfloat16", one_chip)
    kv = _sds((8, 2048, 3, 64), "bfloat16", one_chip)
    compiled = flash_attention.lower(q, kv, kv, causal=True,
                                     interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_decode_compiles_smollm_widths(one_chip):
    """One new query token per sequence against the (8, 2048, 3, 64) cache."""
    q = _sds((8, 1, 9, 64), "bfloat16", one_chip)
    kv = _sds((8, 2048, 3, 64), "bfloat16", one_chip)
    cache_len = _sds((), "int32", one_chip)
    compiled = flash_decode.lower(q, kv, kv, cache_len, scale=64 ** -0.5,
                                  interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
