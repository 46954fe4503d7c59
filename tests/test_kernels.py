"""Per-kernel allclose vs pure-jnp oracles, swept over shapes/dtypes.

All kernels execute in interpret mode on CPU (interpret=None picks it off
the TPU). Interpret mode checks results, not whether Mosaic accepts a
kernel: tests/test_tpu_compile.py compiles the main-path kernels for a
described TPU v5e, and chip_smoke.py runs them compiled on the chip.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core.cuboid import CuboidGrid
from repro.core.distributed import pack_to_cuboids
from repro.kernels.cutout_gather.ops import cutout_gather
from repro.kernels.cutout_gather.ref import cutout_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.morton_matmul.ops import morton_matmul, panel_traffic
from repro.kernels.morton_matmul.ref import matmul_ref
from repro.models.layers import blockwise_attention

RNG = np.random.default_rng(42)


def rand(shape, dtype):
    x = RNG.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype=dtype)


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(
        atol=2e-5, rtol=2e-5)


# ------------------------------------------------- flash attention sweep ----

ATTN_SHAPES = [
    # (B, Sq, Skv, H, K, D)
    (1, 64, 64, 4, 4, 64),     # MHA square
    (2, 128, 128, 8, 2, 64),   # GQA
    (1, 96, 96, 4, 1, 128),    # MQA, non-pow2 seq (padding path)
    (1, 32, 128, 4, 2, 64),    # cross/prefix: fewer q than kv
    (2, 64, 64, 4, 4, 256),    # big head dim (gemma-style)
]


@pytest.mark.parametrize("shape", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 48)])
def test_flash_attention_vs_ref(shape, dtype, causal, window):
    B, Sq, Skv, H, K, D = shape
    q = rand((B, Sq, H, D), dtype)
    k = rand((B, Skv, K, D), dtype)
    v = rand((B, Skv, K, D), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=32, block_kv=32)
    want = attention_ref(q, k, v, causal=causal, scale=D ** -0.5,
                         window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def test_flash_attention_matches_blockwise_jnp():
    """Kernel == the jnp blockwise path used for roofline dry-runs."""
    B, S, H, K, D = 2, 128, 8, 4, 64
    q, k, v = rand((B, S, H, D), jnp.float32), rand(
        (B, S, K, D), jnp.float32), rand((B, S, K, D), jnp.float32)
    a = flash_attention(q, k, v, causal=True, block_q=32, block_kv=64)
    b = blockwise_attention(q, k, v, causal=True, scale=D ** -0.5,
                            block_q=32, block_kv=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("skip", [True, False])
def test_blockwise_attention_grads_match_ref(skip):
    """The training path's gradients, with the running max held constant,
    equal the dense softmax reference's (multi-block, causal)."""
    B, S, H, K, D = 2, 96, 4, 2, 32
    q, k, v = (rand((B, S, n, D), jnp.float32) for n in (H, K, K))

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    got = jax.grad(loss(lambda q, k, v: blockwise_attention(
        q, k, v, causal=True, scale=D ** -0.5, block_q=32, block_kv=32,
        skip_masked_blocks=skip)), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: attention_ref(
        q, k, v, causal=True, scale=D ** -0.5)), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4,
                                   rtol=1e-4)


# --------------------------------------------------- morton matmul sweep ----

MM_SHAPES = [(256, 128, 256), (512, 256, 512), (128, 128, 128),
             (384, 256, 128),  # non-pow2 tile grid (clamped curve cells)
             (256, 96, 200)]   # padding path


@pytest.mark.parametrize("mnk", MM_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("order", ["morton", "hilbert", "rowmajor"])
def test_morton_matmul_vs_ref(mnk, dtype, order):
    M, N, K = mnk
    a = rand((M, K), dtype)
    b = rand((K, N), dtype)
    got = morton_matmul(a, b, block_m=128, block_n=128, block_k=64,
                        order=order)
    want = matmul_ref(a, b)
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    rel = np.abs(got - want) / (np.abs(want) + 1.0)
    assert rel.max() < (3e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_curve_traversal_panel_traffic():
    """The locality claims (paper §3 Hilbert-vs-Morton trade-off, adapted
    to VMEM panel reuse):
      - capacity=1 (Pallas consecutive-DMA-skip): Hilbert optimal — every
        step changes exactly ONE coordinate; Morton actually loses.
      - capacity>=2 (explicit panel cache / GPU L2 swizzle): Morton beats
        row-major by ~2x on square grids.
    """
    for nm, nn in [(8, 8), (16, 16), (32, 32)]:
        ht1 = panel_traffic(nm, nn, "hilbert", capacity=1)
        rt1 = panel_traffic(nm, nn, "rowmajor", capacity=1)
        zt1 = panel_traffic(nm, nn, "morton", capacity=1)
        assert ht1 == nm * nn + 1          # provably optimal
        assert ht1 < rt1 < zt1, (nm, nn, ht1, rt1, zt1)
        zt4 = panel_traffic(nm, nn, "morton", capacity=4)
        rt4 = panel_traffic(nm, nn, "rowmajor", capacity=4)
        assert zt4 < rt4, (nm, nn, zt4, rt4)
    assert (panel_traffic(32, 32, "rowmajor", 4)
            / panel_traffic(32, 32, "morton", 4)) > 1.4


def test_hilbert_decode_properties():
    from repro.core.morton import hilbert_decode_2d
    import numpy as np
    for order in (1, 2, 3, 4):
        n = 1 << (2 * order)
        xs, ys = hilbert_decode_2d(np.arange(n), order)
        # bijective onto the grid
        assert len({(int(x), int(y)) for x, y in zip(xs, ys)}) == n
        # unit-step: consecutive cells are grid neighbors (the property
        # Morton lacks and the paper cites as Hilbert's advantage)
        d = np.abs(np.diff(xs)) + np.abs(np.diff(ys))
        assert (d == 1).all()


# --------------------------------------------------- cutout gather sweep ----


@pytest.mark.parametrize("dtype", ["float32", "uint8"])
@pytest.mark.parametrize("box", [((0, 0, 0), (32, 32, 16)),
                                 ((8, 16, 8), (40, 48, 16)),
                                 ((5, 3, 2), (37, 45, 14))])  # unaligned
def test_cutout_gather_vs_ref(dtype, box):
    grid = CuboidGrid((64, 64, 32), (8, 8, 8))
    vol = RNG.integers(0, 200, size=grid.volume_shape).astype(dtype)
    packed = jnp.asarray(pack_to_cuboids(vol, grid))
    lo, hi = box
    got = cutout_gather(packed, grid, lo, hi)
    want = cutout_ref(packed, grid, lo, hi)
    np.testing.assert_array_equal(np.asarray(got), want)


@given(data=st.data())
@settings(max_examples=20, deadline=None)
def test_cutout_gather_property(data):
    grid = CuboidGrid((32, 32, 16), (8, 8, 4))
    vol = RNG.integers(0, 255, size=grid.volume_shape).astype(np.int32)
    packed = jnp.asarray(pack_to_cuboids(vol, grid))
    lo = [data.draw(st.integers(0, s - 1)) for s in grid.volume_shape]
    hi = [data.draw(st.integers(l + 1, s))
          for l, s in zip(lo, grid.volume_shape)]
    got = cutout_gather(packed, grid, lo, hi)
    want = vol[tuple(slice(l, h) for l, h in zip(lo, hi))]
    np.testing.assert_array_equal(np.asarray(got), want)


# ------------------------------------------------------- ssd scan sweep ----

from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_ref

SSD_SHAPES = [
    # (B, S, H, P, N, chunk)
    (1, 64, 2, 32, 32, 32),     # two chunks
    (2, 128, 4, 64, 64, 32),    # four chunks, wider
    (1, 96, 2, 32, 64, 32),     # S multiple of chunk, N > P
    (1, 80, 3, 16, 32, 32),     # padding path (80 % 32 != 0)
    (2, 64, 2, 64, 128, 64),    # single chunk == mamba2-370m N
]


@pytest.mark.parametrize("shape", SSD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_vs_ref(shape, dtype):
    B, S, H, P, N, chunk = shape
    x = rand((B, S, H, P), dtype)
    dt = jax.nn.softplus(rand((B, S, H), jnp.float32))
    A = -jnp.exp(rand((H,), jnp.float32) * 0.5)
    Bm = rand((B, S, N), dtype)
    Cm = rand((B, S, N), dtype)
    y, s = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    y_ref, s_ref = ssd_ref(x, dt, A, Bm, Cm)
    # chunked vs fully-quadratic associate differently: allow fp32 drift
    t = tol(dtype) if dtype == jnp.bfloat16 else dict(atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), **t)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), **t)


def test_ssd_scan_matches_model_chunked():
    """Kernel == the jnp chunked path used by models/ssm.py."""
    from repro.models.ssm import _ssd_chunked
    B, S, H, P, N, chunk = 2, 128, 4, 32, 64, 32
    x = rand((B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(rand((B, S, H), jnp.float32))
    A = -jnp.exp(rand((H,), jnp.float32) * 0.5)
    Bm = rand((B, S, N), jnp.float32)
    Cm = rand((B, S, N), jnp.float32)
    y_k, s_k = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    y_m, s_m = _ssd_chunked(x, dt, A, Bm, Cm, chunk)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_m),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_m),
                               atol=2e-5, rtol=2e-5)


def test_ssm_block_kernel_flag_equivalence():
    """ssm_block(use_ssd_kernel=True) == ssm_block(False) end to end."""
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config("mamba2_370m").scaled(
        n_layers=2, d_model=64, ssm_state=32, ssm_head_dim=16,
        vocab=128, ssm_chunk=16, dtype="float32")
    from repro.models.params import init_params
    model = build_model(cfg)
    params = init_params(model.specs(), jax.random.key(0))
    tokens = jnp.asarray(RNG.integers(0, 128, size=(2, 48)), jnp.int32)
    logits_jnp, _ = model.forward(params, tokens)
    cfg_k = cfg.scaled(use_ssd_kernel=True)
    model_k = build_model(cfg_k)
    logits_k, _ = model_k.forward(params, tokens)
    np.testing.assert_allclose(np.asarray(logits_jnp),
                               np.asarray(logits_k), atol=1e-4, rtol=1e-4)


@given(data=st.data())
@settings(max_examples=10, deadline=None)
def test_ssd_scan_property(data):
    """Property: kernel matches quadratic oracle on random small shapes."""
    B = data.draw(st.integers(1, 2))
    H = data.draw(st.integers(1, 3))
    P = data.draw(st.sampled_from([8, 16, 32]))
    N = data.draw(st.sampled_from([16, 32]))
    chunk = data.draw(st.sampled_from([8, 16]))
    S = data.draw(st.integers(8, 72))
    x = rand((B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(rand((B, S, H), jnp.float32))
    A = -jnp.exp(rand((H,), jnp.float32) * 0.5)
    Bm = rand((B, S, N), jnp.float32)
    Cm = rand((B, S, N), jnp.float32)
    y, s = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    y_ref, s_ref = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               atol=3e-5, rtol=3e-5)


# ---------------------------------------------------- flash decode sweep ----

from repro.kernels.flash_decode.ops import flash_decode
from repro.models.layers import decode_attention

FD_SHAPES = [
    # (B, S, H, K, D, cache_len, block_kv)
    (2, 128, 8, 2, 64, 128, 32),    # full cache
    (1, 256, 4, 4, 64, 100, 64),    # partial cache (masking)
    (2, 96, 4, 1, 128, 50, 32),     # MQA, non-pow2 S (padding path)
    (1, 64, 8, 8, 64, 1, 64),       # single valid position
]


@pytest.mark.parametrize("shape", FD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_vs_ref(shape, dtype):
    B, S, H, K, D, clen, bkv = shape
    q = rand((B, 1, H, D), dtype)
    kc = rand((B, S, K, D), dtype)
    vc = rand((B, S, K, D), dtype)
    got = flash_decode(q, kc, vc, clen, scale=D ** -0.5, block_kv=bkv)
    want = decode_attention(q, kc, vc, clen, scale=D ** -0.5)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def test_flash_decode_per_batch_lens():
    """Per-sequence cache lengths (continuous batching) mask correctly."""
    B, S, H, K, D = 3, 64, 4, 2, 64
    q = rand((B, 1, H, D), jnp.float32)
    kc = rand((B, S, K, D), jnp.float32)
    vc = rand((B, S, K, D), jnp.float32)
    lens = jnp.asarray([5, 33, 64], jnp.int32)
    got = flash_decode(q, kc, vc, lens, scale=D ** -0.5, block_kv=16)
    want = decode_attention(q, kc, vc, lens, scale=D ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ------------------------------------------------------- moe gemm sweep ----

from repro.kernels.moe_gemm.ops import moe_gemm
from repro.kernels.moe_gemm.ref import moe_gemm_ref

MG_SHAPES = [
    # (E, C, d, f, block_c)
    (4, 64, 32, 16, 32),      # even tiles
    (8, 96, 64, 32, 32),      # imbalanced counts
    (2, 50, 32, 64, 16),      # padding path (50 % 16 != 0)
    (32, 40, 64, 32, 8),      # granite-like: many tiny experts
]


@pytest.mark.parametrize("shape", MG_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gemm_vs_ref(shape, dtype):
    E, C, d, f, bc = shape
    x = rand((E, C, d), dtype)
    wg = rand((E, d, f), dtype)
    wu = rand((E, d, f), dtype)
    wd = rand((E, f, d), dtype)
    counts = jnp.asarray(RNG.integers(0, C + 1, size=(E,)), jnp.int32)
    # zero out buffer rows past counts (as the dispatch would leave them)
    mask = jnp.arange(C)[None, :] < counts[:, None]
    x = x * mask[..., None].astype(x.dtype)
    got = moe_gemm(x, wg, wu, wd, counts, block_c=bc)
    want = moe_gemm_ref(x, wg, wu, wd, counts)
    # intermediates are O(d*sqrt(f)) with cancellation in y: scale-aware tol
    t = (dict(atol=1e-3, rtol=1e-3) if dtype == jnp.float32
         else dict(atol=5e-2, rtol=5e-2))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **t)


def test_moe_gemm_skips_match_dense_einsum():
    """Kernel == the einsum path inside models.moe (zero-padded rows)."""
    E, C, d, f = 4, 32, 32, 16
    x = rand((E, C, d), jnp.float32)
    counts = jnp.asarray([32, 10, 0, 25], jnp.int32)
    mask = jnp.arange(C)[None, :] < counts[:, None]
    x = x * mask[..., None]
    wg, wu, wd = rand((E, d, f), jnp.float32), rand(
        (E, d, f), jnp.float32), rand((E, f, d), jnp.float32)
    got = moe_gemm(x, wg, wu, wd, counts, block_c=8)
    g = jnp.einsum("ecd,edf->ecf", x, wg)
    u = jnp.einsum("ecd,edf->ecf", x, wu)
    h = jax.nn.silu(g) * u
    want = jnp.einsum("ecf,efd->ecd", h, wd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
