"""The plain references against the program, on the CPU at tiny sizes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.archs import llama as arch
from perfbench.lib import lm_data
from perfbench.lib.volume import make_volume
from perfbench.refs import llama, vision

TINY = {"name": "tiny", "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
        "hidden_act": "silu", "tie_word_embeddings": True,
        "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
        "torch_dtype": "float32"}


def make_params(cfg, seed):
    return lm_data.make_params(arch.param_shapes(cfg), cfg["torch_dtype"],
                               seed)


def tile():
    return make_volume((64, 64, 16), 3, 0)[0]


def test_dog_reference_matches_the_program():
    from repro.vision.synapse_detector import difference_of_gaussians
    x = jnp.asarray(tile(), jnp.float32)
    want = vision.dog(x, (1.0, 1.0, 0.5), (3.0, 3.0, 1.5), 4)
    assert vision.relative_gap(difference_of_gaussians(x), want) < 1e-5


def test_fp8_dog_is_far_from_the_reference():
    x = jnp.asarray(tile(), jnp.float32)
    args = ((1.0, 1.0, 0.5), (3.0, 3.0, 1.5), 4)
    gap = vision.relative_gap(vision.dog(x, *args, "fp8"), vision.dog(x, *args))
    assert gap > 0.02


def test_labelling_matches_connected_components():
    from repro.vision.synapse_detector import connected_components, synapse_mask
    _, mask = synapse_mask(tile())
    mask = np.asarray(mask)
    got = np.asarray(connected_components(jnp.asarray(mask)))
    assert mask.sum() > 0
    assert vision.partition_mismatch(got, vision.label(mask)) == 0


def test_partition_mismatch_counts_split_merged_and_moved_voxels():
    a = np.array([1, 1, 0, 2, 2, 3])
    assert vision.partition_mismatch(a, np.array([7, 7, 0, 5, 5, 9])) == 0
    assert vision.partition_mismatch(a, np.array([7, 7, 0, 7, 7, 9])) == 4
    assert vision.partition_mismatch(a, np.array([7, 8, 0, 5, 5, 9])) == 2
    assert vision.partition_mismatch(a, np.array([7, 7, 4, 5, 5, 0])) == 2


def test_llama_reference_matches_the_program_forward_and_gradient():
    from repro.models import build_model
    from repro.train.train_step import loss_fn
    mcfg = arch.model_config(TINY)
    model = build_model(mcfg)
    params = make_params(TINY, 5)
    toks = lm_data.zipf_tokens(5, (2, 33), TINY["vocab_size"])
    tokens, labels = toks[:, :-1], toks[:, 1:]
    got, _ = model.forward(params, jnp.asarray(tokens))
    for b in range(2):
        want = llama.logits(TINY, "f32", params, jnp.asarray(tokens[b]))
        np.testing.assert_allclose(got[b], want, atol=1e-4, rtol=1e-4)
    batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}
    (loss, _), grads = jax.value_and_grad(
        lambda p: loss_fn(model, p, batch, mcfg), has_aux=True)(params)
    ref_loss, ref_grads = llama.loss_and_grad(TINY, params, tokens, labels)
    assert float(loss) == pytest.approx(ref_loss, rel=1e-5)
    for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        np.testing.assert_allclose(g, r, atol=1e-5, rtol=1e-3)


def test_adamw_reference_matches_the_program_update():
    from repro.optim import AdamWConfig, adamw_update
    opt = {"lr_peak": 3e-3, "warmup_steps": 5, "total_steps": 100,
           "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
           "clip_norm": 1.0}
    params = make_params(TINY, 6)
    grads = jax.tree.map(lambda p: jnp.full_like(p, 0.5), params)
    state = {"params": params, "mu": jax.tree.map(jnp.zeros_like, params),
             "nu": jax.tree.map(jnp.zeros_like, params)}
    want, _ = llama.adamw(opt, state, grads, 1)
    pstate = {"mu": state["mu"], "nu": state["nu"], "master": params,
              "step": jnp.zeros((), jnp.int32)}
    _, got, _ = adamw_update(AdamWConfig(**opt), grads, pstate, params)
    for g, w in zip(jax.tree.leaves(got["master"]),
                    jax.tree.leaves(want["params"])):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-7)


def test_exclusion_reference_matches_the_program():
    from repro.vision.synapse_detector import large_structure_mask
    low = jnp.asarray(make_volume((64, 64, 16), 4, 0)[1], jnp.float32)
    got = large_structure_mask(low, sigma=(6.0, 6.0, 3.0), radius=8,
                               quantile=0.9)
    smooth, q = vision.exclusion_smooth(low, (6.0, 6.0, 3.0), 8, 0.9)
    assert 0.05 < float(jnp.mean(got)) < 0.15
    assert float(vision.edge_gap(got, smooth, q)) < 1e-3
    assert float(vision.edge_gap(~got, smooth, q)) > 0.01


def test_res0_voxels_take_their_low_voxel():
    low = np.arange(4 * 4 * 2).reshape(4, 4, 2)
    got = vision.at_res0(low, (2, 4, 1), (6, 8, 2), 2)
    assert got.shape == (4, 4, 1)
    assert got[0, 0, 0] == low[1, 2, 1] and got[3, 3, 0] == low[2, 3, 1]


def test_flip_edge_reads_the_farthest_differing_voxel():
    resp = np.array([1.0, 2.5, 4.0, 1.9])
    want = resp > 2.0
    assert vision.flip_edge(want, want, resp, 2.0, True) == 0.0
    got = np.array([False, True, False, True])
    assert vision.flip_edge(got, want, resp, 2.0, True) == pytest.approx(0.5)
    where = np.array([True, True, False, True])
    assert vision.flip_edge(got, want, resp, 2.0, where) == pytest.approx(
        0.025)
    assert vision.zscore(resp).std() == pytest.approx(1.0, abs=1e-6)
