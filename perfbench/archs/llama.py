"""The Llama architecture (SmolLM) as the program builds it.

An ``lm_train`` cell finds its architecture by the stem of its
configuration's ``"reference"`` (``perfbench/refs/llama.py`` -> this
module), which gives:

- ``model_config(cfg)``: the program's ``ModelConfig``;
- ``param_shapes(cfg)``: the program's parameter tree, flat
  ``{path: (shape, kind)}``, kind "matrix" (normal, served dtype) or "norm"
  (ones, float32), from which ``lm_data.make_params`` draws the weights;
- ``reference``: the plain reference (``train``, ``logits``);
- ``train_flops_per_token(cfg, seq_len, counters)``: forward and backward
  FLOPs per trained token, given the means over the window's steps of the
  step's own scalar outputs, for an architecture whose work depends on the
  data;
- ``SCOPES`` and ``LAYERS``: the named scopes and the ``hlo_scopes.Rule``
  list that give each device operation of the step its layer.
"""
from __future__ import annotations

from typing import Dict, Tuple

from ..lib import roofline
from ..lib.hlo_scopes import Rule
from ..refs import llama as reference  # noqa: F401  (the adapter's reference)

SCOPES = ("attention", "mlp", "head", "optimizer")

LAYERS = (
    Rule("head", "repro/models/lm.py", ("LM.embed_tokens", "LM.logits")),
    Rule("head", "repro/train/train_step.py", ("loss_fn",)),
    Rule("optimizer", "repro/optim/adamw.py"),
    Rule("attention", "repro/models/attention.py"),
    Rule("attention", "repro/models/layers.py",
         ("blockwise_attention", "rotary")),
    Rule("mlp", "repro/models/layers.py", ("mlp",)),
    Rule("layers", "repro/models/lm.py", ("LM._block_fwd", "LM.forward")),
    # the layer scan's own slicing and stacking, which JAX gives no frame
    Rule("layers", component="while"),
)


def model_config(cfg: Dict):
    """The program's ``ModelConfig`` for a Llama-architecture file."""
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        act={"silu": "swiglu"}[cfg["hidden_act"]],
        tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        dtype=cfg["torch_dtype"])


def param_shapes(cfg: Dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """Flat {path: (shape, kind)} of the program's dense-LM parameter tree."""
    L, d, f = cfg["num_hidden_layers"], cfg["hidden_size"], \
        cfg["intermediate_size"]
    H, K, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    return {
        "embed": ((cfg["vocab_size"], d), "matrix"),
        "final_norm": ((d,), "norm"),
        "blocks/ln1": ((L, d), "norm"),
        "blocks/attn/w_q": ((L, d, H * hd), "matrix"),
        "blocks/attn/w_k": ((L, d, K * hd), "matrix"),
        "blocks/attn/w_v": ((L, d, K * hd), "matrix"),
        "blocks/attn/w_o": ((L, H * hd, d), "matrix"),
        "blocks/ln2": ((L, d), "norm"),
        "blocks/mlp/w_gate": ((L, d, f), "matrix"),
        "blocks/mlp/w_up": ((L, d, f), "matrix"),
        "blocks/mlp/w_down": ((L, f, d), "matrix"),
    }


def train_flops_per_token(cfg: Dict, seq_len: int,
                          counters: Dict[str, float]) -> float:
    """``roofline.llama_train_flops_per_token``: a dense model's work does
    not depend on the data, so the step's counters are not read."""
    return roofline.llama_train_flops_per_token(cfg, seq_len)
