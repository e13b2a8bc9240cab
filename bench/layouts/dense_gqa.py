"""Layout of a dense GQA decoder (Qwen3 / Mistral family): the system's
``ModelConfig``, its flat parameter tree made from the seed, each layer's
tensors as the reference gets them, and the useful operations of a
prefill. A configuration file without a ``layout`` key has this one.

The tree handed to the system is its flat parameter tree (``embed``,
``final_norm``, ``lm_head``, ``layers.<name>`` stacked over layers); the
system restacks it into its own per-stage layout inside the same jit.
"""
from __future__ import annotations

import flops
import weights

# Stable ids for ``fold_in``: a tensor's values never depend on which other
# tensors exist. Another layout gives its own tensors ids not used here.
TENSOR_IDS = {
    "embed": 1, "final_norm": 2, "lm_head": 3,
    "ln1": 10, "ln2": 11, "wq": 12, "wk": 13, "wv": 14, "wo": 15,
    "q_norm": 16, "k_norm": 17, "wg": 18, "wu": 19, "wd": 20,
}
NORMS = ("final_norm", "ln1", "ln2", "q_norm", "k_norm")
RESIDUAL = ("wo", "wd")     # write the residual stream


def model_config(c: dict):
    """The system's ModelConfig for configuration file ``c``: the file's
    sizes override the registered architecture's."""
    from repro.configs.base import get_config, replace
    return replace(
        get_config(c["arch"]), num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], head_dim=c["head_dim"],
        qk_norm=bool(c.get("qk_norm")), rope_theta=c["rope_theta"],
        norm_eps=c["rms_norm_eps"],
        tie_embeddings=bool(c.get("tie_word_embeddings")),
        dtype=c["torch_dtype"])


def layer_shapes(c: dict) -> dict:
    """Per-layer tensor shapes of configuration ``c`` (a config file)."""
    d, hd = c["hidden_size"], c["head_dim"]
    h, kvh, f = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["intermediate_size"])
    out = {"ln1": (d,), "ln2": (d,), "wq": (d, h * hd), "wk": (d, kvh * hd),
           "wv": (d, kvh * hd), "wo": (h * hd, d), "wg": (d, f),
           "wu": (d, f), "wd": (f, d)}
    if c.get("qk_norm"):
        out["q_norm"] = (hd,)
        out["k_norm"] = (hd,)
    return out


def global_shapes(c: dict) -> dict:
    d, v = c["hidden_size"], c["vocab_size"]
    out = {"embed": (v, d), "final_norm": (d,)}
    if not c.get("tie_word_embeddings"):
        out["lm_head"] = (d, v)
    return out


def tensor(key, name: str, layer: int, shape, c: dict):
    """One tensor of this layout (one layer's slice for layer tensors), in
    the served dtype."""
    std = weights.residual_std(c) if name in RESIDUAL else weights.STD
    return weights.tensor(key, TENSOR_IDS[name], layer, shape,
                          c["torch_dtype"], std=std, norm=name in NORMS)


def layer(key, c: dict, index: int) -> dict:
    """Layer ``index``'s tensors (the reference's path)."""
    return {n: tensor(key, n, index, s, c) for n, s in layer_shapes(c).items()}


def globals_(key, c: dict) -> dict:
    return {n: tensor(key, n, 0, s, c) for n, s in global_shapes(c).items()}


def flat_params(key, c: dict) -> dict:
    """The whole model in the system's flat layout: ``layers.<name>``
    stacked over ``num_hidden_layers``. Traceable (call it inside jit)."""
    import jax
    import jax.numpy as jnp
    layers = {n: jax.vmap(lambda i, n=n, s=s: tensor(key, n, i, s, c))(
        jnp.arange(c["num_hidden_layers"]))
        for n, s in layer_shapes(c).items()}
    out = globals_(key, c)
    out["layers"] = layers
    return out


def linear_flops_per_token(c: dict) -> float:
    """q, k, v, o projections and the SwiGLU MLP, all layers."""
    d, hd = c["hidden_size"], c["head_dim"]
    h, kvh, f = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["intermediate_size"])
    per_layer = d * (h + 2 * kvh) * hd + h * hd * d + 3 * d * f
    return 2.0 * per_layer * c["num_hidden_layers"]


def attention_flops(c: dict, s: int) -> float:
    """QK^T and PV over the causal triangle, all layers."""
    return (4.0 * c["num_attention_heads"] * c["head_dim"]
            * flops.causal_pairs(s) * attention_layers(c))


def model_flops(c: dict, s: int) -> float:
    """Useful operations of one prefill of ``s`` tokens: every linear layer
    at every position, causal attention, and the output head for the one
    next-token row."""
    return (linear_flops_per_token(c) * s + attention_flops(c, s)
            + 2.0 * c["hidden_size"] * c["vocab_size"])


def attention_layers(c: dict) -> int:
    """Every layer attends."""
    return c["num_hidden_layers"]
