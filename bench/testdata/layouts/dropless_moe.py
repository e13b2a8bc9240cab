"""Layout of a decoder whose every layer is GQA attention followed by a
dropless top-k mixture of experts (Qwen3-MoE's keys: ``num_experts``,
``num_experts_per_tok``, ``moe_intermediate_size``, ``norm_topk_prob``;
no shared expert). A fixture of the benchmark's tests: a parameter tree
other than the dense one, entering through files only.

The attention tensors, norms, embedding and head are the dense layout's,
under its ids; the router and the experts take ids the dense layout does
not use. An expert's tensor is folded in by its expert index, so a model
cut to fewer experts holds the whole model's.
"""
from __future__ import annotations

import flops
import lookup
import weights

DENSE = lookup.module("layouts", "dense_gqa")
DENSE_MLP = ("wg", "wu", "wd")
TENSOR_IDS = dict(
    {n: i for n, i in DENSE.TENSOR_IDS.items() if n not in DENSE_MLP},
    router=40, e_wg=41, e_wu=42, e_wd=43)
EXPERT = ("e_wg", "e_wu", "e_wd")


def model_config(c: dict):
    """The system's MoE ModelConfig, every field from the file."""
    from repro.configs.base import ModelConfig, MoEConfig
    e, k = c["num_experts"], c["num_experts_per_tok"]
    if not c["norm_topk_prob"]:
        raise ValueError("the system renormalises the top-k gates (a "
                         "softmax over the k chosen logits): "
                         "norm_topk_prob must be true")
    return ModelConfig(
        arch=c["arch"], family="moe", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        d_ff=c["moe_intermediate_size"], vocab_size=c["vocab_size"],
        head_dim=c["head_dim"], qk_norm=bool(c.get("qk_norm")),
        tie_embeddings=bool(c.get("tie_word_embeddings")),
        rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
        # A chunk of C tokens sends C * k picks over e experts; the system
        # gives each expert ceil(C * k / e * capacity_factor) slots. At
        # e / k that is C: every token of the chunk fits in any one
        # expert, and a token picks an expert at most once, so none is
        # dropped, as in the published model and the reference.
        moe=MoEConfig(num_experts=e, top_k=k,
                      d_expert=c["moe_intermediate_size"],
                      capacity_factor=e / k),
        dtype=c["torch_dtype"])


def layer_shapes(c: dict) -> dict:
    """Per-layer shapes; an expert tensor's is one expert's."""
    d, e, f = (c["hidden_size"], c["num_experts"],
               c["moe_intermediate_size"])
    out = {n: s for n, s in DENSE.layer_shapes(c).items()
           if n not in DENSE_MLP}
    out.update(router=(d, e), e_wg=(d, f), e_wu=(d, f), e_wd=(f, d))
    return out


def tensor(key, name: str, layer: int, shape, c: dict, expert=None):
    std = (weights.residual_std(c) if name in DENSE.RESIDUAL + ("e_wd",)
           else weights.STD)
    return weights.tensor(key, TENSOR_IDS[name], layer, shape,
                          c["torch_dtype"], std=std, norm=name in DENSE.NORMS,
                          expert=expert)


def _made(key, c, layer, name, shape):
    """One layer's tensor; an expert tensor stacked over the experts."""
    import jax
    import jax.numpy as jnp
    if name not in EXPERT:
        return tensor(key, name, layer, shape, c)
    return jax.vmap(lambda e: tensor(key, name, layer, shape, c, e))(
        jnp.arange(c["num_experts"]))


def layer(key, c: dict, index: int) -> dict:
    """Layer ``index``'s tensors (the reference's path); expert tensors
    as [num_experts, ...]."""
    return {n: _made(key, c, index, n, s) for n, s in layer_shapes(c).items()}


def globals_(key, c: dict) -> dict:
    return DENSE.globals_(key, c)


def flat_params(key, c: dict) -> dict:
    """The system's flat tree: ``layers.<name>`` stacked over layers, the
    expert tensors as [layers, experts, ...]. Traceable."""
    import jax
    import jax.numpy as jnp
    layers = {n: jax.vmap(lambda i, n=n, s=s: _made(key, c, i, n, s))(
        jnp.arange(c["num_hidden_layers"]))
        for n, s in layer_shapes(c).items()}
    out = globals_(key, c)
    out["layers"] = layers
    return out


def model_flops(c: dict, s: int) -> float:
    """Useful operations of one prefill of ``s`` tokens: the attention
    projections, the router and the k picked experts of every layer at
    every position, causal attention, and the output head for one row."""
    d, hd = c["hidden_size"], c["head_dim"]
    h, kvh = c["num_attention_heads"], c["num_key_value_heads"]
    per_layer = (d * (h + 2 * kvh) * hd + h * hd * d + d * c["num_experts"]
                 + c["num_experts_per_tok"] * 3 * d
                 * c["moe_intermediate_size"])
    linear = 2.0 * per_layer * c["num_hidden_layers"]
    return (linear * s + 4.0 * h * hd * flops.causal_pairs(s)
            * attention_layers(c) + 2.0 * d * c["vocab_size"])


def attention_layers(c: dict) -> int:
    """Every layer attends."""
    return c["num_hidden_layers"]
