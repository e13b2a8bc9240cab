"""Layout of a Granite-4.0-H hybrid (Hugging Face ``granitemoehybrid``'s
keys): Mamba-2 and NoPE GQA mixers at the indices ``layer_types`` gives,
each followed by a dropless top-k MoE over the experts this chip holds plus
a shared SwiGLU expert.

The file's ``num_local_experts`` is how many experts are held here (experts
``[0, num_local_experts)``); the router scores all
``published["num_local_experts"]``. ``layer_types`` is the published list;
the first ``num_hidden_layers`` are served.

The system's flat tree groups the layers by kind (``models/hybrid_moe.py``):
``mamba`` stacked over the Mamba layers, ``attn`` over the attention layers,
``moe`` over every layer, expert tensors as [layers, held experts, ...].
Every tensor is made by its global layer index (and expert index), so a cut
model holds the whole model's first layers and experts. The attention
tensors, norms, embedding, router and expert down-projection keep the ids
other layouts give those names.

Also here: the operations and bytes of the two kernels this model adds,
the SSD scan and the held experts' grouped matmuls (``ssd_kernel``,
``moe_experts_kernel``), for their rooflines.
"""
from __future__ import annotations

import math

import weights

TENSOR_IDS = {
    "embed": 1, "final_norm": 2, "ln1": 10, "ln2": 11, "wq": 12, "wk": 13,
    "wv": 14, "wo": 15, "router": 40, "e_wd": 43,
    "ln": 50, "in_proj": 51, "conv_w": 52, "conv_b": 53, "a_log": 54,
    "dt_bias": 55, "d_skip": 56, "gate_norm": 57, "out_proj": 58,
    "e_wgu": 60, "s_wg": 61, "s_wu": 62, "s_wd": 63,
}
NORMS = ("final_norm", "ln1", "ln2", "ln", "gate_norm", "d_skip")
RESIDUAL = ("out_proj", "e_wd", "s_wd")    # residual writes, scaled by depth
EXPERT = ("e_wgu", "e_wd")
F32 = ("a_log", "dt_bias", "d_skip")             # kept in float32
CONV_STD = 0.2
# Seeded weights at the initial scales leave two of the timed path's
# mechanisms invisible in the last position's logits, which are all the
# benchmark compares. The attention layer's scores (x 1/128, the published
# attention_multiplier) would spread by ~0.15, so it would average every
# position alike and its positions would move nothing; and no Mamba head
# would remember past a few hundred tokens, so the state carried from one
# 2048-token chunk to the next would move nothing either. Trained models
# have peaked attention and slow heads; so here wq and wk are drawn wider
# (scores spread by ~1.8; wider still, a near tie among the top keys lets
# rounding move the logits as much as a fault does), wo at the initial
# 0.02 without the depth scaling of the other residual writes, so that the
# attention layer weighs in the residual stream, and A log-uniform over
# [0.1, 16] (Mamba-2 draws it in [1, 16]), which gives a share of the heads
# a memory of thousands of tokens. dt keeps Mamba-2's initial range,
# log-uniform in [0.001, 0.1].
QK_STD = 0.07
A_RANGE = (0.1, 16.0)
DT_RANGE = (0.001, 0.1)
BF16 = 2
F32_BYTES = 4


def kinds(c: dict):
    return tuple(c["layer_types"][:c["num_hidden_layers"]])


def ssm_dims(c: dict):
    """(d_in, heads, head_dim, groups, d_state, conv channels)."""
    d_in = c["mamba_expand"] * c["hidden_size"]
    g, n = c["mamba_n_groups"], c["mamba_d_state"]
    return (d_in, c["mamba_n_heads"], c["mamba_d_head"], g, n,
            d_in + 2 * g * n)


def model_config(c: dict):
    """The system's ModelConfig, every field from the file."""
    from repro.configs.base import ModelConfig, MoEConfig, SSMConfig
    d, h = c["hidden_size"], c["num_attention_heads"]
    d_in, heads, p, g, n, _ = ssm_dims(c)
    if heads * p != d_in:
        raise ValueError(f"mamba_n_heads x mamba_d_head = {heads * p}, "
                         f"not mamba_expand x hidden_size = {d_in}")
    if c["mamba_proj_bias"] or c["attention_bias"] or not c["mamba_conv_bias"]:
        raise ValueError("the system has a conv bias and no projection bias")
    if c["position_embedding_type"] not in ("nope", "rope"):
        raise ValueError(f"position_embedding_type "
                         f"{c['position_embedding_type']!r}")
    return ModelConfig(
        arch=c["arch"], family="hybrid_moe",
        num_layers=c["num_hidden_layers"], d_model=d, num_heads=h,
        num_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        head_dim=d // h, tie_embeddings=bool(c["tie_word_embeddings"]),
        rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
        embedding_multiplier=c["embedding_multiplier"],
        residual_multiplier=c["residual_multiplier"],
        attention_multiplier=c["attention_multiplier"],
        logits_scaling=c["logits_scaling"],
        nope=c["position_embedding_type"] == "nope",
        layer_types=tuple(c["layer_types"]),
        ssm=SSMConfig(d_state=n, head_dim=p, expand=c["mamba_expand"],
                      conv_kernel=c["mamba_d_conv"], n_groups=g,
                      chunk_size=c["mamba_chunk_size"]),
        moe=MoEConfig(num_experts=router_width(c),
                      top_k=c["num_experts_per_tok"],
                      d_expert=c["intermediate_size"], num_shared_experts=1,
                      d_shared=c["shared_intermediate_size"],
                      held_first=0, held_count=c["num_local_experts"]),
        dtype=c["torch_dtype"])


def router_width(c: dict) -> int:
    """Experts the router scores: the published count."""
    return c.get("published", {}).get("num_local_experts",
                                      c["num_local_experts"])


def layer_shapes(c: dict, kind: str) -> dict:
    """Shapes of one layer's tensors; an expert tensor's is one expert's."""
    d, h, kvh = (c["hidden_size"], c["num_attention_heads"],
                 c["num_key_value_heads"])
    hd = d // h
    f, fs = c["intermediate_size"], c["shared_intermediate_size"]
    d_in, heads, _, g, n, ch = ssm_dims(c)
    if kind == "mamba":
        out = {"ln": (d,), "in_proj": (d, d_in + ch + heads),
               "conv_w": (c["mamba_d_conv"], ch), "conv_b": (ch,),
               "a_log": (heads,), "dt_bias": (heads,), "d_skip": (heads,),
               "gate_norm": (d_in,), "out_proj": (d_in, d)}
    else:
        out = {"ln1": (d,), "wq": (d, h * hd), "wk": (d, kvh * hd),
               "wv": (d, kvh * hd), "wo": (h * hd, d)}
    out.update(ln2=(d,), router=(d, router_width(c)), e_wgu=(d, 2 * f),
               e_wd=(f, d), s_wg=(d, fs), s_wu=(d, fs), s_wd=(fs, d))
    return out


def global_shapes(c: dict) -> dict:
    return {"embed": (c["vocab_size"], c["hidden_size"]),
            "final_norm": (c["hidden_size"],)}


def tensor(key, name: str, layer: int, shape, c: dict, expert=None):
    """One tensor; the Mamba-2 decay and step parameters are drawn
    log-uniform over ``A_RANGE`` and ``DT_RANGE`` through the normal's
    CDF."""
    import jax
    import jax.numpy as jnp
    tid = TENSOR_IDS[name]
    if name in ("a_log", "dt_bias"):
        u = jax.scipy.special.ndtr(weights.tensor(key, tid, layer, shape,
                                                  "float32", std=1.0))
        lo, hi = (math.log(v) for v in
                  (A_RANGE if name == "a_log" else DT_RANGE))
        if name == "a_log":
            return lo + (hi - lo) * u
        dt = jnp.exp(lo + (hi - lo) * u)
        return dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1
    std = (weights.residual_std(c) if name in RESIDUAL
           else CONV_STD if name == "conv_w"
           else QK_STD if name in ("wq", "wk") else weights.STD)
    dtype = "float32" if name in F32 else c["torch_dtype"]
    return weights.tensor(key, tid, layer, shape, dtype, std=std,
                          norm=name in NORMS, expert=expert)


def _made(key, c, layer, name, shape):
    """One layer's tensor; an expert tensor stacked over the held experts."""
    import jax
    import jax.numpy as jnp
    if name not in EXPERT:
        return tensor(key, name, layer, shape, c)
    return jax.vmap(lambda e: tensor(key, name, layer, shape, c, e))(
        jnp.arange(c["num_local_experts"]))


def layer(key, c: dict, index) -> dict:
    """Layer ``index``'s tensors (the reference's path). The index may be
    traced, so both mixers' tensors are made at it; the reference reads
    those of the layer's kind."""
    shapes = dict(layer_shapes(c, "mamba"), **layer_shapes(c, "attention"))
    return {n: _made(key, c, index, n, s) for n, s in shapes.items()}


def globals_(key, c: dict) -> dict:
    return {n: tensor(key, n, 0, s, c) for n, s in global_shapes(c).items()}


GROUPS = {"mamba": ("mamba",), "attn": ("attention",),
          "moe": ("mamba", "attention")}
MOE = ("ln2", "router", "e_wgu", "e_wd", "s_wg", "s_wu", "s_wd")


def flat_params(key, c: dict) -> dict:
    """The system's flat tree, each group stacked over the layers of its
    kinds in layer order, one layer at a time (``lax.map``: no group's
    float32 draws are held at once). Traceable."""
    import jax
    import jax.numpy as jnp
    ks = kinds(c)
    out = globals_(key, c)
    for group, wanted in GROUPS.items():
        idx = [i for i, k in enumerate(ks) if k in wanted]
        names = {}
        for kind in wanted:
            for n, s in layer_shapes(c, kind).items():
                if (n in MOE) == (group == "moe"):
                    names[n] = s
        out[group] = {n: jax.lax.map(
            lambda i, n=n, s=s: _made(key, c, i, n, s),
            jnp.asarray(idx, jnp.int32)) for n, s in names.items()}
    return out


# ------------------------------------------------------------------ counts

def attention_layers(c: dict) -> int:
    """The layers that call the attention kernels."""
    return kinds(c).count("attention")


def ssd_kernel(c: dict, chunk: int, batch: int = 1):
    """(flops, bytes) of one layer's SSD scan over a chunk of ``chunk``
    tokens. Operations: within each block of ``mamba_chunk_size`` positions
    the causal (i, j) pairs, C_i B_j per group and its weighting of dt_j x_j
    per head; per token the state's update and its read by C. Bytes: x and
    the output in bfloat16, dt in float32, B and C in bfloat16, the float32
    state in and out."""
    _, heads, p, g, n, _ = ssm_dims(c)
    q = min(c["mamba_chunk_size"], chunk)
    pairs = chunk * (q + 1) / 2.0
    flops = batch * (2.0 * pairs * (g * n + heads * p)
                     + 4.0 * chunk * heads * p * n)
    by = batch * (chunk * (2 * heads * p * BF16 + heads * F32_BYTES
                           + 2 * g * n * BF16)
                  + 2 * heads * p * n * F32_BYTES)
    return flops, float(by)


def moe_experts_kernel(c: dict, held_picks: float):
    """(flops, bytes) of one layer's held-expert grouped matmuls over a
    chunk whose tokens made ``held_picks`` (token, pick) pairs onto the held
    experts: gate, up and down, 6 d f per pair; the held experts' weights
    read once, and each pair's row in and out, in bfloat16."""
    d, f = c["hidden_size"], c["intermediate_size"]
    flops = 6.0 * d * f * held_picks
    by = (c["num_local_experts"] * 3 * d * f + 2 * held_picks * d) * BF16
    return flops, float(by)


def least_seconds(flops: float, by: float, peak: dict) -> float:
    return max(flops / peak["bf16_flops_per_s"], by / peak["hbm_bytes_per_s"])


def model_flops(c: dict, s: int) -> float:
    """Useful operations of one prefill of ``s`` tokens: the Mamba
    projections and scans, the attention projections and causal attention,
    the router, the held experts (their expected share of the picks, k x
    held / routed per token) and the shared expert of every layer at every
    position, and the output head for the one next-token row."""
    d, h, kvh = (c["hidden_size"], c["num_attention_heads"],
                 c["num_key_value_heads"])
    hd = d // h
    d_in, heads, _, g, n, ch = ssm_dims(c)
    ks = kinds(c)
    mamba = 2.0 * (d * (d_in + ch + heads) + d_in * d) * s \
        + ssd_kernel(c, s)[0]
    attn = (2.0 * (d * (h + 2 * kvh) * hd + h * hd * d) * s
            + 4.0 * h * hd * s * (s + 1) / 2.0)
    picks = c["num_experts_per_tok"] * c["num_local_experts"] \
        / router_width(c)
    moe = 2.0 * s * (d * router_width(c)
                     + 3 * d * c["shared_intermediate_size"]) \
        + moe_experts_kernel(c, picks * s)[0]
    return (ks.count("mamba") * mamba + ks.count("attention") * attn
            + len(ks) * moe + 2.0 * d * c["vocab_size"])
