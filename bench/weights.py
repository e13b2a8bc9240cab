"""Seeded random weights of a dense GQA decoder, made by the benchmark.

Every tensor is a pure function of (seed, tensor name, layer index), so the
system under test gets the whole model from one jitted call on the device,
and the plain reference makes one layer at a time, with the same values,
after the system's copy has been freed. Neither side makes the other's
weights.

The layout handed to the system is its flat parameter tree (``embed``,
``final_norm``, ``lm_head``, ``layers.<name>`` stacked over layers); the
system restacks it into its own per-stage layout inside the same jit.
"""
from __future__ import annotations

import math

import numpy as np

# Stable ids for ``fold_in``: a tensor's values never depend on which other
# tensors exist.
TENSOR_IDS = {
    "embed": 1, "final_norm": 2, "lm_head": 3,
    "ln1": 10, "ln2": 11, "wq": 12, "wk": 13, "wv": 14, "wo": 15,
    "q_norm": 16, "k_norm": 17, "wg": 18, "wu": 19, "wd": 20,
}
NORMS = ("final_norm", "ln1", "ln2", "q_norm", "k_norm")
STD = 0.02            # projections and embeddings
NORM_STD = 0.05       # norm gains are 1 + NORM_STD * N(0, 1)


def base_key(seed: int):
    """A threefry key from any non-negative integer seed (wider than 32
    bits included): the seed is hashed to two 32-bit words."""
    import jax
    import jax.numpy as jnp
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


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
    """One tensor (one layer's slice for layer tensors), in the served
    dtype."""
    import jax
    import jax.numpy as jnp
    k = jax.random.fold_in(jax.random.fold_in(key, TENSOR_IDS[name]), layer)
    z = jax.random.normal(k, shape, jnp.float32)
    if name in NORMS:
        w = 1.0 + NORM_STD * z
    elif name in ("wo", "wd"):
        w = z * (STD / math.sqrt(2 * c["num_hidden_layers"]))
    else:
        w = z * STD
    return w.astype(jnp.dtype(c["torch_dtype"]))


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


def check_layout(made, expected) -> None:
    """The generated tree must match the system's own parameter tree
    (``jax.eval_shape`` of its init) leaf for leaf, shape and dtype."""
    import jax
    a = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), made)
    b = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), expected)
    if a != b:
        raise ValueError(f"weights layout differs from the system's: "
                         f"made {a} expected {b}")
