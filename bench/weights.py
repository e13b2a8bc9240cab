"""Seeded random weights, made by the benchmark.

Every tensor is a pure function of (seed, tensor id, layer index, and for
an expert's tensor the expert index), so the system under test gets the
whole model from one jitted call on the device, and the plain reference
makes one layer at a time, with the same values, after the system's copy
has been freed. Neither side makes the other's weights.

What tensors a configuration has, their ids and their layout is its
layout's (``layouts/<name>.py``); this module holds what every layout
shares.
"""
from __future__ import annotations

import math

import numpy as np

STD = 0.02            # projections and embeddings
NORM_STD = 0.05       # norm gains are 1 + NORM_STD * N(0, 1)


def base_key(seed: int):
    """A threefry key from any non-negative integer seed (wider than 32
    bits included): the seed is hashed to two 32-bit words."""
    import jax
    import jax.numpy as jnp
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def residual_std(c: dict) -> float:
    """Standard deviation of a projection that writes the residual stream
    (attention output, MLP down), scaled down by the depth."""
    return STD / math.sqrt(2 * c["num_hidden_layers"])


def tensor(key, tid: int, layer: int, shape, dtype, std: float = STD,
           norm: bool = False, expert=None):
    """One tensor (one layer's slice for layer tensors, one expert's for
    expert tensors) in ``dtype``: a norm gain ``1 + NORM_STD * z``, else
    ``std * z``. ``tid`` is the tensor's id in its layout; ``z`` never
    depends on which other tensors, layers or experts exist, so a model
    cut to fewer experts holds the whole model's experts (the scale of a
    residual projection follows the depth, ``residual_std``)."""
    import jax
    import jax.numpy as jnp
    k = jax.random.fold_in(jax.random.fold_in(key, tid), layer)
    if expert is not None:
        k = jax.random.fold_in(k, expert)
    z = jax.random.normal(k, shape, jnp.float32)
    w = 1.0 + NORM_STD * z if norm else z * std
    return w.astype(jnp.dtype(dtype))


def check_layout(made, expected) -> None:
    """The generated tree must match the system's own parameter tree
    (``jax.eval_shape`` of its init) leaf for leaf, shape and dtype."""
    import jax
    a = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), made)
    b = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), expected)
    if a != b:
        raise ValueError(f"weights layout differs from the system's: "
                         f"made {a} expected {b}")
