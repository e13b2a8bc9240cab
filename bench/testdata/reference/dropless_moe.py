"""Plain reference of a decoder whose every layer is GQA attention followed
by a dropless top-k mixture of experts (the ``dropless_moe`` layout), for
the benchmark's tests. It imports nothing of the system under test; the
attention, norms and products are the dense reference's.

Per layer, on the residual stream x (float32):

    x = x + attention(x)                       (as ``dense_gqa``)
    h = RMSNorm(x) * ln2
    p = top-k of h Wr, g = softmax(p)          (the k picked logits)
    x = x + sum over the k picked experts e of g_e (silu(h Wg_e) * (h Wu_e)) Wd_e

Every expert runs on every token, weighted by its gate (0 where not
picked): no capacity, no token dropped.
"""
from __future__ import annotations

import functools

import numpy as np

import lookup

DENSE = lookup.module("reference", "dense_gqa")


def _moe(c, lw, x, mode):
    import jax
    import jax.numpy as jnp
    hn = DENSE._rms(x, lw["ln2"], c["rms_norm_eps"])
    logits = DENSE._mm(hn, lw["router"], mode)                 # [S, E]
    top, picked = jax.lax.top_k(logits, c["num_experts_per_tok"])
    gates = jnp.sum(jax.nn.one_hot(picked, c["num_experts"])
                    * jax.nn.softmax(top, -1)[..., None], axis=1)
    out = jnp.zeros_like(x)
    for e in range(c["num_experts"]):
        y = DENSE._mm(jax.nn.silu(DENSE._mm(hn, lw["e_wg"][e], mode))
                      * DENSE._mm(hn, lw["e_wu"][e], mode),
                      lw["e_wd"][e], mode)
        out = out + gates[:, e:e + 1] * y
    return out


@functools.lru_cache(maxsize=None)
def _programs(ckey, mode):
    """Jitted (inner layer, last layer, head) for one configuration."""
    import jax
    import jax.numpy as jnp
    c = dict(ckey)

    def inner(lw, x):
        pos = jnp.arange(x.shape[0])
        q, k, v = DENSE._qkv_blocked(c, lw, x, pos, mode)
        att = DENSE._causal_attention(q, k, v, mode)
        x = x + DENSE._mm(att, lw["wo"], mode)
        return x + _moe(c, lw, x, mode)

    def last(lw, x):
        pos = jnp.arange(x.shape[0])
        q, k, v = DENSE._qkv_blocked(c, lw, x, pos, mode)
        att = DENSE._last_attention(q[-1:], k, v, mode)
        xl = x[-1:] + DENSE._mm(att, lw["wo"], mode)
        return xl + _moe(c, lw, xl, mode)

    def head(gw, xl):
        hn = DENSE._rms(xl, gw["final_norm"], c["rms_norm_eps"])
        w = gw["embed"].T if c.get("tie_word_embeddings") else gw["lm_head"]
        return DENSE._mm(hn, w, mode)[0]

    return jax.jit(inner), jax.jit(last), jax.jit(head)


def config_key(c: dict):
    keep = ("num_experts", "num_experts_per_tok", "moe_intermediate_size")
    return DENSE.config_key(c) + tuple((k, c.get(k)) for k in keep)


def last_logits(c: dict, globals_w: dict, layer_w, prompts, modes=("f32",)):
    """Next-token logits [vocab_size] of each prompt, as
    ``[{mode: logits}, ...]``; the same contract as ``dense_gqa``."""
    import jax.numpy as jnp
    xs = []
    for toks in prompts:
        emb = jnp.take(globals_w["embed"], jnp.asarray(toks, jnp.int32),
                       axis=0).astype(jnp.float32)
        xs.append({m: emb for m in modes})
    n = c["num_hidden_layers"]
    for i in range(n):
        lw = layer_w(i)
        for x in xs:
            for m in modes:
                inner, last, _ = _programs(config_key(c), m)
                x[m] = (last if i == n - 1 else inner)(lw, x[m])
        del lw
    return [{m: np.asarray(_programs(config_key(c), m)[2](globals_w, x[m]))
             [: c["vocab_size"]] for m in modes} for x in xs]
