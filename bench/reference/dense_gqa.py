"""Plain reference of a dense GQA decoder (Qwen3 / Mistral family), for the
benchmark's correctness check. It imports nothing of the system under test.

Equations, per layer, on the residual stream x (float32):

    h = RMSNorm(x) * ln1
    q, k, v = h Wq, h Wk, h Wv             (heads of head_dim; GQA groups)
    q, k = RMSNorm(q) * q_norm, RMSNorm(k) * k_norm     (Qwen3 only)
    q, k = RoPE(q), RoPE(k)                 (rotate-half, theta from config)
    x = x + softmax(q k^T / sqrt(head_dim), causal) v Wo
    h = RMSNorm(x) * ln2
    x = x + (silu(h Wg) * (h Wu)) Wd

then logits = (RMSNorm(x_last) * final_norm) Wlm for the last position.

Everything is float32, and the weights are the served bfloat16 values.
Products with a weight carry 16 bits of the float32 activation's mantissa
(see ``_mm``); attention products run at ``lax.Precision.HIGH`` (bf16x3).
Either is exact to about 1e-5, three orders below the bfloat16 rounding of
the served path. Causal attention is blocked (query
block by key block, only blocks on or below the diagonal), and the linear
layers run in row blocks, so that a 32k-token prompt fits beside nothing
else on one chip. The last layer computes attention and the MLP for the
last position only, which is all the output needs.

``mode="fp8"`` is the control: the same forward with every matrix product's
inputs rounded to float8_e4m3fn (per-row scales on activations and
probabilities, per-output-column scales on weights), float32 accumulation.
"""
from __future__ import annotations

import functools
import math

import numpy as np

ROWS = 4096        # row block of the linear layers
QBLOCK = 1024      # attention query / key block


def _jax():
    import jax
    import jax.numpy as jnp
    return jax, jnp


def _fp8(x, axis):
    """Round to float8_e4m3fn with an absmax scale along ``axis``."""
    jax, jnp = _jax()
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, w, mode):
    """[..., k] float32 @ [k, n] bfloat16 weights, float32 result. The
    activation is split into a bfloat16 high part and a bfloat16 remainder;
    the weights are exact bfloat16, so the two products with float32
    accumulation carry 16 bits of the activation's mantissa (what
    ``Precision.HIGH`` gives, without a float32 copy of the weights)."""
    jax, jnp = _jax()
    f32 = jnp.float32
    if mode == "fp8":
        return jnp.matmul(_fp8(a, -1), _fp8(w.astype(f32), 0),
                          precision=jax.lax.Precision.HIGH)
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(f32)).astype(jnp.bfloat16)
    return (jnp.matmul(hi, w, preferred_element_type=f32)
            + jnp.matmul(lo, w, preferred_element_type=f32))


def _rms(x, w, eps):
    jax, jnp = _jax()
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [S, H, D] float32, pos [S]."""
    jax, jnp = _jax()
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0
                           / x.shape[-1]))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rowmap(fn, x, rows=ROWS):
    """Apply ``fn`` to row blocks of x [S, ...]."""
    jax, jnp = _jax()
    s = x.shape[0]
    r = min(rows, s)
    if s % r:
        return fn(x)
    out = jax.lax.map(fn, x.reshape((s // r, r) + x.shape[1:]))
    return out.reshape((s,) + out.shape[2:])


def _qkv(c, lw, x, pos, mode):
    jax, jnp = _jax()
    hd, h, kvh = (c["head_dim"], c["num_attention_heads"],
                  c["num_key_value_heads"])
    s = x.shape[0]
    hn = _rms(x, lw["ln1"], c["rms_norm_eps"])
    q = _mm(hn, lw["wq"], mode).reshape(s, h, hd)
    k = _mm(hn, lw["wk"], mode).reshape(s, kvh, hd)
    v = _mm(hn, lw["wv"], mode).reshape(s, kvh, hd)
    if c.get("qk_norm"):
        q = _rms(q, lw["q_norm"], c["rms_norm_eps"])
        k = _rms(k, lw["k_norm"], c["rms_norm_eps"])
    return _rope(q, pos, c["rope_theta"]), _rope(k, pos, c["rope_theta"]), v


def _scores(qb, kb, mode):
    """qb [B, K, G, D], kb [T, K, D] -> [K, G, B, T]."""
    jax, jnp = _jax()
    if mode == "fp8":
        qb, kb = _fp8(qb, -1), _fp8(kb, -1)
    return jnp.einsum("bkgd,tkd->kgbt", qb, kb,
                      precision=jax.lax.Precision.HIGH)


def _pv(p, vb, mode):
    """p [K, G, B, T], vb [T, K, D] -> [K, G, B, D]."""
    jax, jnp = _jax()
    if mode == "fp8":
        p, vb = _fp8(p, -1), _fp8(vb, 0)
    return jnp.einsum("kgbt,tkd->kgbd", p, vb,
                      precision=jax.lax.Precision.HIGH)


def _causal_attention(q, k, v, mode):
    """q [S, H, D], k/v [S, K, D] -> [S, H*D]; blocked online softmax over
    the blocks on or below the diagonal only."""
    jax, jnp = _jax()
    s, h, d = q.shape
    kvh = k.shape[1]
    g = h // kvh
    bs = min(QBLOCK, s)
    nb = s // bs
    scale = 1.0 / math.sqrt(d)
    qb_all = q.reshape(nb, bs, kvh, g, d)
    rows = jnp.arange(bs)

    def q_block(i):
        qb = qb_all[i] * scale

        def step(j, carry):
            m, l, acc = carry
            kb = jax.lax.dynamic_slice_in_dim(k, j * bs, bs)
            vb = jax.lax.dynamic_slice_in_dim(v, j * bs, bs)
            sc = _scores(qb, kb, mode)
            ok = (j * bs + rows)[None, :] <= (i * bs + rows)[:, None]
            sc = jnp.where(ok, sc, -jnp.inf)
            m2 = jnp.maximum(m, sc.max(-1))
            p = jnp.exp(sc - m2[..., None])
            corr = jnp.exp(m - m2)
            return (m2, l * corr + p.sum(-1),
                    acc * corr[..., None] + _pv(p, vb, mode))

        init = (jnp.full((kvh, g, bs), -jnp.inf, jnp.float32),
                jnp.zeros((kvh, g, bs), jnp.float32),
                jnp.zeros((kvh, g, bs, d), jnp.float32))
        m, l, acc = jax.lax.fori_loop(0, i + 1, step, init)
        return acc / l[..., None]                       # [K, G, B, D]

    out = jax.lax.map(q_block, jnp.arange(nb))          # [nb, K, G, B, D]
    return out.transpose(0, 3, 1, 2, 4).reshape(s, h * d)


def _last_attention(q, k, v, mode):
    """The last position only: q [1, H, D] against every key."""
    jax, jnp = _jax()
    _, h, d = q.shape
    kvh = k.shape[1]
    qb = q.reshape(1, kvh, h // kvh, d) / math.sqrt(d)
    sc = _scores(qb, k, mode)                            # [K, G, 1, T]
    p = jax.nn.softmax(sc, -1)
    return _pv(p, v, mode).transpose(2, 0, 1, 3).reshape(1, h * d)


def _mlp(c, lw, x, mode):
    jax, jnp = _jax()
    hn = _rms(x, lw["ln2"], c["rms_norm_eps"])
    return _mm(jax.nn.silu(_mm(hn, lw["wg"], mode)) * _mm(hn, lw["wu"], mode),
               lw["wd"], mode)


@functools.lru_cache(maxsize=None)
def _programs(ckey, mode):
    """Jitted (inner layer, last layer, head) for one configuration."""
    jax, jnp = _jax()
    c = dict(ckey)

    def inner(lw, x):
        pos = jnp.arange(x.shape[0])
        q, k, v = _qkv_blocked(c, lw, x, pos, mode)
        att = _causal_attention(q, k, v, mode)
        x = x + _rowmap(lambda a: _mm(a, lw["wo"], mode), att)
        return x + _rowmap(lambda xb: _mlp(c, lw, xb, mode), x)

    def last(lw, x):
        pos = jnp.arange(x.shape[0])
        q, k, v = _qkv_blocked(c, lw, x, pos, mode)
        att = _last_attention(q[-1:], k, v, mode)
        xl = x[-1:] + _mm(att, lw["wo"], mode)
        return xl + _mlp(c, lw, xl, mode)

    def head(gw, xl):
        hn = _rms(xl, gw["final_norm"], c["rms_norm_eps"])
        w = gw["embed"].T if c.get("tie_word_embeddings") else gw["lm_head"]
        return _mm(hn, w, mode)[0]

    return jax.jit(inner), jax.jit(last), jax.jit(head)


def _qkv_blocked(c, lw, x, pos, mode):
    """q, k, v of every position, computed in row blocks."""
    jax, jnp = _jax()
    s = x.shape[0]
    r = min(ROWS, s)
    if s % r:
        return _qkv(c, lw, x, pos, mode)
    xb = x.reshape(s // r, r, x.shape[1])
    pb = pos.reshape(s // r, r)
    q, k, v = jax.lax.map(lambda a: _qkv(c, lw, a[0], a[1], mode), (xb, pb))
    return (q.reshape((s,) + q.shape[2:]), k.reshape((s,) + k.shape[2:]),
            v.reshape((s,) + v.shape[2:]))


def config_key(c: dict):
    keep = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size", "rope_theta", "rms_norm_eps", "qk_norm",
            "tie_word_embeddings")
    return tuple((k, c.get(k)) for k in keep)


def last_logits(c: dict, globals_w: dict, layer_w, prompts, modes=("f32",)):
    """Next-token logits [vocab_size] (numpy float32) of each prompt in
    ``prompts`` (token arrays), as ``[{mode: logits}, ...]``. ``layer_w(i)``
    returns layer i's weights on the device; ``globals_w`` holds embed,
    final_norm and lm_head. All prompts and modes go through a layer
    before the next layer's weights are made."""
    jax, jnp = _jax()
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
