"""Plain reference of a Granite-4.0-H hybrid (``granitemoehybrid``), for the
benchmark's correctness check and the CPU tests. It imports nothing of the
system under test: straightforward ``jax.numpy`` in float32, every product
under ``jax.default_matmul_precision("highest")``.

Equations, after ``modeling_granitemoehybrid.py`` in Hugging Face
transformers, on the residual stream x (float32):

    x = embed[tokens] * embedding_multiplier
    per layer i (mixer kind from layer_types[i]):
      mamba:      h = RMSNorm(x) * ln
                  z | xBC | dt = h in_proj
                  xBC = silu(causal depthwise conv_K(xBC) + conv_b)
                  x_ | B | C = xBC;  dt = softplus(dt + dt_bias);  A = -exp(a_log)
                  s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T;  y_t = C_t s_t + D x_t
                  m = RMSNorm(y * silu(z)) * gate_norm  (over all of d_in:
                  one group) then out_proj
      attention:  h = RMSNorm(x) * ln1;  q, k, v = h Wq, h Wk, h Wv (GQA)
                  m = softmax(q k^T * attention_multiplier, causal) v Wo
                  (no rotary positions: position_embedding_type "nope")
      x = x + residual_multiplier * m
      h = RMSNorm(x) * ln2
      g = softmax over the top num_experts_per_tok of h Wr (Wr: every expert)
      x = x + residual_multiplier * (sum over the k picked experts e that
              this chip holds of g_e (silu(h Wg_e) * (h Wu_e)) Wd_e
              + (silu(h Sg) * (h Su)) Sd)
    logits = (RMSNorm(x_last) * final_norm) embed^T / logits_scaling

Departures from ``modeling_granitemoehybrid.py``:

- Activations are float32 throughout; the weights are the served bfloat16
  values. The gates stay float32 (the model casts them to its dtype).
- The routed sum runs over the experts held here, ``[0, num_local_experts)``
  of the router's ``published.num_local_experts``, as the program does: the
  part this chip gives in a deployment that splits each layer's experts
  over chips. The router keeps every expert.
- The scan is the plain recurrence evaluated a chunk at a time
  (``SSD_CHUNK`` positions: the decay inside a chunk as a masked matrix, the
  state carried from chunk to chunk); the model's ``torch_forward`` is the
  same chunked form. Its ``time_step_limit`` clamp (0, inf) is a no-op.
- Only the last position's logits are made: prefill-only.
- Every expert runs on every token, weighted by its gate (0 where not
  picked), in row blocks; causal attention runs in query and key blocks.

``mode="fp8"`` is the control: the same forward with every product's
inputs rounded to float8_e4m3fn (absmax scales), float32 accumulation.
"""
from __future__ import annotations

import functools

import numpy as np

ROWS = 4096         # row block of the projections and the experts
QBLOCK = 1024       # attention query / key block
SSD_CHUNK = 256     # positions per step of the scan


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
    """[..., k] @ [k, n] weights, float32."""
    jax, jnp = _jax()
    w = w.astype(jnp.float32)
    if mode == "fp8":
        a, w = _fp8(a, -1), _fp8(w, 0)
    return jnp.matmul(a, w)


def _ein(spec, a, b, mode, axes=(-1, -1)):
    """Two-operand einsum; in fp8 mode each input rounded along the given
    (contraction) axis."""
    jax, jnp = _jax()
    if mode == "fp8":
        a, b = _fp8(a, axes[0]), _fp8(b, axes[1])
    return jnp.einsum(spec, a, b)


def _rms(x, w, eps):
    jax, jnp = _jax()
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rowmap(fn, x, rows=ROWS):
    """Apply ``fn`` to row blocks of x [S, ...]."""
    jax, jnp = _jax()
    s = x.shape[0]
    r = min(rows, s)
    if s % r:
        return fn(x)
    out = jax.lax.map(fn, x.reshape((s // r, r) + x.shape[1:]))
    return jax.tree.map(lambda a: a.reshape((s,) + a.shape[2:]), out)


def _dims(c):
    d = c["hidden_size"]
    d_in = c["mamba_expand"] * d
    heads, p = c["mamba_n_heads"], c["mamba_d_head"]
    g, n = c["mamba_n_groups"], c["mamba_d_state"]
    return d, d_in, heads, p, g, n


# ------------------------------------------------------------------ mamba

def _scan(x, dt, a, b, cm, mode):
    """y [S, H, P] of the recurrence s_t = exp(dt_t a) s_{t-1} + dt_t x_t
    b_t^T, y_t = c_t s_t, from s_0 = 0; x [S, H, P], dt [S, H], a [H],
    b, cm [S, H, N] (each head's group)."""
    jax, jnp = _jax()
    s, h, p = x.shape
    q = min(SSD_CHUNK, s)
    pad = -s % q
    if pad:
        x, dt, b, cm = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                        for v in (x, dt, b, cm))
    nc = x.shape[0] // q
    blocks = [v.reshape((nc, q) + v.shape[1:]) for v in (x, dt, b, cm)]
    causal = jnp.tril(jnp.ones((q, q), bool))[:, :, None]

    def step(state, blk):                       # state [H, P, N]
        xq, dq, bq, cq = blk
        cs = jnp.cumsum(dq * a, axis=0)          # [Q, H]
        seg = jnp.where(causal, cs[:, None, :] - cs[None, :, :], -jnp.inf)
        w = _ein("ihn,jhn->ijh", cq, bq, mode) * jnp.exp(seg)
        y = _ein("ijh,jhp->ihp", w, dq[..., None] * xq, mode, (1, 0))
        y = y + _ein("ihn,hpn->ihp", cq, state, mode) * jnp.exp(cs)[..., None]
        decay = jnp.exp(cs[-1] - cs) * dq        # [Q, H]
        state = (state * jnp.exp(cs[-1])[:, None, None]
                 + _ein("jhp,jhn->hpn", decay[..., None] * xq, bq, mode,
                        (0, 0)))
        return state, y

    n = b.shape[-1]
    _, ys = jax.lax.scan(step, jnp.zeros((h, p, n), jnp.float32), blocks)
    return ys.reshape(nc * q, h, p)[:s]


def _mamba(c, lw, x, mode):
    jax, jnp = _jax()
    d, d_in, heads, p, g, n = _dims(c)
    conv_ch = d_in + 2 * g * n
    k = c["mamba_d_conv"]
    eps = c["rms_norm_eps"]
    proj = _rowmap(lambda xb: _mm(_rms(xb, lw["ln"], eps), lw["in_proj"],
                                  mode), x)
    z, xbc, dt = jnp.split(proj, [d_in, d_in + conv_ch], axis=-1)
    w = lw["conv_w"].astype(jnp.float32)                     # [K, ch]
    xp = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = sum(xp[i:i + x.shape[0]] * w[i] for i in range(k))
    xbc = jax.nn.silu(xbc + lw["conv_b"].astype(jnp.float32))
    xs, b, cm = jnp.split(xbc, [d_in, d_in + g * n], axis=-1)
    s = x.shape[0]
    xs = xs.reshape(s, heads, p)
    per = heads // g
    b = jnp.repeat(b.reshape(s, g, n), per, axis=1)
    cm = jnp.repeat(cm.reshape(s, g, n), per, axis=1)
    dt = jax.nn.softplus(dt + lw["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(lw["a_log"].astype(jnp.float32))
    y = _scan(xs, dt, a, b, cm, mode) \
        + lw["d_skip"].astype(jnp.float32)[:, None] * xs
    y = y.reshape(s, d_in) * jax.nn.silu(z)
    return _rowmap(lambda yb: _mm(_rms(yb, lw["gate_norm"], eps),
                                  lw["out_proj"], mode), y)


# -------------------------------------------------------------- attention

def _attention(c, lw, x, mode):
    """Causal GQA attention of every position, no rotary positions;
    blocked online softmax over the key blocks on or below the diagonal."""
    jax, jnp = _jax()
    d = c["hidden_size"]
    h, kvh = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // h
    s = x.shape[0]
    hn = _rowmap(lambda xb: _rms(xb, lw["ln1"], c["rms_norm_eps"]), x)
    q = _rowmap(lambda a: _mm(a, lw["wq"], mode), hn).reshape(s, kvh, h // kvh, hd)
    k = _rowmap(lambda a: _mm(a, lw["wk"], mode), hn).reshape(s, kvh, hd)
    v = _rowmap(lambda a: _mm(a, lw["wv"], mode), hn).reshape(s, kvh, hd)
    bs = min(QBLOCK, s)
    if s % bs:
        bs = s
    nb = s // bs
    rows = jnp.arange(bs)
    scale = c["attention_multiplier"]

    def q_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * bs, bs) * scale

        def step(j, carry):
            m, l, acc = carry
            kb = jax.lax.dynamic_slice_in_dim(k, j * bs, bs)
            vb = jax.lax.dynamic_slice_in_dim(v, j * bs, bs)
            sc = _ein("bkgd,tkd->kgbt", qb, kb, mode)
            ok = (j * bs + rows)[None, :] <= (i * bs + rows)[:, None]
            sc = jnp.where(ok, sc, -jnp.inf)
            m2 = jnp.maximum(m, sc.max(-1))
            pr = jnp.exp(sc - m2[..., None])
            corr = jnp.exp(m - m2)
            return (m2, l * corr + pr.sum(-1),
                    acc * corr[..., None]
                    + _ein("kgbt,tkd->kgbd", pr, vb, mode, (-1, 0)))

        g = h // kvh
        init = (jnp.full((kvh, g, bs), -jnp.inf, jnp.float32),
                jnp.zeros((kvh, g, bs), jnp.float32),
                jnp.zeros((kvh, g, bs, hd), jnp.float32))
        m, l, acc = jax.lax.fori_loop(0, i + 1, step, init)
        return acc / l[..., None]                          # [K, G, B, D]

    out = jax.lax.map(q_block, jnp.arange(nb))
    att = out.transpose(0, 3, 1, 2, 4).reshape(s, h * hd)
    return _rowmap(lambda a: _mm(a, lw["wo"], mode), att)


# -------------------------------------------------------------------- moe

def _moe(c, lw, x, mode):
    """Held routed experts plus the shared expert, of RMSNorm(x) * ln2."""
    jax, jnp = _jax()
    held = c["num_local_experts"]
    k = c["num_experts_per_tok"]
    eps = c["rms_norm_eps"]

    def block(xb):
        hn = _rms(xb, lw["ln2"], eps)
        logits = _mm(hn, lw["router"], mode)                 # [R, E]
        top, picked = jax.lax.top_k(logits, k)
        gates = jnp.sum(jax.nn.one_hot(picked, logits.shape[-1])
                        * jax.nn.softmax(top, -1)[..., None], axis=1)

        def expert(acc, e):
            wgu = lw["e_wgu"][e]
            f = wgu.shape[-1] // 2
            gu = _mm(hn, wgu, mode)
            y = _mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], lw["e_wd"][e], mode)
            return acc + gates[:, e, None] * y, None

        routed, _ = jax.lax.scan(expert, jnp.zeros_like(xb),
                                 jnp.arange(held))
        shared = _mm(jax.nn.silu(_mm(hn, lw["s_wg"], mode))
                     * _mm(hn, lw["s_wu"], mode), lw["s_wd"], mode)
        return routed + shared

    return _rowmap(block, x)


# --------------------------------------------------------------- programs

@functools.lru_cache(maxsize=None)
def _programs(ckey, mode):
    """Jitted (mamba layer, attention layer, head) for one configuration."""
    jax, jnp = _jax()
    c = dict(ckey)
    rm = c["residual_multiplier"]

    def layer(mixer):
        def fn(lw, x):
            x = x + rm * mixer(c, lw, x, mode)
            return x + rm * _moe(c, lw, x, mode)
        return jax.jit(fn)

    def head(gw, xl):
        hn = _rms(xl, gw["final_norm"], c["rms_norm_eps"])
        return _mm(hn, gw["embed"].T, mode)[0] / c["logits_scaling"]

    return layer(_mamba), layer(_attention), jax.jit(head)


def config_key(c: dict):
    keep = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "vocab_size", "rms_norm_eps", "mamba_expand", "mamba_n_heads",
            "mamba_d_head", "mamba_n_groups", "mamba_d_state", "mamba_d_conv",
            "num_local_experts", "num_experts_per_tok", "embedding_multiplier",
            "residual_multiplier", "attention_multiplier", "logits_scaling")
    return tuple((k, c[k]) for k in keep)


def last_logits(c: dict, globals_w: dict, layer_w, prompts, modes=("f32",)):
    """Next-token logits [vocab_size] (numpy float32) of each prompt, as
    ``[{mode: logits}, ...]``. ``layer_w(i)`` returns layer i's weights;
    ``globals_w`` holds ``embed`` and ``final_norm`` (the head is the tied
    embedding). Every prompt and mode goes through a layer before the next
    layer's weights are made."""
    jax, jnp = _jax()
    kinds = c["layer_types"][:c["num_hidden_layers"]]
    with jax.default_matmul_precision("highest"):
        xs = []
        for toks in prompts:
            emb = jnp.take(globals_w["embed"], jnp.asarray(toks, jnp.int32),
                           axis=0).astype(jnp.float32)
            xs.append({m: emb * c["embedding_multiplier"] for m in modes})
        for i, kind in enumerate(kinds):
            lw = layer_w(i)
            for x in xs:
                for m in modes:
                    mamba, attention, _ = _programs(config_key(c), m)
                    x[m] = (mamba if kind == "mamba" else attention)(lw, x[m])
            del lw
        return [{m: np.asarray(_programs(config_key(c), m)[2](
            globals_w, x[m][-1:]))[: c["vocab_size"]] for m in modes}
            for x in xs]
