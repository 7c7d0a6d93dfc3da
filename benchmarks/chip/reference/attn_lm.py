"""Plain float32 reference of a decoder of attention and gated-MLP blocks.

Written from the architecture's description, in straightforward
``jax.numpy`` at full float32 precision, with no kernel, cache, blocking or
batching: embedding; per layer a pre-norm causal self-attention with
rotary positions (rotate-half on the first ``partial_rotary_factor`` of
each head's dims) and a pre-norm gated MLP ``act(x W_i) * (x W_g) W_o``;
a final norm and the output head. It reads the weights from the tree the
benchmark made from the seed (the program's layout: stacked layers under
``blocks/s0``), never from the program.

``lowp="fp8"`` rounds every matrix-multiplication operand to float8 e4m3
with a per-tensor scale: the control, one precision below the bfloat16
that the configuration states.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

f32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    q = (x / s).astype(jnp.float8_e4m3fn).astype(f32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec, a, b, lowp):
    if lowp == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _norm(x, p, c):
    if c["norm"] == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + c["layer_norm_eps"]) * p["scale"] + \
            p["bias"]
    raise ValueError(c["norm"])


def _rope(x, c):
    """x: (b, s, h, dh); rotate-half on the first rot dims of each head."""
    dh = x.shape[-1]
    rot = int(dh * c["partial_rotary_factor"])
    half = rot // 2
    inv = c["rope_theta"] ** (-jnp.arange(half, dtype=f32) * 2.0 / rot)
    ang = jnp.arange(x.shape[1], dtype=f32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def _act(name):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


def _layer(x, p, c, lowp):
    a, m = p["attn"], p["mlp"]
    h = _norm(x, a["ln"], c)
    q = _rope(_mm("bsd,dhk->bshk", h, a["wq"], lowp), c)
    k = _rope(_mm("bsd,dhk->bshk", h, a["wk"], lowp), c)
    v = _mm("bsd,dhk->bshk", h, a["wv"], lowp)
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = _mm("bqhd,bkhd->bhqk", q, k, lowp) / math.sqrt(q.shape[-1])
    n = x.shape[1]
    causal = jnp.tril(jnp.ones((n, n), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, lowp)
    x = x + _mm("bshk,hkd->bsd", o, a["wo"], lowp)
    h = _norm(x, m["ln"], c)
    g = _act(c["hidden_act"])(_mm("bsd,df->bsf", h, m["wi"], lowp)) * \
        _mm("bsd,df->bsf", h, m["wg"], lowp)
    return x + _mm("bsf,fd->bsd", g, m["wo"], lowp)


def _upcast(w):
    return jax.tree.map(lambda a: a.astype(f32), w)


def hidden(w, tokens, c, lowp=None):
    """Final-norm hidden states (b, s, d), float32."""
    w = _upcast(w)
    x = w["embed"][tokens]
    if lowp == "fp8":
        x = _fp8(x)

    def body(x, p):
        return jax.checkpoint(lambda x, p: _layer(x, p, c, lowp))(x, p), None

    x, _ = jax.lax.scan(body, x, w["blocks"]["s0"])
    return _norm(x, w["final_norm"], c)


def head(w, c):
    return (w["embed"].T if c["tie_word_embeddings"] else w["lm_head"]) \
        .astype(f32)


def logits(w, tokens, c, lowp=None):
    """(b, s, V) float32 logits of a teacher-forced pass."""
    return _mm("bsd,dv->bsv", hidden(w, tokens, c, lowp), head(w, c), lowp)


def loss_sums(w, inputs, targets, c, lowp=None):
    """(sum of cross-entropy, sum of squared log-partition) over tokens."""
    z = logits(w, inputs, c, lowp)
    lse = jax.scipy.special.logsumexp(z, -1)
    tgt = jnp.take_along_axis(z, targets[..., None], -1)[..., 0]
    return jnp.sum(lse - tgt), jnp.sum(jnp.square(lse))
