"""Flash-decode Pallas TPU kernel: one query token vs. a long KV cache.

Grid: (batch, kv_head, k_blocks) — the k sweep is innermost and sequential
on TPU, so the online-softmax state lives in VMEM scratch (same structure
as the prefill kernel). One grid step serves the g = h / kv query heads
that share a KV head, so each (bk, dh) key tile is read once per group and
the score block is a (g, dh) x (dh, bk) MXU product.

The kernel works on head-major arrays, q as (b, kv, g, dh) and the cache
as (b, kv, S, dh), so the last two block dims are (g, dh) and (bk, dh): a
(…, 1, dh) head slice of the (b, S, kv, dh) cache is not a legal TPU block.
The wrapper transposes the cache (one HBM pass over it per call), so a
model that adopts this kernel would keep its cache head-major instead.

The valid prefix length arrives via scalar prefetch (SMEM) so block masks
are computed without streaming a position tensor from HBM.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BK = 256
NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            window: int, softcap: float, scale: float, g: int, bk: int,
            nk: int):
    bi = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[bi]
    k_lo = ki * bk
    run = k_lo < length
    if window:
        run &= (k_lo + bk) > jnp.maximum(length - window, 0)

    @pl.when(run)
    def _compute():
        q = q_ref[...]                                       # (g, dh)
        k = k_ref[...]                                       # (bk, dh)
        v = v_ref[...]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (g, bk), 1)
        mask = kpos < length
        if window:
            mask &= kpos >= (length - window)
        s = jnp.where(mask, s, NEG_INF)
        # When S % bk != 0 the last block reads past the cache end; those
        # lanes are masked (kpos >= S >= length) but the padded v rows hold
        # garbage, and 0 * NaN = NaN would poison the accumulator.
        vpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        v = jnp.where(vpos < length, v, jnp.zeros_like(v))

        m_prev = m_ref[...]                                  # (g, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, lengths, *, window: int = 0,
                     softcap: float = 0.0, bk: int = DEFAULT_BK,
                     interpret: bool = False):
    """q: (b, h, dh); k/v_cache: (b, S, kv, dh); lengths: (b,) -> (b, h, dh)."""
    b, h, dh = q.shape
    S, kv = k_cache.shape[1], k_cache.shape[2]
    g = h // kv
    bk = min(bk, S)
    nk = -(-S // bk)
    scale = 1.0 / math.sqrt(dh)

    kern = functools.partial(_kernel, window=window, softcap=softcap,
                             scale=scale, g=g, bk=bk, nk=nk)
    q_spec = pl.BlockSpec((None, None, g, dh),
                          lambda b_, h_, k_, lens: (b_, h_, 0, 0))
    kv_spec = pl.BlockSpec((None, None, bk, dh),
                           lambda b_, h_, k_, lens: (b_, h_, k_, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kv, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, dh), q.dtype),
        interpret=interpret,
    )(lengths, q.reshape(b, kv, g, dh), k_cache.transpose(0, 2, 1, 3),
      v_cache.transpose(0, 2, 1, 3))
    return out.reshape(b, h, dh)
