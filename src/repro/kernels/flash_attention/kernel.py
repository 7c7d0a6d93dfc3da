"""Flash attention Pallas TPU kernel (fwd): causal/sliding-window/softcap/GQA.

Grid: (batch, q_head, q_blocks, k_blocks) — k innermost, so the online
softmax state (m, l, acc) lives in VMEM scratch and persists across the
k-block sweep for one q block. BlockSpecs stage (bq, dh) query tiles and
(bk, dh) key/value tiles HBM->VMEM; dh is the lane dim. The kernel works
on head-major (b, h, s, dh) arrays so the last two block dims are
(seq tile, dh): a (…, 1, dh) head slice of the (b, s, h, dh) layout is
not a legal TPU block. The wrapper transposes in and out (one HBM pass
each way). The MXU consumes the inputs' own dtype with f32 accumulation.

GQA is handled by the k/v index maps (kv head = q head // group) — no
repeated KV in HBM, the repeat happens implicitly via block addressing.
Causal/window structure is exploited at block granularity: fully-masked
k blocks are skipped under ``pl.when`` (no MXU work issued).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            causal: bool, window: int, softcap: float, scale: float,
            bq: int, bk: int, nk: int, sk: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = qi * bq
    k_lo = ki * bk
    run = jnp.bool_(True)
    if causal:
        run = k_lo <= q_lo + bq - 1            # block not fully in the future
        if window:
            run &= (k_lo + bk - 1) >= (q_lo - window + 1)

    @pl.when(run)
    def _compute():
        q = q_ref[...]                                        # (bq, dh)
        k = k_ref[...]                                        # (bk, dh)
        v = v_ref[...]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < sk
        if causal:
            mask &= kpos <= qpos
            if window:
                mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)
        if sk % bk:
            # the last block reads past the key end; those rows of v hold
            # garbage, and 0 * NaN = NaN would poison the accumulator
            vpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
            v = jnp.where(vpos < sk, v, jnp.zeros_like(v))

        m_prev = m_ref[...]                                   # (bq, 1)
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
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, bq: int = DEFAULT_BQ,
                    bk: int = DEFAULT_BK, interpret: bool = False):
    """q: (b, sq, h, dh); k/v: (b, sk, kv, dh) -> (b, sq, h, dh)."""
    b, sq, h, dh = q.shape
    sk, kv = k.shape[1], k.shape[2]
    g = h // kv
    bq = min(bq, sq)
    bk = min(bk, sk)
    nq = -(-sq // bq)
    nk = -(-sk // bk)
    scale = 1.0 / math.sqrt(dh)

    kern = functools.partial(
        _kernel, causal=causal, window=window, softcap=softcap, scale=scale,
        bq=bq, bk=bk, nk=nk, sk=sk)
    q_spec = pl.BlockSpec((None, None, bq, dh),
                          lambda b_, h_, q_, k_: (b_, h_, q_, 0))
    kv_spec = pl.BlockSpec((None, None, bk, dh),
                           lambda b_, h_, q_, k_: (b_, h_ // g, k_, 0))
    out = pl.pallas_call(
        kern,
        grid=(b, h, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        interpret=interpret,
    )(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
      v.transpose(0, 2, 1, 3))
    return out.transpose(0, 2, 1, 3)
