"""SSD (Mamba-2 state-space duality) chunk-scan Pallas TPU kernel.

Grid: (batch, head, chunks) — chunks innermost and sequential, so the
inter-chunk state S (n x p) lives in VMEM scratch and is carried across
grid steps (the TPU analogue of mamba2's persistent-state triton kernel;
sequential grid order replaces the GPU's software pipelining).

Per chunk (length Q):
  intra:  Y += ((C B^T) o L) (dt * x)      L = masked cumulative decay
  inter:  Y += (C o exp(cum)) S_prev
  state:  S  = S_prev * exp(total) + B^T ((dt * x) o exp(total - cum))

All contractions are (Q x n)(n x Q)/(Q x Q)(Q x p) MXU shapes with Q, n, p
multiples of the 128-lane granule at production sizes.

The wrapper folds the per-head scalars into the inputs (dt * x and
dt * A), so every block is a 2-D tile whose last two dims are legal on
TPU: dt * A arrives as a (1, Q) row, and the kernel forms its cumulative
sum as both a column and a row with masked reductions over a (Q, Q) tile
instead of a 1-D cumsum and a transpose.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(xdt_ref, da_ref, b_ref, c_ref, y_ref, s_out_ref, state_ref,
            *, q: int, nc: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    xdt = xdt_ref[...]                            # (Q, p) f32
    da = da_ref[...]                              # (1, Q) f32, negative
    B = b_ref[...].astype(jnp.float32)            # (Q, n)
    C = c_ref[...].astype(jnp.float32)            # (Q, n)

    iq = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    ik = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    causal = ik <= iq
    # inclusive cumulative decay, as a column (Q, 1) and as a row (1, Q)
    cum = jnp.sum(jnp.where(causal, da, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(iq == ik, cum, 0.0), axis=0, keepdims=True)
    total = jnp.sum(da, axis=1, keepdims=True)    # (1, 1)

    # ---- intra-chunk (quadratic in Q) ----
    cb = _dot(C, B, ((1,), (1,)))                                  # (Q,Q)
    L = jnp.where(causal, jnp.exp(cum - cum_row), 0.0)
    y = _dot(cb * L, xdt, ((1,), (0,)))

    # ---- inter-chunk ----
    s_prev = state_ref[...]                                        # (n,p)
    y += _dot(C * jnp.exp(cum), s_prev, ((1,), (0,)))

    # ---- state update ----
    w = jnp.exp(total - cum)                                       # (Q,1)
    bx = _dot(B, xdt * w, ((0,), (0,)))                            # (n,p)
    state_ref[...] = s_prev * jnp.exp(total) + bx

    y_ref[...] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _emit_state():
        s_out_ref[...] = state_ref[...]


def ssd_chunk_scan(x, dt, A, B, C, *, chunk: int = 256,
                   interpret: bool = False):
    """x: (b, s, h, p); dt: (b, s, h); A: (h,); B/C: (b, s, g, n).

    Returns (y: (b, s, h, p), final_state: (b, h, n, p)).
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    hpg = h // g
    q = min(chunk, s)
    if s % q:
        raise ValueError(f"sequence {s} is not a multiple of chunk {q}")
    nc = s // q

    # (b, nc, Q, ...) chunked layouts, head-major for clean block addressing
    f32 = jnp.float32
    dtc = dt.astype(f32).reshape(b, nc, q, h).transpose(0, 3, 1, 2)
    xdt = x.astype(f32).reshape(b, nc, q, h, p).transpose(0, 3, 1, 2, 4) * \
        dtc[..., None]                                        # (b,h,nc,Q,p)
    da = (dtc * A.astype(f32)[None, :, None, None])[:, :, :, None, :]
    Bc = B.reshape(b, nc, q, g, n).transpose(0, 3, 1, 2, 4)   # (b,g,nc,Q,n)
    Cc = C.reshape(b, nc, q, g, n).transpose(0, 3, 1, 2, 4)

    def chunk_spec(rows, cols, group=False):
        if group:
            return pl.BlockSpec((None, None, None, rows, cols),
                                lambda b_, h_, c_: (b_, h_ // hpg, c_, 0, 0))
        return pl.BlockSpec((None, None, None, rows, cols),
                            lambda b_, h_, c_: (b_, h_, c_, 0, 0))

    kern = functools.partial(_kernel, q=q, nc=nc)
    y, state = pl.pallas_call(
        kern,
        grid=(b, h, nc),
        in_specs=[chunk_spec(q, p), chunk_spec(1, q),
                  chunk_spec(q, n, group=True), chunk_spec(q, n, group=True)],
        out_specs=[
            chunk_spec(q, p),
            pl.BlockSpec((None, None, n, p), lambda b_, h_, c_: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, nc, q, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(xdt, da, Bc, Cc)
    y = y.transpose(0, 2, 3, 1, 4).reshape(b, s, h, p)
    return y, state
