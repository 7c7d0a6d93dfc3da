"""Train step: chunked vocab-parallel loss, grad accumulation, AdamW.

* **Chunked cross-entropy** — the (b, s, V) logits tensor is never
  materialized: the loss scans over sequence chunks, projecting each chunk to
  the vocab and reducing immediately. With a 256k vocab (gemma2) this is the
  difference between ~4 GB/device of logits and ~70 MB.
* **Vocab-parallel** — the head projection is sharded over ``model``; XLA
  turns the per-chunk logsumexp/target-pick into partial reductions +
  small all-reduces (Megatron-style parallel CE emerges from sharding).
* **Gradient accumulation** — microbatch scan with fp32 accumulators.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.models.model import Model
from repro.optim import AdamWConfig, adamw_update

f32 = jnp.float32


@dataclass
class TrainState:
    params: dict
    opt: dict
    step: int = 0


def chunked_cross_entropy(hidden, head_w, targets, *, final_softcap: float = 0.0,
                          chunk: int = 512, z_weight: float = 1e-4):
    """Mean CE over valid (target >= 0) tokens, scanning sequence chunks.

    hidden: (b, s, d); head_w: (d, V); targets: (b, s) int32 (-1 = pad).
    """
    b, s, d = hidden.shape
    chunk = min(chunk, s)
    while s % chunk:
        chunk -= 1
    nc = s // chunk
    hc = hidden.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    tc = targets.reshape(b, nc, chunk).transpose(1, 0, 2)

    def body(carry, xs):
        h, t = xs                                    # (b,chunk,d), (b,chunk)
        logits = jnp.einsum("bcd,dv->bcv", h, head_w).astype(f32)
        if final_softcap:
            logits = jnp.tanh(logits / final_softcap) * final_softcap
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(
            logits, jnp.maximum(t, 0)[..., None], axis=-1)[..., 0]
        valid = (t >= 0).astype(f32)
        ce = jnp.sum((lse - tgt) * valid)
        zl = jnp.sum(jnp.square(lse) * valid)
        n = jnp.sum(valid)
        c_ce, c_zl, c_n = carry
        return (c_ce + ce, c_zl + zl, c_n + n), None

    # remat: recompute each chunk's logits in the backward instead of
    # saving (nc, b, chunk, V) fp32 residuals (~4 GiB/device at 256k vocab)
    (ce, zl, n), _ = jax.lax.scan(
        jax.checkpoint(body), (jnp.zeros((), f32),) * 3, (hc, tc))
    n = jnp.maximum(n, 1.0)
    return ce / n + z_weight * zl / n, ce / n, n


def make_loss_fn(model: Model, *, ce_chunk: int = 512):
    cfg = model.cfg

    def loss_fn(params, batch):
        hidden, aux = model.forward_hidden(
            params, batch["inputs"], enc_embeds=batch.get("enc_embeds"))
        with jax.named_scope("cross_entropy"):
            loss, ce, n = chunked_cross_entropy(
                hidden, model.head_weights(params), batch["targets"],
                final_softcap=cfg.final_softcap, chunk=ce_chunk)
        return loss + aux, {"ce": ce, "aux": aux, "tokens": n}

    return loss_fn


def make_train_step(model: Model, opt_cfg: AdamWConfig, *,
                    grad_accum: int = 1, ce_chunk: int = 512,
                    grad_pspecs=None):
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    With grad_accum > 1, the global batch is split along axis 0 into
    microbatches processed by a scan with fp32 grad accumulators (collectives
    for the gradient reduce-scatter overlap with the next microbatch's
    backward under XLA's scheduler).

    grad_pspecs: optional PartitionSpec pytree matching params — pins each
    gradient to its parameter's sharding before the optimizer (without it,
    SPMD materialized e.g. the full-vocab fp32 embedding gradient replicated
    on every device).
    """
    loss_fn = make_loss_fn(model, ce_chunk=ce_chunk)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def pin(grads):
        if grad_pspecs is None:
            return grads
        return jax.tree.map(jax.lax.with_sharding_constraint, grads,
                            grad_pspecs)

    def train_step(params, opt_state, batch):
        if grad_accum == 1:
            (loss, _aux), grads = grad_fn(params, batch)
            grads = pin(grads)
        else:
            def micro(carry, mb):
                acc, lacc = carry
                (lo, _a), g = grad_fn(params, mb)
                acc = jax.tree.map(lambda a, b: a + b.astype(f32), acc, g)
                return (acc, lacc + lo), None

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, f32), params)
            mbs = jax.tree.map(
                lambda x: x.reshape((grad_accum, x.shape[0] // grad_accum) +
                                    x.shape[1:]), batch)
            (gsum, lsum), _ = jax.lax.scan(
                micro, (zeros, jnp.zeros((), f32)), mbs)
            grads = pin(jax.tree.map(lambda g: g / grad_accum, gsum))
            loss = lsum / grad_accum
        with jax.named_scope("optimizer"):
            new_params, new_opt, om = adamw_update(params, grads, opt_state,
                                                   opt_cfg)
        metrics = {"loss": loss, **om}
        return new_params, new_opt, metrics

    return train_step


def train_state_template(model: Model):
    """ShapeDtypeStruct pytree for (params, opt_state) — dry-run inputs."""
    from repro.models.param import template_shapes
    ptpl = template_shapes(model.param_template())
    opt = {
        "step": jax.ShapeDtypeStruct((), jnp.int32),
        "m": jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, f32), ptpl),
        "v": jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, f32), ptpl),
        "master": jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, f32),
                               ptpl),
    }
    return ptpl, opt
