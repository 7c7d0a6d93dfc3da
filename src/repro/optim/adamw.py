"""AdamW with fp32 master weights + moments, bf16 compute params.

Optimizer state is sharded identically to the parameters (ZeRO-style: the
FSDP/TP axes of each param shard its moments), which the dry-run verifies at
512 devices. Optional int8 gradient compression (stochastic rounding around
a per-tensor scale) models DCN-frugal cross-pod all-reduce.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

f32 = jnp.float32


@dataclass(frozen=True)
class AdamWConfig:
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    compress_grads: bool = False   # int8 stochastic-rounding all-reduce model


def adamw_init(params):
    def zeros(p):
        return jnp.zeros(p.shape, f32)
    return {
        "step": jnp.zeros((), jnp.int32),
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        # a copy even where the param is already f32: the train step
        # donates params and state, and one buffer cannot be donated twice
        "master": jax.tree.map(lambda p: jnp.array(p, f32, copy=True), params),
    }


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(f32)))
                        for g in jax.tree.leaves(tree)))


def compress_int8(g, key):
    """Stochastic-rounding int8 quantization (gradient compression model)."""
    scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-12) / 127.0
    noise = jax.random.uniform(key, g.shape, f32, -0.5, 0.5)
    q = jnp.clip(jnp.round(g / scale + noise), -127, 127).astype(jnp.int8)
    return q.astype(f32) * scale


def adamw_update(params, grads, state, cfg: AdamWConfig, lr=None):
    """One AdamW step. grads may be bf16; math in fp32."""
    from repro.optim.schedule import warmup_cosine
    step = state["step"] + 1
    if lr is None:
        lr = warmup_cosine(step, peak_lr=cfg.peak_lr,
                           warmup_steps=cfg.warmup_steps,
                           total_steps=cfg.total_steps)
    gnorm = _global_norm(grads)
    clip = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9))

    b1c = 1 - cfg.b1 ** step.astype(f32)
    b2c = 1 - cfg.b2 ** step.astype(f32)

    def upd(g, m, v, master):
        g = g.astype(f32) * clip
        m = cfg.b1 * m + (1 - cfg.b1) * g
        v = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g)
        mh = m / b1c
        vh = v / b2c
        new_master = master - lr * (mh / (jnp.sqrt(vh) + cfg.eps) +
                                    cfg.weight_decay * master)
        return m, v, new_master

    flat_g, tdef = jax.tree.flatten(grads)
    flat_m = jax.tree.leaves(state["m"])
    flat_v = jax.tree.leaves(state["v"])
    flat_w = jax.tree.leaves(state["master"])
    out = [upd(g, m, v, w) for g, m, v, w in
           zip(flat_g, flat_m, flat_v, flat_w)]
    new_m = jax.tree.unflatten(tdef, [o[0] for o in out])
    new_v = jax.tree.unflatten(tdef, [o[1] for o in out])
    new_master = jax.tree.unflatten(tdef, [o[2] for o in out])
    param_dtypes = jax.tree.map(lambda p: p.dtype, params)
    new_params = jax.tree.map(lambda w, d: w.astype(d), new_master,
                              param_dtypes)
    new_state = {"step": step, "m": new_m, "v": new_v, "master": new_master}
    return new_params, new_state, {"grad_norm": gnorm, "lr": lr}
