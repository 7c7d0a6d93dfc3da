"""Three steps of the plain reference's training, and leaf norms.

The reference follows the configuration, not the program: the model from
``reference/<name>.py`` in float32, the loss as the traffic file states it
(mean cross-entropy plus ``z_loss`` times the mean squared log-partition),
global-norm clipping and AdamW with decoupled weight decay on every leaf
and bias correction, under linear warm-up. Gradients are summed row by row
so that the float32 step fits next to float32 weights and moments.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

f32 = jnp.float32


def leaf_names(tree) -> list[str]:
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def _norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(f32))))
            for a in jax.tree.leaves(tree)]


@jax.jit
def _diff_norms(a, b):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(f32) - y.astype(f32))))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


def leaf_norms(tree, scale: float = 1.0) -> dict:
    return {n: float(v) * scale
            for n, v in zip(leaf_names(tree), _norms(tree))}


def leaf_diff_norms(a, b) -> dict:
    return {n: float(v) for n, v in zip(leaf_names(a), _diff_norms(a, b))}


def lr_at(opt: dict, step):
    """Linear warm-up to ``peak_lr``, then cosine to a tenth of it."""
    step = jnp.asarray(step, f32)
    warm = opt["peak_lr"] * step / opt["warmup_steps"]
    t = jnp.clip((step - opt["warmup_steps"]) /
                 max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0, 1.0)
    cos = opt["peak_lr"] * (0.1 + 0.9 * 0.5 * (1 + jnp.cos(jnp.pi * t)))
    return jnp.where(step < opt["warmup_steps"], warm, cos)


def _grads(ref, c, z_loss, lowp, w, inputs, targets):
    n = inputs.size

    def row_loss(w, x, y):
        ce, lse2 = ref.loss_sums(w, x[None], y[None], c, lowp)
        return (ce + z_loss * lse2) / n

    vg = jax.value_and_grad(row_loss)

    def body(carry, xy):
        loss, g = vg(w, *xy)
        return (carry[0] + loss, jax.tree.map(jnp.add, carry[1], g)), None

    zero = jax.tree.map(jnp.zeros_like, w)
    (loss, g), _ = jax.lax.scan(body, (jnp.zeros((), f32), zero),
                                (inputs, targets))
    return loss, g


def _clip(opt, g):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    s = jnp.minimum(1.0, opt["grad_clip"] / norm)
    return jax.tree.map(lambda x: x * s, g)


def _adamw(opt, w, m, v, g, step):
    lr = lr_at(opt, step)
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    w = jax.tree.map(
        lambda w, m, v: w - lr * ((m / c1) / (jnp.sqrt(v / c2) + opt["eps"]) +
                                  opt["weight_decay"] * w), w, m, v)
    return w, m, v


def train_readings(ref, c: dict, make_w, batches, opt: dict, z_loss: float,
                   lowp=None, rows: int | None = None) -> dict:
    """Losses of each step, each leaf's first clipped-gradient norm, and
    each leaf's norm of change over all the steps.

    ``make_w()`` returns the seed's weights; ``rows`` keeps only the
    first rows of each batch (the half-batch fault)."""
    w = jax.jit(lambda t: jax.tree.map(lambda a: a.astype(f32), t))(make_w())
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    grads = jax.jit(partial(_grads, ref, c, z_loss, lowp))
    clip = jax.jit(partial(_clip, opt))
    update = jax.jit(partial(_adamw, opt), donate_argnums=(0, 1, 2))
    losses, gnorms = [], None
    for step, b in enumerate(batches, 1):
        x, y = b["inputs"], b["targets"]
        if rows is not None:
            x, y = x[:rows], y[:rows]
        loss, g = grads(w, jnp.asarray(x), jnp.asarray(y))
        g = clip(g)
        if step == 1:
            gnorms = leaf_norms(g)
        w, m, v = update(w, m, v, g, jnp.asarray(step, f32))
        losses.append(float(loss))
        del g
    del m, v
    change = leaf_diff_norms(w, make_w())
    return {"losses": losses, "grad_norms": gnorms, "change_norms": change}
