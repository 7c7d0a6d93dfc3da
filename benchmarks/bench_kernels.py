"""Kernel-adjacent micro-benchmarks on the host clock: the XLA blocked
attention, the sequential SSD oracle and MoE dispatch. None of them calls
the Pallas kernels in ``repro.kernels``."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

# --quick smoke mode (set by benchmarks.run): single timed iteration
QUICK = False


def _time(fn, *args, iters=3):
    if QUICK:
        iters = 1
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        (out[0] if isinstance(out, tuple) else out).block_until_ready()
    return (time.perf_counter() - t0) / iters


def bench_attention_xla(report):
    from repro.models.layers import blocked_attention
    key = jax.random.PRNGKey(0)
    b, s, h, kv, dh = 2, 1024, 8, 2, 64
    q = jax.random.normal(key, (b, s, h, dh), jnp.float32)
    k = jax.random.normal(key, (b, s, kv, dh), jnp.float32)
    fn = jax.jit(lambda q, k, v: blocked_attention(q, k, v, q_blocks=8))
    dt = _time(fn, q, k, k)
    flops = 4 * b * h * dh * s * s * 9 / 16
    report("attn.xla_blocked_1k", dt * 1e6, f"{flops / dt / 1e9:.1f}GFLOP/s")


def bench_ssd_xla(report):
    from repro.kernels.ssd.ref import ssd_ref
    key = jax.random.PRNGKey(0)
    b, s, h, p, n = 2, 512, 8, 32, 32
    x = jax.random.normal(key, (b, s, h, p))
    dt_ = jax.nn.softplus(jax.random.normal(key, (b, s, h)))
    A = -jnp.exp(jax.random.normal(key, (h,)) * 0.3)
    B = jax.random.normal(key, (b, s, 1, n))
    fn = jax.jit(lambda *a: ssd_ref(*a)[0])
    dt = _time(fn, x, dt_, A, B, B)
    report("ssd.ref_seq_512", dt * 1e6, "sequential oracle")


def bench_moe_dispatch(report):
    from repro.models.layers import moe_mlp
    from repro.configs import smoke_config
    from repro.models.param import init_params
    from repro.models.layers import moe_template
    from dataclasses import replace
    key = jax.random.PRNGKey(0)
    cfg = replace(smoke_config("dbrx-132b"), d_model=128, d_ff=256,
                  n_experts=8, top_k=2)
    p = init_params(moe_template(cfg), key)
    x = jax.random.normal(key, (4, 512, 128), jnp.bfloat16)
    fn = jax.jit(lambda x, p: moe_mlp(x, p, cfg)[0])
    dt = _time(fn, x, p)
    report("moe.dispatch_gshard", dt * 1e6, "sort+gather combine")


ALL = [bench_attention_xla, bench_ssd_xla, bench_moe_dispatch]
