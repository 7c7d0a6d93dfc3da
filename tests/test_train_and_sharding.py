"""Train-loop numerics, sharding resolver, and HLO cost-model unit tests."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.sharding import Sharder
from repro.train.loop import chunked_cross_entropy


# ------------------------------------------------------------------ loss
def test_chunked_ce_matches_direct():
    key = jax.random.PRNGKey(0)
    b, s, d, v = 2, 32, 16, 50
    hidden = jax.random.normal(key, (b, s, d), jnp.float32)
    w = jax.random.normal(key, (d, v), jnp.float32) * 0.1
    targets = jax.random.randint(key, (b, s), 0, v)
    loss_c, ce_c, n = chunked_cross_entropy(hidden, w, targets, chunk=8,
                                            z_weight=0.0)
    logits = hidden @ w
    lse = jax.scipy.special.logsumexp(logits, -1)
    tgt = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    ce_direct = jnp.mean(lse - tgt)
    assert abs(float(ce_c - ce_direct)) < 1e-5
    assert int(n) == b * s


def test_chunked_ce_ignores_padding():
    key = jax.random.PRNGKey(1)
    hidden = jax.random.normal(key, (1, 8, 4))
    w = jax.random.normal(key, (4, 11))
    targets = jnp.array([[1, 2, -1, -1, 3, -1, 4, 5]])
    _, ce, n = chunked_cross_entropy(hidden, w, targets, chunk=4)
    assert int(n) == 5


def test_grad_accum_matches_full_batch():
    from dataclasses import replace
    from repro.configs import smoke_config
    from repro.models.model import Model
    from repro.models.param import init_params
    from repro.optim import AdamWConfig, adamw_init
    from repro.train.loop import make_train_step

    cfg = replace(smoke_config("stablelm-3b"), dtype="float32")
    model = Model(cfg)
    key = jax.random.PRNGKey(2)
    params = init_params(model.param_template(), key)
    batch = {"inputs": jax.random.randint(key, (4, 16), 0, cfg.vocab_size),
             "targets": jax.random.randint(key, (4, 16), 0, cfg.vocab_size)}
    s1 = make_train_step(model, AdamWConfig(), grad_accum=1, ce_chunk=16)
    s2 = make_train_step(model, AdamWConfig(), grad_accum=2, ce_chunk=16)
    p1, _, m1 = jax.jit(s1)(params, adamw_init(params), batch)
    p2, _, m2 = jax.jit(s2)(params, adamw_init(params), batch)
    # losses are means over microbatches; grads averaged — params must agree
    l1 = jax.tree.leaves(p1)
    l2 = jax.tree.leaves(p2)
    for a, b in zip(l1, l2):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=2e-4, rtol=2e-3)


def test_adamw_descends_quadratic():
    from repro.optim import AdamWConfig, adamw_init, adamw_update
    params = {"w": jnp.array([5.0, -3.0])}
    opt = adamw_init(params)
    cfg = AdamWConfig(peak_lr=0.5, warmup_steps=0, total_steps=100,
                      weight_decay=0.0)
    for _ in range(60):
        grads = {"w": 2 * params["w"]}
        params, opt, _ = adamw_update(params, grads, opt, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.5


def test_int8_grad_compression_roundtrip():
    from repro.optim.adamw import compress_int8
    g = jax.random.normal(jax.random.PRNGKey(3), (1024,)) * 0.1
    q = compress_int8(g, jax.random.PRNGKey(4))
    # unbiased-ish, bounded quantization error
    assert float(jnp.abs(q - g).max()) <= float(jnp.abs(g).max()) / 127 + 1e-6


# ------------------------------------------------------------ sharding rules
def test_sharder_divisibility_fallback():
    sh = Sharder({"data": 16, "model": 16})
    # kv=4 cannot shard 16 ways -> replicated
    assert sh.resolve(("embed", "kv_heads", "head_dim"),
                      (4096, 4, 128)) == jax.sharding.PartitionSpec("data")
    # heads=32 shard over model
    spec = sh.resolve(("embed", "heads", "head_dim"), (4096, 32, 128))
    assert spec == jax.sharding.PartitionSpec("data", "model")


def test_sharder_duplicate_axis_avoidance():
    sh = Sharder({"data": 16, "model": 16})
    # experts takes model; mlp then cannot reuse it
    spec = sh.resolve(("experts", "embed", "mlp"), (16, 6144, 10752))
    assert spec == jax.sharding.PartitionSpec("model", "data")
    # 40 experts: unshardable -> mlp gets model instead
    spec = sh.resolve(("experts", "embed", "mlp"), (40, 1536, 512))
    assert spec == jax.sharding.PartitionSpec(None, "data", "model")


def test_sharder_batch_multi_axis():
    sh = Sharder({"pod": 2, "data": 16, "model": 16})
    spec = sh.resolve(("batch", "seq"), (256, 4096))
    assert spec == jax.sharding.PartitionSpec(("pod", "data"))
    # batch=1 (long_500k): replicate
    assert sh.resolve(("batch",), (1,)) == jax.sharding.PartitionSpec()


def test_sharder_null_noop():
    sh = Sharder.null()
    x = jnp.ones((4, 4))
    assert sh(x, "batch", "seq") is x


# ---------------------------------------------------------- hlo cost model
def test_hlo_walker_counts_scan_trips():
    """Scan-of-matmuls: walker flops must be ~L x the single-layer flops
    (XLA's own cost_analysis undercounts while bodies)."""
    from repro.launch.hlo_analysis import analyze
    L, M = 7, 64

    def f(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, ws)
        return y.sum()

    x = jnp.ones((M, M))
    ws = jnp.ones((L, M, M))
    compiled = jax.jit(f).lower(x, ws).compile()
    res = analyze(compiled.as_text())
    expect = 2 * M * M * M * L
    assert 0.9 * expect <= res["dot_flops"] <= 1.2 * expect, res["dot_flops"]


def test_hlo_walker_matches_xla_on_straightline():
    from repro.launch.hlo_analysis import analyze

    def f(a, b):
        return (a @ b).sum()

    a = jnp.ones((128, 256))
    b = jnp.ones((256, 64))
    compiled = jax.jit(f).lower(a, b).compile()
    res = analyze(compiled.as_text())
    xla = compiled.cost_analysis()["flops"]
    assert abs(res["dot_flops"] - 2 * 128 * 256 * 64) / xla < 0.1

