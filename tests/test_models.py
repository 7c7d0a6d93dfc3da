"""Per-architecture smoke tests (reduced configs) + decode consistency."""

from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, get_config, smoke_config
from repro.models.config import LayerSpec, SHAPE_CELLS
from repro.models.model import Model
from repro.models.param import count_params, init_params

KEY = jax.random.PRNGKey(0)
B, S = 2, 24


def _inputs(cfg, s=S):
    tokens = jax.random.randint(KEY, (B, s), 0, cfg.vocab_size)
    kw = {}
    if cfg.encoder:
        kw["enc_embeds"] = jax.random.normal(
            KEY, (B, cfg.encoder.n_frames, cfg.d_model), jnp.dtype(cfg.dtype))
    return tokens, kw


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_step(arch):
    """One forward + one train step on CPU: shapes + finiteness."""
    cfg = smoke_config(arch)
    model = Model(cfg)
    params = init_params(model.param_template(), KEY)
    tokens, kw = _inputs(cfg)
    logits, aux = jax.jit(lambda p, t: model.forward(p, t, **kw))(params,
                                                                  tokens)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    # one train step
    from repro.optim import AdamWConfig, adamw_init
    from repro.train.loop import make_train_step
    step = jax.jit(make_train_step(model, AdamWConfig(), ce_chunk=S))
    opt = adamw_init(params)
    batch = {"inputs": tokens, "targets": tokens}
    batch.update(kw)
    if cfg.encoder:
        batch["enc_embeds"] = kw["enc_embeds"]
    p2, o2, metrics = step(params, opt, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert int(o2["step"]) == 1


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_teacher_forcing(arch):
    """prefill+decode must reproduce the teacher-forced logits (fp32,
    capacity high enough that MoE drops nothing)."""
    cfg = replace(smoke_config(arch), dtype="float32", capacity_factor=8.0)
    if arch == "gemma2-27b":   # exercise the ring-buffer window path
        cfg = replace(cfg, cycle=(LayerSpec(kind="attn", window=8),
                                  LayerSpec(kind="attn", window=0)))
    model = Model(cfg)
    params = init_params(model.param_template(), KEY)
    tokens, kw = _inputs(cfg, S + 1)
    full, _ = model.forward(params, tokens, **kw)
    last, cache = model.prefill(params, tokens[:, :S], cache_len=S + 8, **kw)
    assert float(jnp.max(jnp.abs(full[:, S - 1] - last))) < 2e-3
    logits2, _ = model.decode_step(params, cache, tokens[:, S],
                                   jnp.full((B,), S, jnp.int32))
    assert float(jnp.max(jnp.abs(full[:, S] - logits2))) < 2e-3


def test_full_config_parameter_counts():
    """Full configs build templates with plausible parameter counts
    (templates only — no allocation)."""
    expect = {
        "gemma2-27b": (24e9, 30e9),
        "stablelm-3b": (2e9, 4e9),
        "yi-9b": (8e9, 10e9),
        "starcoder2-15b": (14e9, 17e9),
        "dbrx-132b": (120e9, 142e9),
        "granite-moe-3b-a800m": (2.5e9, 4.5e9),
        "jamba-v0.1-52b": (45e9, 60e9),
        "chameleon-34b": (30e9, 38e9),
        "whisper-small": (0.15e9, 0.35e9),
        "mamba2-2.7b": (2.2e9, 3.2e9),
    }
    for arch, (lo, hi) in expect.items():
        n = count_params(Model(get_config(arch)).param_template())
        assert lo <= n <= hi, (arch, f"{n:,}")


@pytest.mark.parametrize("n_layers", [2, 4])
def test_stacked_weights_init_at_layer_fan_in(n_layers):
    """Stacking layers for the scan must not change a weight's init: its
    fan-in is the layer's input width, not the number of layers."""
    cfg = replace(get_config("stablelm-3b"), n_layers=n_layers, d_model=512,
                  d_ff=1024, vocab_size=256)
    params = init_params(Model(cfg).param_template(), KEY)
    wq = params["blocks"]["s0"]["attn"]["wq"].astype(jnp.float32)
    assert abs(float(jnp.std(wq)) * 512 ** 0.5 - 1.0) < 0.05


def test_moe_capacity_drops_tokens():
    cfg = replace(smoke_config("dbrx-132b"), dtype="float32",
                  capacity_factor=0.25)
    model = Model(cfg)
    params = init_params(model.param_template(), KEY)
    tokens, _ = _inputs(cfg)
    logits, aux = model.forward(params, tokens)
    assert bool(jnp.isfinite(logits).all())      # drops are benign
    assert float(aux) > 0.0                      # aux losses active


def test_shape_cells_defined():
    names = [c.name for c in SHAPE_CELLS]
    assert names == ["train_4k", "prefill_32k", "decode_32k", "long_500k"]
    assert SHAPE_CELLS[3].global_batch == 1
    assert SHAPE_CELLS[0].step == "train"
