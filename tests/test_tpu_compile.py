"""Compiles for a TPU v5e that is described, not attached.

The TPU compiler is installed with jax, and it refuses here what the chip
would refuse: illegal kernel block shapes, too much fast memory, a program
that does not fit the device's HBM, a sharding that cannot be partitioned.
Nothing runs, so these tests say nothing about results or times.

The topology is described inside a module fixture, never while a module
is imported: only one process may load the TPU library at a time, and the
test runner's workers each import every test file.
"""

import os
import tempfile
from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config

HBM_BYTES = 16 * 2**30          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # programs compiled for a described chip cannot be read back from the
    # persistent cache without one; keep them out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes +
            m.temp_size_in_bytes - m.alias_size_in_bytes)


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()     # the Pallas kernel
    return compiled


# --------------------------------------------------------------- kernels
@pytest.mark.parametrize("head_dim", [80, 128])
def test_flash_attention_compiles(one_chip, head_dim):
    from repro.kernels.flash_attention.ops import flash_attention_op
    cfg = get_config("stablelm-3b")
    qkv = jax.ShapeDtypeStruct((4, 2048, cfg.n_heads, head_dim),
                               jnp.bfloat16, sharding=one_chip)
    _compile_kernel(flash_attention_op, qkv, qkv, qkv)


@pytest.mark.parametrize("head_dim", [80, 128])
def test_decode_attention_compiles(one_chip, head_dim):
    from repro.kernels.decode_attention.ops import decode_attention_op
    cfg = get_config("stablelm-3b")
    q = jax.ShapeDtypeStruct((4, cfg.n_heads, head_dim), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((4, 2048, cfg.n_kv_heads, head_dim),
                              jnp.bfloat16, sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=one_chip)
    _compile_kernel(decode_attention_op, q, kv, kv, lengths)


def test_ssd_compiles_at_mamba2_widths(one_chip):
    from repro.kernels.ssd.ops import ssd_op
    sc = get_config("mamba2-2.7b").ssm
    b, s, f32 = 1, 2048, jnp.float32

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, f32, sharding=one_chip)

    _compile_kernel(
        lambda x, dt, A, B, C: ssd_op(x, dt, A, B, C, chunk=sc.chunk),
        shape(b, s, sc.n_heads, sc.head_dim), shape(b, s, sc.n_heads),
        shape(sc.n_heads), shape(b, s, sc.n_groups, sc.d_state),
        shape(b, s, sc.n_groups, sc.d_state))


# ----------------------------------------------------------- main path
def test_serve_decode_step_full_depth_fits_one_chip(one_chip):
    """The engine's decode step for all 32 layers of stablelm-3b, with a
    4 x 2048 KV cache."""
    from repro.models.model import Model
    from repro.models.param import template_shapes
    from repro.serve.engine import ServeEngine
    model = Model(get_config("stablelm-3b"))
    eng = ServeEngine(model, None, cache_len=2048)
    params = _on(template_shapes(model.param_template()), one_chip)
    cache = _on(template_shapes(model.cache_template(4, 2048)), one_chip)
    tok = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=one_chip)
    compiled = eng._step.lower(params, cache, tok, tok).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_trainer_step_donates_state_and_fits_one_chip(one_chip, fs):
    """``Trainer``'s own jitted step at full width, 4 layers, b=4 s=2048:
    params and optimizer state are donated, so the outputs reuse them."""
    from repro.data import LakeDataLoader, write_synth_corpus
    from repro.models.model import Model
    from repro.train.loop import train_state_template
    from repro.train.trainer import Trainer, TrainerConfig
    root = tempfile.mkdtemp()
    write_synth_corpus(fs, f"{root}/corpus", n_docs=1, pack_len=3, vocab=8)
    model = Model(replace(get_config("stablelm-3b"), n_layers=4))
    loader = LakeDataLoader(fs, f"{root}/corpus", "delta", batch_size=1,
                            seq_len=2)
    tr = Trainer(model, loader, fs, f"{root}/ckpt", TrainerConfig())
    params, opt = train_state_template(model)
    tokens = jax.ShapeDtypeStruct((4, 2048), jnp.int32, sharding=one_chip)
    compiled = tr.step_fn.lower(
        _on(params, one_chip), _on(opt, one_chip),
        {"inputs": tokens, "targets": tokens}).compile()
    state_bytes = sum(x.size * jnp.dtype(x.dtype).itemsize
                      for x in jax.tree.leaves((params, opt)))
    # every state buffer is aliased (small leaves are padded to tiles)
    assert compiled.memory_analysis().alias_size_in_bytes >= state_bytes
    assert _device_bytes(compiled) < HBM_BYTES


def test_sharded_train_cell_compiles_on_2x2(topo, monkeypatch):
    """``launch.cells.build_cell`` on a 2 x 2 (data, model) mesh of the
    described chips, compiled through the dry run's ``compile_cell``.
    The cell is cut to 4 layers and a global batch of 8 at 2048 tokens so
    that it fits four chips."""
    from repro.launch import cells
    from repro.launch.dryrun import compile_cell
    from repro.launch.mesh import make_mesh
    from repro.models.config import ShapeCell
    real = cells.get_config
    monkeypatch.setattr(cells, "get_config",
                        lambda arch: replace(real(arch), n_layers=4))
    monkeypatch.setattr(cells, "get_shape_cell",
                        lambda name: ShapeCell(name, "train", 2048, 8))
    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    cb = cells.build_cell("stablelm-3b", "train_4k", mesh)
    compiled, _, _ = compile_cell(cb, mesh)
    text = compiled.as_text()
    assert "all-reduce" in text or "reduce-scatter" in text
    assert _device_bytes(compiled) < HBM_BYTES
