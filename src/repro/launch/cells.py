"""(architecture x input-shape) cells: step functions + input specs + shardings.

``input_specs`` returns weak-type-correct ``ShapeDtypeStruct`` stand-ins for
every model input — nothing is allocated; the dry-run lowers directly from
these.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.models.config import ShapeCell, get_shape_cell
from repro.models.model import Model
from repro.models.param import template_shapes
from repro.optim import AdamWConfig
from repro.parallel.sharding import Sharder
from repro.train.loop import make_train_step, train_state_template

f32 = jnp.float32

# long_500k needs sub-quadratic attention; every other arch is pure full
# attention, so its long_500k cell is skipped
LONG_CONTEXT_ARCHS = ("gemma2-27b", "jamba-v0.1-52b", "mamba2-2.7b")


def is_applicable(arch: str, shape: str) -> tuple[bool, str]:
    if shape == "long_500k" and arch not in LONG_CONTEXT_ARCHS:
        return False, ("pure full-attention architecture: 512k KV decode "
                       "requires sub-quadratic attention")
    return True, ""


def attn_intermediate_bytes(cfg, cell, sh: Sharder) -> float:
    """Per-device HBM bytes of materialized attention intermediates on the
    XLA path (f32 scores write+read by softmax, bf16 probs write+read by the
    PV matmul = 12 B per visible (q,k) pair; x3 on the train path for
    recompute + backward). The Pallas flash kernel keeps these in VMEM —
    subtracting this models the kernel-path roofline."""
    specs = cfg.layer_specs()
    attn_layers = [s for s in specs if s.kind == "attn"]
    if not attn_layers or cfg.n_heads == 0:
        return 0.0
    def _div(spec) -> int:
        d = 1
        for ax in list(spec):
            for a in (ax if isinstance(ax, tuple) else ((ax,) if ax else ())):
                d *= sh.mesh_axes.get(a, 1)
        return max(d, 1)

    h_loc = cfg.n_heads // _div(sh.resolve(("heads",), (cfg.n_heads,)))
    b_loc = max(1, cell.global_batch //
                _div(sh.resolve(("batch",), (cell.global_batch,))))

    total_pairs = 0.0
    s = cell.seq_len
    for spec in attn_layers:
        if cell.step == "decode":
            klen = min(spec.window, s) if spec.window else s
            pairs = float(klen)                     # one query token
        else:
            nq = max(1, min(cfg.attn_q_blocks, s))
            qb = s // nq
            pairs = 0.0
            for i in range(nq):
                q_lo = i * qb
                k_hi = min(q_lo + qb, s)
                k_lo = max(0, q_lo - spec.window) if spec.window else 0
                pairs += qb * (k_hi - k_lo)
        total_pairs += pairs * b_loc * h_loc
    mult = 3.0 if cell.step == "train" else 1.0
    return total_pairs * 12.0 * mult


@dataclass
class CellBuild:
    arch: str
    shape: str
    step_name: str
    fn: object
    args: tuple
    in_shardings: tuple
    out_shardings: object
    donate_argnums: tuple
    model: Model
    cell: ShapeCell
    n_params: int
    n_active_params: int
    attn_hbm_bytes: float = 0.0   # XLA-path attention intermediates/device


def _counted_params(model: Model) -> tuple[int, int]:
    """(total, active) parameter counts; active discounts unrouted experts."""
    import numpy as np
    from repro.models.param import is_spec
    cfg = model.cfg
    total = expert = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            model.param_template(), is_leaf=is_spec)[0]:
        n = int(np.prod(leaf.shape))
        total += n
        keys = [getattr(k, "key", getattr(k, "name", "")) for k in path]
        if any(k in ("w_in", "w_gate", "w_out") for k in keys) and \
                cfg.n_experts:
            expert += n
    active = total - int(expert * (1 - cfg.top_k / max(cfg.n_experts, 1)))
    return total, active


# sub-1B models: TP over 16 ways is counterproductive — prefer widening data
# parallelism onto the model axis (params are small enough to replicate
# across it; activations shard fully).
_PURE_DP_ARCHS = ("whisper-small",)


def make_cell_sharder(mesh, arch: str, shape: str) -> Sharder:
    overrides = {}
    if shape == "long_500k":
        overrides["kvseq"] = (("data",),)   # sequence-parallel 512k KV
    if arch in _PURE_DP_ARCHS:
        overrides.update({
            "batch": (("pod", "data", "model"), ("pod", "data")),
            "embed": (),                     # replicate the small params
            "act_seq": (),
        })
    return Sharder.for_mesh(mesh, overrides)


def _arch_cfg(arch: str, shape: str):
    cfg = get_config(arch)
    if shape == "long_500k":
        cfg = cfg.with_updates(long_context_seq_shard=True)
    return cfg


def _token_specs(sh: Sharder, b: int, s: int):
    spec = jax.ShapeDtypeStruct((b, s), jnp.int32)
    pspec = sh.resolve(("batch", "seq"), (b, s))
    return spec, pspec


def build_cell(arch: str, shape: str, mesh, *,
               grad_accum: int = 1) -> CellBuild:
    ok, why = is_applicable(arch, shape)
    if not ok:
        raise ValueError(f"{arch} x {shape} skipped: {why}")
    cell = get_shape_cell(shape)
    cfg = _arch_cfg(arch, shape)
    sh = make_cell_sharder(mesh, arch, shape)
    model = Model(cfg, sh)
    n_params, n_active = _counted_params(model)
    attn_hbm = attn_intermediate_bytes(cfg, cell, sh)

    params_shapes = template_shapes(model.param_template())
    params_pspecs = sh.template_pspecs(model.param_template())
    b, s = cell.global_batch, cell.seq_len

    if cell.step == "train":
        ptpl, opt_shapes = train_state_template(model)
        opt_pspecs = {
            "step": P(),
            "m": params_pspecs, "v": params_pspecs, "master": params_pspecs,
        }
        tok, tok_p = _token_specs(sh, b, s)
        batch_shapes = {"inputs": tok, "targets": tok}
        batch_pspecs = {"inputs": tok_p, "targets": tok_p}
        if cfg.encoder:
            eshape = (b, cfg.encoder.n_frames, cfg.d_model)
            batch_shapes["enc_embeds"] = jax.ShapeDtypeStruct(
                eshape, jnp.dtype(cfg.dtype))
            batch_pspecs["enc_embeds"] = sh.resolve(
                ("batch", "frames", None), eshape)
        step = make_train_step(model, AdamWConfig(), grad_accum=grad_accum,
                               grad_pspecs=params_pspecs)
        metrics_p = {"loss": P(), "grad_norm": P(), "lr": P()}
        return CellBuild(
            arch, shape, "train_step", step,
            (params_shapes, opt_shapes, batch_shapes),
            (params_pspecs, opt_pspecs, batch_pspecs),
            (params_pspecs, opt_pspecs, metrics_p),
            (0, 1), model, cell, n_params, n_active, attn_hbm)

    if cell.step == "prefill":
        tok, tok_p = _token_specs(sh, b, s)
        cache_tpl = model.cache_template(b, s)
        cache_pspecs = sh.template_pspecs(cache_tpl)
        logits_p = sh.resolve(("batch", "vocab"), (b, cfg.vocab_size))
        args = [params_shapes, tok]
        in_sh = [params_pspecs, tok_p]
        if cfg.encoder:
            eshape = (b, cfg.encoder.n_frames, cfg.d_model)
            args.append(jax.ShapeDtypeStruct(eshape, jnp.dtype(cfg.dtype)))
            in_sh.append(sh.resolve(("batch", "frames", None), eshape))

            def fn(params, tokens, enc):
                return model.prefill(params, tokens, cache_len=s,
                                     enc_embeds=enc)
        else:
            def fn(params, tokens):
                return model.prefill(params, tokens, cache_len=s)
        return CellBuild(
            arch, shape, "prefill_step", fn, tuple(args), tuple(in_sh),
            (logits_p, cache_pspecs), (), model, cell, n_params,
            n_active, attn_hbm)

    # decode: one new token against a cache of length seq_len
    cache_tpl = model.cache_template(b, s)
    cache_shapes = template_shapes(cache_tpl)
    cache_pspecs = sh.template_pspecs(cache_tpl)
    tok = jax.ShapeDtypeStruct((b,), jnp.int32)
    pos = jax.ShapeDtypeStruct((b,), jnp.int32)
    tok_p = sh.resolve(("batch",), (b,))
    logits_p = sh.resolve(("batch", "vocab"), (b, cfg.vocab_size))

    def fn(params, cache, tokens, posv):
        return model.decode_step(params, cache, tokens, posv)

    return CellBuild(
        arch, shape, "serve_step", fn,
        (params_shapes, cache_shapes, tok, pos),
        (params_pspecs, cache_pspecs, tok_p, tok_p),
        (logits_p, cache_pspecs), (1,), model, cell, n_params, n_active,
        attn_hbm)


def input_specs(arch: str, shape: str, mesh) -> dict:
    """Public helper: ShapeDtypeStruct stand-ins for every input of the cell."""
    cb = build_cell(arch, shape, mesh)
    return {"step": cb.step_name, "args": cb.args,
            "in_shardings": cb.in_shardings}
