"""Composable decoder / encoder-decoder model covering all ten architectures.

Layers are grouped into a repeating *cycle* (gemma2: [local, global]; jamba:
[7x mamba + 1x attn, alternating MoE]; dense models: [attn]) and the stack is
a ``lax.scan`` over stacked cycle parameters — bounded HLO size and compile
time at 512 devices regardless of depth.

Three entry points per model (the dry-run lowers each):
* ``forward``     — full teacher-forced pass (train loss path)
* ``prefill``     — forward + KV/SSM cache construction (inference prefill)
* ``decode_step`` — one new token against the cache (inference decode)
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import ssm as S
from repro.models.config import LayerSpec, ModelConfig
from repro.models.param import ParamSpec, stack_cycle
from repro.parallel.sharding import Sharder

f32 = jnp.float32


# ------------------------------------------------------------- templates
def _attn_template(cfg: ModelConfig) -> dict:
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    t = {"ln": L.norm_template(cfg),
         "wq": ParamSpec((d, h, dh), ("embed", "heads", "head_dim"), cfg.dtype),
         "wk": ParamSpec((d, kv, dh), ("embed", "kv_heads", "head_dim"),
                         cfg.dtype),
         "wv": ParamSpec((d, kv, dh), ("embed", "kv_heads", "head_dim"),
                         cfg.dtype),
         "wo": ParamSpec((h, dh, d), ("heads", "head_dim", "embed"),
                         cfg.dtype)}
    if cfg.qk_norm:
        t["qn"] = {"scale": ParamSpec((dh,), (None,), "float32", "zeros")}
        t["kn"] = {"scale": ParamSpec((dh,), (None,), "float32", "zeros")}
    if cfg.post_block_norm:
        t["post_ln"] = L.norm_template(cfg)
    return t


def _mlp_part_template(cfg: ModelConfig, spec: LayerSpec) -> dict:
    t = {"ln": L.norm_template(cfg)}
    t.update(L.moe_template(cfg) if spec.moe else L.mlp_template(cfg))
    if cfg.post_block_norm:
        t["post_ln"] = L.norm_template(cfg)
    return t


def _block_template(cfg: ModelConfig, spec: LayerSpec) -> dict:
    t = {}
    if spec.kind == "attn":
        t["attn"] = _attn_template(cfg)
    else:
        t["ssm"] = {"ln": L.norm_template(cfg), **S.ssm_template(cfg)}
    if spec.cross_attn:
        t["cross"] = _attn_template(cfg)
    if spec.mlp:
        t["mlp"] = _mlp_part_template(cfg, spec)
    return t


class Model:
    def __init__(self, cfg: ModelConfig, sharder: Sharder | None = None):
        self.cfg = cfg
        self.sh = sharder or Sharder.null()

    # --------------------------------------------------------- param spec
    def param_template(self) -> dict:
        cfg = self.cfg
        tpl = {
            "embed": ParamSpec((cfg.vocab_size, cfg.d_model),
                               ("vocab", "embed"), cfg.dtype, "normal", 0.02),
            "blocks": stack_cycle(
                {f"s{i}": _block_template(cfg, spec)
                 for i, spec in enumerate(cfg.cycle)}, cfg.n_cycles),
            "final_norm": L.norm_template(cfg),
        }
        if not cfg.tie_embeddings:
            tpl["lm_head"] = ParamSpec((cfg.d_model, cfg.vocab_size),
                                       ("embed", "vocab"), cfg.dtype,
                                       "normal", 0.02)
        if cfg.encoder:
            enc_spec = LayerSpec(kind="attn", causal=False)
            tpl["encoder"] = {
                "blocks": stack_cycle(
                    {"s0": _block_template(cfg, enc_spec)},
                    cfg.encoder.n_layers),
                "final_norm": L.norm_template(cfg),
            }
        return tpl

    def cache_template(self, batch: int, cache_len: int) -> dict:
        cfg = self.cfg
        per_cycle = {}
        for i, spec in enumerate(cfg.cycle):
            c = {}
            if spec.kind == "attn":
                sc = min(spec.window, cache_len) if spec.window else cache_len
                kvshape = (batch, sc, cfg.n_kv_heads, cfg.head_dim)
                kvaxes = ("batch", "kvseq", "kv_heads", "head_dim")
                c["k"] = ParamSpec(kvshape, kvaxes, cfg.dtype, "zeros")
                c["v"] = ParamSpec(kvshape, kvaxes, cfg.dtype, "zeros")
                c["kpos"] = ParamSpec((batch, sc), ("batch", "kvseq"),
                                      "int32", "neg_ones")
            else:
                c.update(S.ssm_cache_template(cfg, batch))
            if spec.cross_attn:
                xshape = (batch, cfg.encoder.n_frames, cfg.n_kv_heads,
                          cfg.head_dim)
                c["ck"] = ParamSpec(xshape, ("batch", "frames", "kv_heads",
                                             "head_dim"), cfg.dtype, "zeros")
                c["cv"] = ParamSpec(xshape, ("batch", "frames", "kv_heads",
                                             "head_dim"), cfg.dtype, "zeros")
            per_cycle[f"s{i}"] = c
        return stack_cycle(per_cycle, cfg.n_cycles)

    # ------------------------------------------------------------- blocks
    def _project_qkv(self, h, p, positions, use_rope: bool = True):
        cfg = self.cfg
        q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
        if cfg.qk_norm:
            q = L.rms_norm(q, p["qn"]["scale"], cfg.norm_eps)
            k = L.rms_norm(k, p["kn"]["scale"], cfg.norm_eps)
        if use_rope:
            q = L.rope(q, positions, cfg.rope_theta)
            k = L.rope(k, positions, cfg.rope_theta)
        q = self.sh(q, "batch", "seq", "heads", None)
        k = self.sh(k, "batch", "seq", "kv_heads", None)
        v = self.sh(v, "batch", "seq", "kv_heads", None)
        return q, k, v

    def _attn_part(self, x, p, spec: LayerSpec, *, mode, cache, pos,
                   cache_len):
        cfg = self.cfg
        b, sq, _ = x.shape
        h = self.sh(L.apply_norm(x, p["ln"], cfg), "batch", "seq", None)
        if mode == "decode":
            positions = pos[:, None]                      # (b,1)
        else:
            positions = jnp.arange(sq)[None, :]
        q, k, v = self._project_qkv(h, p, positions)

        new_cache = None
        if mode == "decode":
            sc = cache["k"].shape[1]
            idx = pos % sc
            barange = jnp.arange(b)
            kc = cache["k"].at[barange, idx].set(k[:, 0])
            vc = cache["v"].at[barange, idx].set(v[:, 0])
            kp = cache["kpos"].at[barange, idx].set(pos)
            kc = self.sh(kc, "batch", "kvseq", "kv_heads", None)
            vc = self.sh(vc, "batch", "kvseq", "kv_heads", None)
            o = L.decode_attention(q, kc, vc, kp, pos, window=spec.window,
                                   cap=cfg.attn_softcap, sh=self.sh)
            new_cache = {"k": kc, "v": vc, "kpos": kp}
        else:
            o = L.blocked_attention(q, k, v, causal=spec.causal,
                                    window=spec.window, cap=cfg.attn_softcap,
                                    q_blocks=cfg.attn_q_blocks, sh=self.sh)
            if mode == "prefill":
                new_cache = self._build_cache(k, v, spec, cache_len)
        out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        if cfg.post_block_norm:
            out = L.apply_norm(out, p["post_ln"], cfg)
        return x + out, new_cache

    def _build_cache(self, k, v, spec: LayerSpec, cache_len: int) -> dict:
        b, s = k.shape[:2]
        sc = min(spec.window, cache_len) if spec.window else cache_len
        take = min(s, sc)
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

        def place(a, fill):
            buf = jnp.full((b, sc) + a.shape[2:], fill, a.dtype)
            return jax.lax.dynamic_update_slice_in_dim(
                buf, jax.lax.slice_in_dim(a, s - take, s, axis=1), 0, axis=1)

        kc, vc = place(k, 0), place(v, 0)
        kp = place(positions.astype(jnp.int32), -1)
        kc = self.sh(kc, "batch", "kvseq", "kv_heads", None)
        vc = self.sh(vc, "batch", "kvseq", "kv_heads", None)
        return {"k": kc, "v": vc, "kpos": kp}

    def _cross_part(self, x, p, *, mode, cache, enc_out):
        cfg = self.cfg
        h = self.sh(L.apply_norm(x, p["ln"], cfg), "batch", "seq", None)
        q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
        if mode == "decode":
            ck, cv = cache["ck"], cache["cv"]
        else:
            ck = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"])
            cv = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"])
        o = L.blocked_attention(q, ck, cv, causal=False,
                                q_blocks=cfg.attn_q_blocks, sh=self.sh)
        out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        new_cache = {"ck": ck, "cv": cv} if mode in ("prefill",) else \
            ({"ck": ck, "cv": cv} if mode == "decode" else None)
        return x + out, new_cache

    def _mlp_part(self, x, p, spec: LayerSpec):
        cfg = self.cfg
        h = self.sh(L.apply_norm(x, p["ln"], cfg), "batch", "seq", None)
        if spec.moe:
            y, aux = L.moe_mlp(h, p, cfg, sh=self.sh)
        else:
            y, aux = L.mlp(h, p, cfg, sh=self.sh), jnp.zeros((), f32)
        y = self.sh(y, "batch", "seq", None)
        if cfg.post_block_norm:
            y = L.apply_norm(y, p["post_ln"], cfg)
        return x + y, aux

    def _ssm_part(self, x, p, *, mode, cache):
        cfg = self.cfg
        h = self.sh(L.apply_norm(x, p["ln"], cfg), "batch", "seq", None)
        if mode == "train":
            return x + S.ssd_forward(h, p, cfg), None
        if mode == "prefill":
            y, (conv, st) = S.ssd_forward(h, p, cfg, return_state=True)
            return x + y, {"conv": conv, "state": st}
        y, (conv, st) = S.ssd_decode(h, p, cfg, cache["conv"], cache["state"])
        return x + y, {"conv": conv, "state": st}

    def apply_block(self, x, p, spec: LayerSpec, *, mode, cache=None,
                    pos=None, enc_out=None, cache_len=None):
        aux = jnp.zeros((), f32)
        new_cache = {}
        if spec.kind == "attn":
            x, c = self._attn_part(x, p["attn"], spec, mode=mode,
                                   cache=cache, pos=pos, cache_len=cache_len)
            if c:
                new_cache.update(c)
        else:
            x, c = self._ssm_part(x, p["ssm"], mode=mode, cache=cache)
            if c:
                new_cache.update(c)
        if spec.cross_attn:
            x, c = self._cross_part(x, p["cross"], mode=mode, cache=cache,
                                    enc_out=enc_out)
            if c:
                new_cache.update(c)
        if spec.mlp:
            x, a = self._mlp_part(x, p["mlp"], spec)
            aux = aux + a
        return x, aux, (new_cache if mode != "train" else None)

    # -------------------------------------------------------------- stacks
    def _run_blocks(self, x, blocks, *, mode, cache=None, pos=None,
                    enc_out=None, cache_len=None, cycle=None):
        cfg = self.cfg
        cycle = cycle or cfg.cycle

        def cycle_fn(carry, cp, cc):
            x = carry
            aux = jnp.zeros((), f32)
            ncache = {}
            for i, spec in enumerate(cycle):
                x, a, nc = self.apply_block(
                    x, cp[f"s{i}"], spec, mode=mode,
                    cache=None if cc is None else cc[f"s{i}"],
                    pos=pos, enc_out=enc_out, cache_len=cache_len)
                aux = aux + a
                if nc is not None:
                    ncache[f"s{i}"] = nc
            x = self.sh(x, "batch", "act_seq", None)
            return x, (aux, ncache)

        if mode == "train" and cfg.remat != "none":
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if cfg.remat == "dots" else None)
            body = jax.checkpoint(lambda c, p: cycle_fn(c, p, None),
                                  policy=policy)
        elif cache is None:
            def body(c, p):
                return cycle_fn(c, p, None)
        else:
            body = None

        if cache is not None:
            x, (auxs, new_cache) = jax.lax.scan(
                lambda c, xs: cycle_fn(c, xs[0], xs[1]), x, (blocks, cache))
        else:
            x, (auxs, new_cache) = jax.lax.scan(body, x, blocks)
        return x, jnp.sum(auxs), new_cache

    def encode(self, params, enc_embeds):
        cfg = self.cfg
        x = self.sh(enc_embeds, "batch", "frames", None)
        x, _, _ = self._run_blocks(
            x, params["encoder"]["blocks"], mode="encode",
            cycle=(LayerSpec(kind="attn", causal=False),))
        return L.apply_norm(x, params["encoder"]["final_norm"], cfg)

    def _head(self, x, params):
        cfg = self.cfg
        x = L.apply_norm(x, params["final_norm"], cfg)
        logits = jnp.einsum("bsd,dv->bsv", x, self.head_weights(params))
        logits = L.softcap(logits.astype(f32), cfg.final_softcap)
        return self.sh(logits, "batch", "seq", "vocab")

    def _embed(self, params, tokens):
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
        if cfg.embed_scale:
            x = x * math.sqrt(cfg.d_model)
        return self.sh(x, "batch", "act_seq", None)

    def head_weights(self, params):
        """(d_model, vocab) projection used by the chunked loss.

        Constrained to (replicated, vocab-sharded): one cheap all-gather of
        the FSDP axis instead of a per-chunk logits all-reduce over d_model
        partial sums."""
        w = params["embed"].T if self.cfg.tie_embeddings else params["lm_head"]
        return self.sh(w, None, "vocab")

    def forward_hidden(self, params, tokens, enc_embeds=None):
        """Final-norm hidden states (b,s,d) + aux loss — no logits
        materialization (the train loss computes chunked vocab projections)."""
        enc_out = self.encode(params, enc_embeds) if self.cfg.encoder else None
        # named scopes put the train step's device ops down to a layer in
        # the profiler trace (op metadata only; the program is unchanged)
        with jax.named_scope("embed"):
            x = self._embed(params, tokens)
        with jax.named_scope("layers"):
            x, aux, _ = self._run_blocks(x, params["blocks"], mode="train",
                                         enc_out=enc_out)
        x = L.apply_norm(x, params["final_norm"], self.cfg)
        # regather the sequence for the (vocab-parallel) chunked loss
        return self.sh(x, "batch", "seq", None), aux

    # ------------------------------------------------------------ entries
    def forward(self, params, tokens, enc_embeds=None):
        """Teacher-forced pass -> (logits (b,s,V) fp32, aux loss)."""
        enc_out = self.encode(params, enc_embeds) if self.cfg.encoder else None
        x = self._embed(params, tokens)
        x, aux, _ = self._run_blocks(x, params["blocks"], mode="train",
                                     enc_out=enc_out)
        return self._head(x, params), aux

    def prefill(self, params, tokens, cache_len: int | None = None,
                enc_embeds=None):
        """Build the cache; returns (last-position logits (b,V), cache)."""
        cache_len = cache_len or tokens.shape[1]
        enc_out = self.encode(params, enc_embeds) if self.cfg.encoder else None
        x = self._embed(params, tokens)
        x, _, cache = self._run_blocks(x, params["blocks"], mode="prefill",
                                       enc_out=enc_out, cache_len=cache_len)
        logits = self._head(x[:, -1:], params)
        return logits[:, 0], cache

    def decode_step(self, params, cache, tokens, pos):
        """One token step. tokens: (b,), pos: (b,) -> (logits (b,V), cache)."""
        x = self._embed(params, tokens[:, None])
        x, _, new_cache = self._run_blocks(x, params["blocks"], mode="decode",
                                           cache=cache, pos=pos)
        logits = self._head(x, params)
        return logits[:, 0], new_cache
