"""Chip smoke run: the lake -> train -> checkpoint -> translate -> serve path
on one accelerator chip, through the entry points a user calls.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py

The model is stablelm-3b at its published widths (d_model 2560, 32 heads x
80, d_ff 6912, vocab 50304) with 4 of its 32 layers; depth is the only size
cut. Weights are random from a seed and the corpus is generated from one.
All phases run in this one process, and each prints one line with its wall
time:

1. device  -- ``jax.devices()[0]`` must be a TPU, else exit 1 before any work
2. kernels -- the three Pallas kernels, compiled for the chip, against their
   ``ref.py`` oracles
3. train   -- ``Trainer`` over a ``LakeDataLoader`` on a Delta corpus
4. ckpt    -- the final save, committed as Hudi and translated to Iceberg and
   Delta; both views must list the saved step
5. resume  -- a fresh ``Trainer`` restores through the Iceberg view (params
   byte-identical, same step and loader cursor) and takes one more step
6. serve   -- ``ServeEngine.from_lake(fmt="iceberg")`` answers 4 requests;
   every generated position is checked against ``model.forward`` without a
   KV cache

Any failed check raises, and the script exits non-zero. The last line of
standard output is ``{"ok": true, "device": {...}}``. Lake tables live in a
temporary directory that is removed at the end.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, replace

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "stablelm-3b"
SSM_ARCH = "mamba2-2.7b"
DEPTH = 4                   # of stablelm-3b's 32 layers (cycle = 1 layer)
BATCH = 4
SEQ = 2048
TRAIN_STEPS = 3
PROMPT_LENS = (64, 128, 256, 512)
NEW_TOKENS = 16
SEED = 0

# (atol, rtol) of each kernel against its oracle: bf16 inputs for attention
# (the oracle computes in f32 from the same bf16 values), f32 for SSD
ATTN_TOL = (2e-2, 2e-2)
SSD_TOL = (1e-3, 1e-3)
# served logits vs the no-cache forward: both are bf16 logits rounded on
# different accumulation paths, so allow 1/16 of the logit scale (8-16
# bf16 ulps); a wrong cache slot, position or mask moves logits by O(scale)
SERVE_REL_TOL = 2.0 ** -4


class SmokeError(RuntimeError):
    """A check of the smoke run failed."""


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)


def chip_config():
    """stablelm-3b at published widths, depth cut to DEPTH layers."""
    from repro.configs import get_config
    return replace(get_config(ARCH), n_layers=DEPTH)


# --------------------------------------------------------------- kernels
def _close(name, out, ref, tol) -> str:
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    require(out.shape == ref.shape, f"{name}: shape {out.shape} != {ref.shape}")
    require(bool(np.isfinite(out).all()), f"{name}: non-finite output")
    err = float(np.max(np.abs(out - ref)))
    atol, rtol = tol
    require(bool(np.all(np.abs(out - ref) <= atol + rtol * np.abs(ref))),
            f"{name}: max |kernel - ref| = {err:.3e} beyond atol {atol} "
            f"rtol {rtol}")
    return f"{name} max_err={err:.3e}"


def run_kernels(attn_cfg, ssm_cfg, *, seq: int, interpret: bool) -> list:
    """Each Pallas kernel once against its oracle, at the configs' widths:
    flash and decode attention at ``attn_cfg``'s heads and head_dim and at
    head_dim 128, SSD at ``ssm_cfg.ssm``'s. Returns one summary per call."""
    from repro.kernels.decode_attention.ops import decode_attention_op
    from repro.kernels.decode_attention.ref import decode_attention_ref
    from repro.kernels.flash_attention.ops import flash_attention_op
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.ssd.ops import ssd_op
    from repro.kernels.ssd.ref import ssd_ref

    key = jax.random.PRNGKey(SEED)
    h, kv = attn_cfg.n_heads, attn_cfg.n_kv_heads
    out = []
    for dh in sorted({attn_cfg.head_dim, 128}):
        k1, k2, k3, k4, key = jax.random.split(key, 5)
        b = 2
        q = jax.random.normal(k1, (b, seq, h, dh), jnp.bfloat16)
        k = jax.random.normal(k2, (b, seq, kv, dh), jnp.bfloat16)
        v = jax.random.normal(k3, (b, seq, kv, dh), jnp.bfloat16)
        got = flash_attention_op(q, k, v, causal=True, interpret=interpret)
        with jax.default_matmul_precision("highest"):
            want = attention_ref(q, k, v, causal=True)
        out.append(_close(f"flash_attention[h={h},dh={dh},s={seq}]", got,
                          want, ATTN_TOL))

        lengths = jax.random.randint(k4, (b,), 1, seq + 1)
        got = decode_attention_op(q[:, 0], k, v, lengths, interpret=interpret)
        with jax.default_matmul_precision("highest"):
            want = decode_attention_ref(q[:, 0], k, v, lengths)
        out.append(_close(f"decode_attention[h={h},dh={dh},S={seq}]", got,
                          want, ATTN_TOL))

    sc = ssm_cfg.ssm
    ks = jax.random.split(key, 5)
    b, s, nh, p, g, n = 1, seq, sc.n_heads, sc.head_dim, sc.n_groups, sc.d_state
    x = jax.random.normal(ks[0], (b, s, nh, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, nh), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,), jnp.float32) * 0.5)
    B = jax.random.normal(ks[3], (b, s, g, n), jnp.float32)
    C = jax.random.normal(ks[4], (b, s, g, n), jnp.float32)
    y, state = ssd_op(x, dt, A, B, C, chunk=sc.chunk, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        y_ref, state_ref = ssd_ref(x, dt, A, B, C)
    tag = f"h={nh},p={p},n={n},chunk={sc.chunk},s={s}"
    out.append(_close(f"ssd.y[{tag}]", y, y_ref, SSD_TOL))
    out.append(_close(f"ssd.state[{tag}]", state, state_ref, SSD_TOL))
    return out


# ------------------------------------------------------------- lake path
@dataclass
class Lake:
    """Where one smoke run keeps its tables, and its shapes."""
    fs: object
    root: str
    batch: int
    seq: int

    @property
    def corpus(self) -> str:
        return f"{self.root}/corpus"

    @property
    def ckpt(self) -> str:
        return f"{self.root}/ckpt"

    def loader(self):
        from repro.data import LakeDataLoader
        return LakeDataLoader(self.fs, self.corpus, "delta",
                              batch_size=self.batch, seq_len=self.seq)


def write_corpus(lake: Lake, vocab: int, steps: int) -> int:
    """A Delta corpus with one packed document per row, enough rows for
    ``steps`` batches; returns the row count."""
    from repro.data import write_synth_corpus
    n_docs = lake.batch * steps
    write_synth_corpus(lake.fs, lake.corpus, fmt="delta", n_docs=n_docs,
                       pack_len=lake.seq + 1, vocab=vocab, seed=SEED)
    return n_docs


def _trainer(model, lake: Lake, steps: int, restore_format=None):
    from repro.train.trainer import Trainer, TrainerConfig
    return Trainer(model, lake.loader(), lake.fs, lake.ckpt, TrainerConfig(
        steps=steps, save_every=0, log_every=1, ckpt_format="hudi",
        sync_targets=("iceberg", "delta"), restore_format=restore_format))


def _finite_losses(history) -> list:
    losses = [loss for _, loss in history]
    require(bool(losses) and all(math.isfinite(x) for x in losses),
            f"non-finite training loss: {losses}")
    return losses


def train(model, lake: Lake, steps: int):
    """Fresh weights, ``steps`` steps, final save; -> (trainer, losses)."""
    tr = _trainer(model, lake, steps)
    require(tr.init_or_restore(seed=SEED) == 0, "trainer did not start fresh")
    return tr, _finite_losses(tr.run())


def check_checkpoint(tr, step: int) -> dict:
    """The saved step is listed by the Hudi source and both translations;
    returns what was written and how long saving and translating took."""
    ev = tr.ckpt.telemetry.events
    errors = [e.detail for e in ev if e.phase == "error"]
    require(not errors, f"translation failed: {errors}")
    for fmt in ("hudi", "iceberg", "delta"):
        steps = tr.ckpt.steps(fmt=fmt)
        require(step in steps, f"{fmt} view lists steps {steps}, not {step}")
    files = [f for f in tr.ckpt.handle.snapshot().files.values()
             if int(f.partition_values["step"]) == step]
    save_s = sum(e.elapsed_s for e in ev if e.phase == "save")
    sync_s = sum(e.elapsed_s for e in ev
                 if e.phase in ("full", "incremental"))
    return {"chunks": len(files),
            "bytes": sum(f.size_bytes for f in files),
            "save_s": save_s, "translate_s": sync_s}


def resume(model, lake: Lake, saved_params, saved_step: int,
           saved_row: int):
    """Restore through Iceberg, check it, take one more step;
    -> (trainer, restore seconds, losses)."""
    tr = _trainer(model, lake, saved_step + 2, restore_format="iceberg")
    t0 = time.perf_counter()
    start = tr.init_or_restore(seed=SEED + 1)
    jax.block_until_ready(tr.params)
    restore_s = time.perf_counter() - t0
    require(start == saved_step + 1,
            f"resumed at step {start}, saved step was {saved_step}")
    require(tr.loader.row == saved_row,
            f"loader cursor {tr.loader.row}, saved cursor was {saved_row}")
    same = jax.tree.map(lambda a, b: np.asarray(a).tobytes() == b.tobytes(),
                        tr.params, saved_params)
    require(all(jax.tree.leaves(same)),
            "params restored through Iceberg differ from the saved ones")
    return tr, restore_s, _finite_losses(tr.run())


def serve(model, lake: Lake, prompt_lens, new_tokens: int, vocab: int):
    """Serve through the Iceberg view and check every generated position
    against ``model.forward`` on the same tokens with no KV cache;
    -> summary dict."""
    from repro.serve.engine import Request, ServeEngine
    max_prompt = max(prompt_lens)
    t0 = time.perf_counter()
    eng = ServeEngine.from_lake(model, lake.fs, lake.ckpt, fmt="iceberg",
                                cache_len=max_prompt + new_tokens)
    jax.block_until_ready(eng.params)
    restore_s = time.perf_counter() - t0

    rng = np.random.default_rng(SEED)
    reqs = [Request(prompt=rng.integers(0, vocab, n).tolist(),
                    max_new=new_tokens) for n in prompt_lens]
    t0 = time.perf_counter()
    outs, logits = eng.generate(reqs, temperature=0.0, return_logits=True)
    gen_s = time.perf_counter() - t0
    require(all(len(o) == new_tokens for o in outs), "short generation")
    require(logits.shape == (new_tokens, len(reqs), vocab),
            f"logits shape {logits.shape}")
    require(bool(np.isfinite(logits).all()), "non-finite served logits")
    toks = np.asarray(outs, np.int32)                       # (b, new)
    require(bool((logits.argmax(-1).T == toks).all()),
            "greedy tokens are not the argmax of their logits")

    # the engine left-pads with token vocab-1; the reference sees the same
    # rows: padded prompt followed by the generated tokens
    rows = np.full((len(reqs), max_prompt + new_tokens), vocab - 1, np.int32)
    for i, r in enumerate(reqs):
        rows[i, max_prompt - len(r.prompt):max_prompt] = r.prompt
    rows[:, max_prompt:] = toks
    fwd = jax.jit(lambda p, t: model.forward(p, t)[0])
    ref = np.asarray(fwd(eng.params, jnp.asarray(rows)), np.float32)
    ref = ref[:, max_prompt - 1:max_prompt - 1 + new_tokens]     # (b,new,V)
    ref = ref.transpose(1, 0, 2)
    scale = float(np.max(np.abs(ref)))
    err = float(np.max(np.abs(logits - ref)))
    require(err <= SERVE_REL_TOL * scale,
            f"served logits differ from the no-cache forward by {err:.4f} "
            f"(logit scale {scale:.3f}, tolerance {SERVE_REL_TOL * scale:.4f})")
    agree = float((ref.argmax(-1) == logits.argmax(-1)).mean())
    return {"restore_s": restore_s, "generate_s": gen_s,
            "max_err": err, "scale": scale, "argmax_agree": agree}


# ---------------------------------------------------------------- phases
def _phase(n: int, name: str, t0: float, detail: str) -> None:
    print(f"phase {n} {name}: {detail} ({time.perf_counter() - t0:.3f} s)",
          flush=True)


def _drop_state(tr) -> None:
    """Release a trainer's device copy of params and optimizer state."""
    tr.params = tr.opt_state = None


def run_phases(cfg, ssm_cfg, *, batch: int, seq: int, steps: int,
               prompt_lens, new_tokens: int, interpret: bool) -> None:
    """Phases 2-6 at ``cfg`` (attention model) and ``ssm_cfg`` (SSD
    kernel widths); each prints its line, any failed check raises."""
    from repro.lst import LocalFS
    from repro.models.model import Model

    t = time.perf_counter()
    lines = run_kernels(cfg, ssm_cfg, seq=seq, interpret=interpret)
    for line in lines:
        print(f"  kernel {line}", flush=True)
    _phase(2, "kernels", t, f"{len(lines)} Pallas outputs agree with their "
           f"oracles (interpret={interpret})")

    root = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        lake = Lake(LocalFS(), root, batch, seq)
        model = Model(cfg)

        t = time.perf_counter()
        rows = write_corpus(lake, cfg.vocab_size, steps + 1)
        tr, losses = train(model, lake, steps)
        saved_step, saved_row = steps - 1, tr.loader.row
        _phase(3, "train", t, f"{rows} corpus rows, {steps} steps at "
               f"b={batch} s={seq}, losses {losses} (incl. corpus write, "
               f"compile and the final save; the step lines above give "
               f"cumulative times, step 0's includes its compile)")

        t = time.perf_counter()
        ck = check_checkpoint(tr, saved_step)
        saved_params = jax.device_get(tr.params)
        _drop_state(tr)
        free = shutil.disk_usage(root).free
        _phase(4, "ckpt", t, f"step {saved_step}: {ck['chunks']} chunks, "
               f"{ck['bytes']} bytes written as hudi in {ck['save_s']:.3f} s, "
               f"translated to iceberg+delta in {ck['translate_s']:.3f} s; "
               f"all three views list it; {free} bytes free on the lake disk")

        t = time.perf_counter()
        tr, restore_s, losses = resume(model, lake, saved_params, saved_step,
                                       saved_row)
        _drop_state(tr)
        _phase(5, "resume", t, f"restored step {saved_step} through iceberg "
               f"in {restore_s:.3f} s, {ck['bytes']} bytes read (params "
               f"byte-identical, cursor {saved_row}), one more step: loss "
               f"{losses}")

        t = time.perf_counter()
        info = serve(model, lake, prompt_lens, new_tokens, cfg.vocab_size)
        _phase(6, "serve", t, f"{len(prompt_lens)} requests, prompts "
               f"{list(prompt_lens)}, {new_tokens} new tokens each: restore "
               f"{info['restore_s']:.3f} s, generate {info['generate_s']:.3f} "
               f"s; max |served - forward| logit {info['max_err']:.4f} at "
               f"scale {info['scale']:.3f} (tolerance {SERVE_REL_TOL} x "
               f"scale), argmax agreement {info['argmax_agree']:.4f}")
    finally:
        shutil.rmtree(root, ignore_errors=True)


def main() -> int:
    t0 = time.perf_counter()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r}",
              file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    _phase(1, "device", t0, json.dumps(device))

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    cfg = chip_config()
    print(f"config: {ARCH} at published widths (d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads x {cfg.head_dim}, kv {cfg.n_kv_heads}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}); depth {DEPTH} of "
          f"{get_config(ARCH).n_layers} layers is the one reduced size; "
          f"random weights and corpus from seed {SEED}", flush=True)
    run_phases(cfg, get_config(SSM_ARCH), batch=BATCH, seq=SEQ,
               steps=TRAIN_STEPS, prompt_lens=PROMPT_LENS,
               new_tokens=NEW_TOKENS, interpret=False)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
