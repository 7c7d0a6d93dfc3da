"""Trainer: data-lake input + LST checkpoints + XTable sync + restart.

The fault-tolerance loop this implements (designed for 1000+ nodes, exercised
here at host scale):

1. loader reads token shards from an LST table (any format),
2. every ``save_every`` steps the full train state (params + optimizer +
   loader cursor) is committed as an LST checkpoint; XTable translates the
   metadata to the other formats asynchronously,
3. on (re)start, the trainer restores the latest *committed* snapshot —
   through ANY format — and resumes byte-exactly (loader cursor included),
4. elastic restart: the restored host arrays are ``device_put`` against
   whatever mesh the new job has (the chunk metadata carries global shapes,
   so any device count works).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax

from repro.checkpoint import LSTCheckpointManager
from repro.data import LakeDataLoader
from repro.models.model import Model
from repro.models.param import init_params, template_shapes
from repro.optim import AdamWConfig, adamw_init
from repro.train.loop import make_train_step


@dataclass
class TrainerConfig:
    steps: int = 100
    save_every: int = 20
    log_every: int = 10
    ckpt_format: str = "hudi"
    sync_targets: tuple = ("iceberg", "delta")
    restore_format: str | None = None     # restore via a different connector
    opt: AdamWConfig = field(default_factory=AdamWConfig)
    grad_accum: int = 1
    ce_chunk: int = 128


class Trainer:
    def __init__(self, model: Model, loader: LakeDataLoader, fs,
                 ckpt_path: str, cfg: TrainerConfig = TrainerConfig()):
        self.model = model
        self.loader = loader
        self.cfg = cfg
        self.ckpt = LSTCheckpointManager(
            fs, ckpt_path, fmt=cfg.ckpt_format,
            sync_targets=cfg.sync_targets)
        # params and optimizer state are donated: the step's outputs reuse
        # their buffers, so the device holds one copy of the train state
        self.step_fn = jax.jit(make_train_step(
            model, cfg.opt, grad_accum=cfg.grad_accum,
            ce_chunk=cfg.ce_chunk), donate_argnums=(0, 1))
        self.params = None
        self.opt_state = None
        self.start_step = 0
        self.history: list = []

    # ------------------------------------------------------------ lifecycle
    def init_or_restore(self, seed: int = 0) -> int:
        """Restore the newest checkpoint, or start fresh when none exists.

        Only an absent checkpoint starts fresh: a checkpoint that exists
        but cannot be restored (a missing leaf, a torn chunk) raises.
        """
        tpl = self.model.param_template()
        fmt = self.cfg.restore_format or self.cfg.ckpt_format
        if not self.ckpt.steps(fmt):
            self.params = init_params(tpl, jax.random.PRNGKey(seed))
            self.opt_state = adamw_init(self.params)
            self.start_step = 0
            return self.start_step
        shapes = template_shapes(tpl)
        state_tpl = {"params": shapes, "opt": _opt_template(shapes)}
        step, state = self.ckpt.restore_pytree(state_tpl, fmt=fmt)
        self.params = jax.tree.map(jax.numpy.asarray, state["params"])
        self.opt_state = jax.tree.map(jax.numpy.asarray, state["opt"])
        cursor = int(self.ckpt.latest_meta(fmt).get("loader.row", 0))
        self.loader.load_state_dict({"row": cursor})
        self.start_step = step + 1
        return self.start_step

    def save(self, step: int) -> None:
        self.ckpt.save(step, {"params": self.params, "opt": self.opt_state},
                       extra_meta={"loader.row": str(self.loader.row)})

    # ----------------------------------------------------------------- run
    def run(self) -> list:
        if self.params is None:
            self.init_or_restore()
        t0 = time.perf_counter()
        for step in range(self.start_step, self.cfg.steps):
            batch = self.loader.next_batch()
            batch = {k: jax.numpy.asarray(v) for k, v in batch.items()}
            self.params, self.opt_state, metrics = self.step_fn(
                self.params, self.opt_state, batch)
            loss = float(metrics["loss"])
            self.history.append((step, loss))
            if step % self.cfg.log_every == 0:
                dt = time.perf_counter() - t0
                print(f"step {step:5d} loss {loss:.4f} "
                      f"({dt:.1f}s)", flush=True)
            if self.cfg.save_every and step and \
                    step % self.cfg.save_every == 0:
                self.save(step)
                self.ckpt.gc()
        self.save(self.cfg.steps - 1)
        return self.history


def _opt_template(param_shapes):
    import jax.numpy as jnp
    f32 = jnp.float32
    return {
        "step": jax.ShapeDtypeStruct((), jnp.int32),
        "m": jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, f32),
                          param_shapes),
        "v": jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, f32),
                          param_shapes),
        "master": jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, f32),
                               param_shapes),
    }
