"""A training cell: ``Trainer.run`` fed by ``LakeDataLoader`` from the lake.

The window's tokens per second are reported under the name the traffic
file gives (``throughput_metric``), so that mixes whose runs spread
differently hold their own bounds.

Set-up writes the seed's corpus into a lake table in a temporary directory
(translating it first where the traffic reads another format than it
writes), makes the weights on the device, builds one ``Trainer`` and
drives it through its first three steps with ``Trainer.run``. Those steps
are the ones the reference checks; step 0 compiles. The same trainer then
runs the window, as many steps as fill ``--seconds`` at the rate of steps
1 and 2.

``Trainer.run`` ends with a save of the whole train state into the lake
(8 GB at stablelm-3b's 4 layers, far longer than a window); the harness
replaces that trainer's ``save`` with a time stamp, and the window ends at
the stamp, after the last step's loss reached the host.

Every row the loader hands out, in set-up and in the window, is copied as
it comes; after the window each is held against the seed's corpus row that
the loader's cursor was due to hand out (``check.rows_wrong``).
"""

from __future__ import annotations

import shutil
import tempfile
import time

import jax
import numpy as np

from chipbench import check, corpus, reftrain, weights
from chipbench.cell import Cell, load_family, load_reference
from chipbench.common import Outcome, Readings, Stretch
from chipbench.device import memory_peak_bytes
from chipbench.spans import Spans

FIRST_STEPS = 3


class LoaderProxy:
    """The program's loader with a span around each ``next_batch`` and a
    copy of every row it hands out, the window's too."""

    def __init__(self, loader, spans: Spans):
        self.loader = loader
        self.spans = spans
        self.consumed: list[np.ndarray] = []    # (batch, seq + 1) a step
        self.before_batch = None

    def next_batch(self) -> dict:
        if self.before_batch is not None:
            self.before_batch()
        with self.spans.span("bench.next_batch"):
            batch = self.loader.next_batch()
        self.consumed.append(np.concatenate(
            [batch["inputs"], batch["targets"][:, -1:]], axis=1))
        return batch

    def __getattr__(self, name):
        return getattr(self.loader, name)


def consumed_docs(consumed: list[np.ndarray], rows: np.ndarray) -> np.ndarray:
    """Corpus index of every row the steps consumed, -1 where the row is no
    corpus row exactly."""
    index = {r.tobytes(): i for i, r in enumerate(rows)}
    return np.array([index.get(r.astype(np.int32).tobytes(), -1)
                     for b in consumed for r in b], np.int64)


def reference_batches(consumed: list[np.ndarray], docs: np.ndarray,
                      rows: np.ndarray) -> list[dict]:
    """The first steps' batches as the reference trains on them: the
    corpus rows the loader handed out (a row that is no corpus row, as it
    came)."""
    out, i = [], 0
    for full in consumed[:FIRST_STEPS]:
        d = docs[i:i + len(full)]
        full = np.where((d >= 0)[:, None], rows[np.maximum(d, 0)], full)
        out.append({"inputs": full[:, :-1], "targets": full[:, 1:]})
        i += len(full)
    return out


def rows_wrong(proxy: LoaderProxy, fs, path: str, fmt: str,
               rows: np.ndarray) -> tuple[np.ndarray, int]:
    """-> (corpus index of each consumed row, ``rows_wrong``)."""
    docs = consumed_docs(proxy.consumed, rows)
    order = corpus.cursor_order(fs, path, fmt)
    return docs, check.rows_wrong(docs, order, len(rows))


def _translate(fs, path: str, src: str, dst: str, telemetry) -> None:
    from repro.core import SyncConfig, run_sync
    run_sync(SyncConfig.from_dict({
        "sourceFormat": src.upper(), "targetFormats": [dst.upper()],
        "datasets": [{"tableBasePath": path}]}), fs, telemetry)


def build(cell: Cell, seed: int, root: str, spans: Spans):
    """Corpus, loader, weights and trainer; -> (trainer, proxy, rows, maker,
    view), ``view`` the lake, the table's path and the format the loader
    reads it as."""
    from repro.core import Telemetry
    from repro.data import LakeDataLoader
    from repro.lst import LocalFS
    from repro.models.model import Model
    from repro.optim import AdamWConfig, adamw_init
    from repro.train.trainer import Trainer, TrainerConfig

    c, t = cell.config, cell.traffic
    model = Model(load_family(c["family"]).model_config(c))
    fs = LocalFS()
    path = f"{root}/corpus"
    rows = corpus.write(fs, path, t, c["vocab_size"], seed)
    src, dst = t["corpus"]["format"], t["corpus"]["read_format"]
    if dst != src:
        _translate(fs, path, src, dst, Telemetry())
    loader = LakeDataLoader(fs, path, dst, batch_size=t["batch"],
                            seq_len=t["seq_len"])
    proxy = LoaderProxy(loader, spans)
    make = weights.maker(model.param_template())
    key = weights.prng_key(seed)
    params = make(key)
    opt_state = jax.jit(adamw_init)(params)
    trainer = Trainer(model, proxy, fs, f"{root}/ckpt", TrainerConfig(
        steps=1, save_every=0, log_every=1 << 30,
        opt=AdamWConfig(**t["optimizer"])))
    trainer.params, trainer.opt_state = params, opt_state
    return trainer, proxy, rows, (lambda: make(key)), (fs, path, dst)


def first_steps(trainer, make_w, b1: float) -> tuple[dict, float]:
    """Steps 0..2 through ``Trainer.run``; -> (readings, seconds a step)."""
    stamps = trainer.stamps
    trainer.start_step, trainer.cfg.steps = 0, 1
    trainer.run()
    prog = {"grad_norms": reftrain.leaf_norms(trainer.opt_state["m"],
                                              1.0 / (1.0 - b1))}
    trainer.start_step, trainer.cfg.steps = 1, FIRST_STEPS
    t0 = time.perf_counter()
    trainer.run()
    step_s = (stamps[-1] - t0) / (FIRST_STEPS - 1)
    prog["change_norms"] = reftrain.leaf_diff_norms(
        trainer.opt_state["master"], make_w())
    prog["losses"] = [loss for _, loss in trainer.history[:FIRST_STEPS]]
    return prog, step_s


def run(cell: Cell, seed: int, seconds: float, trace_dir: str | None,
        t_start: float, devs, peaks: dict) -> Outcome:
    t = cell.traffic
    spans = Spans()
    root = tempfile.mkdtemp(prefix="chipbench-")
    try:
        trainer, proxy, rows, make_w, view = build(cell, seed, root, spans)
        trainer.stamps = []
        trainer.save = lambda step: trainer.stamps.append(time.perf_counter())
        prog, step_s = first_steps(trainer, make_w, t["optimizer"]["b1"])

        n = max(2, round(seconds / step_s))
        trainer.start_step, trainer.cfg.steps = FIRST_STEPS, FIRST_STEPS + n
        stretch = Stretch(trace_dir, "bench.step_loop")
        proxy.before_batch = stretch.tick
        setup_s = time.perf_counter() - t_start
        stretch.begin()
        w0 = time.perf_counter()
        trainer.run()
        w1 = trainer.stamps[-1]
        stretch.tick(force=True)
        proxy.before_batch = None
        losses = [loss for _, loss in trainer.history[FIRST_STEPS:]]
        tokens = n * t["batch"] * t["seq_len"]
        mem = memory_peak_bytes(devs)
        trainer.params = trainer.opt_state = None

        readings = None
        if trace_dir:
            lo, hi = stretch.t0, stretch.t1
            steps, loader_s = spans.total("bench.next_batch", lo, hi)
            readings = Readings(
                cell.config, t, peaks, cell.chips, trace=stretch.summary(),
                window={"seconds": hi - lo, "steps": steps,
                        "tokens": steps * t["batch"] * t["seq_len"],
                        "loader_s": loader_s})

        docs, wrong = rows_wrong(proxy, *view, rows)
        ref = reftrain.train_readings(
            load_reference(cell.config["reference"]), cell.config, make_w,
            reference_batches(proxy.consumed, docs, rows), t["optimizer"],
            t["z_loss"])
        numbers = check.train_numbers(prog, ref)
        numbers["rows_wrong"] = wrong
        return Outcome(
            end_to_end={t["throughput_metric"]: tokens / (w1 - w0),
                        "setup_s": setup_s},
            numbers=numbers, attempted=n,
            failed=sum(not np.isfinite(x) for x in losses),
            memory_peak_bytes=mem, readings=readings)
    finally:
        shutil.rmtree(root, ignore_errors=True)
