"""Telemetry on the train path: spans (totals, nesting, bounded state, the
profiler annotation), the compile counter, the trainer's per-step spans and
the loader's storage counters."""

from __future__ import annotations

import gc
import os
import subprocess
import sys
import tempfile
import time
from contextlib import contextmanager
from types import ModuleType, SimpleNamespace

import pytest

from repro.core import telemetry as telmod
from repro.core.telemetry import COMPILES, Telemetry
from repro.data import LakeDataLoader, write_synth_corpus

STEP_SPANS = ("train.step", "train.batch", "train.h2d", "train.dispatch",
              "train.loss_sync")


def test_span_totals_count_and_time_nested_blocks():
    tel = Telemetry()
    for _ in range(3):
        with tel.span("outer"):
            with tel.span("inner"):
                time.sleep(0.002)
            with tel.span("inner"):
                pass
    (n_out, s_out), (n_in, s_in) = tel.spans["outer"], tel.spans["inner"]
    assert (n_out, n_in) == (3, 6)
    assert s_in >= 3 * 0.002
    assert s_out >= s_in
    assert tel.events == []


def test_span_total_is_kept_when_the_block_raises():
    tel = Telemetry()
    with pytest.raises(KeyError):
        with tel.span("failing"):
            raise KeyError("x")
    assert tel.spans["failing"][0] == 1


def test_span_state_stays_bounded_over_a_long_run():
    tel = Telemetry()
    for i in range(20_000):
        with tel.span(("a", "b", "c")[i % 3]):
            pass
    assert sorted(tel.spans) == ["a", "b", "c"]
    assert sum(n for n, _ in tel.spans.values()) == 20_000
    assert all(len(v) == 2 for v in tel.spans.values())
    assert tel.events == []


class _Annotation:
    opened: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.opened.append(self.name)

    def __exit__(self, *exc):
        self.opened.append("/" + self.name)


def _fake_jax() -> ModuleType:
    mod = ModuleType("jax")
    mod.profiler = SimpleNamespace(TraceAnnotation=_Annotation)
    return mod


def test_span_is_a_profiler_annotation_only_while_jax_is_loaded(monkeypatch):
    _Annotation.opened = []
    tel = Telemetry()
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    with tel.span("train.step"):
        pass
    assert _Annotation.opened == []
    monkeypatch.setitem(sys.modules, "jax", _fake_jax())
    with tel.span("train.step"):
        with tel.span("train.batch"):
            pass
    assert _Annotation.opened == ["train.step", "train.batch",
                                  "/train.batch", "/train.step"]
    assert tel.spans["train.step"][0] == 2


def test_repro_core_imports_and_spans_without_jax():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = ("import sys\n"
            "import repro.core\n"
            "t = repro.core.Telemetry()\n"
            "with t.span('x'):\n"
            "    pass\n"
            "assert t.spans['x'][0] == 1\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    env = dict(os.environ, PYTHONPATH=src)
    subprocess.run([sys.executable, "-c", code], check=True, env=env)


def test_timed_records_its_event_and_a_span():
    tel = Telemetry()
    with tel.timed("ds", "delta", "full", "3 files"):
        time.sleep(0.001)
    (ev,) = tel.events
    assert (ev.dataset, ev.target, ev.phase, ev.detail) == \
        ("ds", "delta", "full", "3 files")
    assert ev.elapsed_s >= 0.001
    assert tel.spans["full"][0] == 1
    assert "ds -> delta: full 3 files" in tel.timeline()[0]


def test_summary_gives_counters_beside_span_totals():
    tel = Telemetry()
    tel.bump("storage.get", 2)
    with tel.span("train.step"):
        pass
    s = tel.summary()
    assert s["counters"] == {"storage.get": 2}
    assert s["spans"]["train.step"][0] == 1
    s["spans"]["train.step"][0] = 99          # a copy, not the live state
    assert tel.spans["train.step"][0] == 1


def test_compile_listener_copies_its_watchers_under_the_lock():
    import threading
    tel = Telemetry()
    with telmod._watch_lock:
        telmod._compile_watchers.add(tel)
        t = threading.Thread(target=telmod._on_jax_duration,
                             args=(telmod.COMPILE_EVENT, 0.0))
        t.start()
        t.join(0.2)
        assert t.is_alive(), "listener read the watchers without the lock"
    t.join(30)
    assert tel.counters[COMPILES] == 1
    telmod._compile_watchers.discard(tel)


# ------------------------------------------------------------ checkpoint
def _ckpt(fs, tel):
    import numpy as np
    from repro.checkpoint import LSTCheckpointManager
    mgr = LSTCheckpointManager(fs, tempfile.mkdtemp() + "/ckpt", fmt="hudi",
                               sync_targets=(), telemetry=tel)
    return mgr, {"w": np.ones((4, 8), np.float32),
                 "b": np.zeros((8,), np.float32)}


def test_checkpoint_save_records_its_chunk_count_and_a_span(fs):
    tel = Telemetry()
    mgr, tree = _ckpt(fs, tel)
    mgr.save(3, tree)
    (ev,) = [e for e in tel.events if e.phase == "save"]
    assert ev.detail == "step 3: 2 chunks"
    assert tel.spans["save"][0] == 1


def test_a_checkpoint_save_that_raises_records_no_save_event(fs,
                                                             monkeypatch):
    tel = Telemetry()
    mgr, tree = _ckpt(fs, tel)

    def fail(*a, **k):
        raise OSError("commit lost")
    monkeypatch.setattr(mgr.handle, "commit", fail)
    with pytest.raises(OSError):
        mgr.save(3, tree)
    assert [e for e in tel.events if e.phase == "save"] == []
    assert tel.spans["save"][0] == 1


# ------------------------------------------------------------ train path
def _corpus(fs, n_docs=16, pack_len=17, n_shards=4):
    base = tempfile.mkdtemp() + "/corpus"
    write_synth_corpus(fs, base, fmt="delta", n_docs=n_docs,
                       pack_len=pack_len, vocab=256, n_shards=n_shards)
    return base


def _trainer(fs, base, steps=2, log_every=100, telemetry=None):
    from repro.configs import smoke_config
    from repro.models.model import Model
    from repro.train.trainer import Trainer, TrainerConfig
    loader = LakeDataLoader(fs, base, "delta", batch_size=2, seq_len=16)
    return Trainer(Model(smoke_config("stablelm-3b")), loader, fs,
                   tempfile.mkdtemp() + "/ckpt",
                   TrainerConfig(steps=steps, save_every=0,
                                 log_every=log_every, ce_chunk=16),
                   telemetry=telemetry)


def test_trainer_run_spans_every_step_and_shares_the_loaders_telemetry(
        fs, capsys):
    tr = _trainer(fs, _corpus(fs), steps=3, log_every=1)
    assert tr.telemetry is tr.loader.telemetry
    assert tr.ckpt.telemetry is tr.telemetry
    tr.run()
    spans = tr.telemetry.spans
    assert {k: spans[k][0] for k in STEP_SPANS} == dict.fromkeys(STEP_SPANS,
                                                                 3)
    assert spans["train.save"][0] == 1                    # the final save
    inner = sum(spans[k][1] for k in STEP_SPANS[1:])
    assert spans["train.step"][1] >= inner
    assert tr.telemetry.counters["storage.bytes_read"] > 0
    assert spans["data.read_chunk"][0] == 6               # a read per row
    assert any(e.phase == "save" for e in tr.telemetry.events)
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("step")]
    assert len(lines) == 3 and lines[2].endswith("in 2 steps)")


def test_trainer_takes_the_telemetry_it_is_given(fs):
    tel = Telemetry()
    tr = _trainer(fs, _corpus(fs), telemetry=tel)
    assert tr.telemetry is tel and tr.ckpt.telemetry is tel
    assert tr.loader.telemetry is not tel


def test_compile_counter_counts_a_first_run_and_not_a_second(fs):
    tr = _trainer(fs, _corpus(fs), steps=2)
    tel = tr.telemetry
    assert tel.counters[COMPILES] == 0
    tr.run()
    first = tel.counters[COMPILES]
    assert first >= 1
    tr.start_step, tr.cfg.steps = 2, 4                # same shapes again
    tr.run()
    assert tel.counters[COMPILES] == first
    assert tel.spans["train.step"][0] == 4


def test_many_trainers_register_one_compile_listener(fs):
    from jax._src import monitoring
    base = _corpus(fs)
    trainers = [_trainer(fs, base) for _ in range(12)]

    def ours():
        return sum(cb is telmod._on_jax_duration
                   for cb in monitoring.get_event_duration_listeners())
    assert ours() == 1
    assert all(t.telemetry in telmod._compile_watchers for t in trainers)
    watching = len(telmod._compile_watchers)
    del trainers
    gc.collect()
    assert len(telmod._compile_watchers) <= watching - 12
    assert ours() == 1


@contextmanager
def _delta(tel: Telemetry):
    before = dict(tel.counters)
    out: dict = {}
    yield out
    out.update({k: v - before.get(k, 0) for k, v in tel.counters.items()})


def _file_of_row(loader):
    """Row index -> (path, size) of the file the loader reads it from."""
    out = []
    for f in loader._files:
        size = loader.table.fs.inner.size(f"{loader.table.base}/{f.path}")
        out += [(f.path, size)] * f.record_count
    return out


def test_loader_bytes_read_are_the_files_its_rows_came_from(fs):
    ld = LakeDataLoader(fs, _corpus(fs), "delta", batch_size=2, seq_len=16)
    files = _file_of_row(ld)
    assert len({p for p, _ in files}) == 4                 # 4 files, 4 rows
    with _delta(ld.telemetry) as d:
        for _ in range(3):
            ld.next_batch()
    assert d["storage.bytes_read"] == sum(s for _, s in files[:6])
    assert d["storage.get"] == 6
    assert ld.telemetry.spans["data.read_chunk"][0] == 6


def test_prefetch_producer_reads_each_file_once_and_counts_it(fs):
    ld = LakeDataLoader(fs, _corpus(fs), "delta", batch_size=2, seq_len=16,
                        loop=False)
    files = _file_of_row(ld)
    with _delta(ld.telemetry) as d:
        ld.start()
        n = 0
        with pytest.raises(StopIteration):
            while True:
                ld.get(timeout=30)
                n += 1
        ld.stop()
    assert n == 8
    assert d["storage.bytes_read"] == sum(s for _, s in dict(files).items())
    assert d["storage.get"] == 4
    assert ld.telemetry.spans["data.read_chunk"][0] == 4


def test_loader_takes_the_telemetry_it_is_given(fs):
    tel = Telemetry()
    ld = LakeDataLoader(fs, _corpus(fs), "delta", batch_size=2, seq_len=16,
                        telemetry=tel)
    ld.next_batch()
    assert ld.telemetry is tel and tel.counters["storage.get"] >= 2
