"""Checkpoint-as-LST + data pipeline tests (the framework integration)."""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import LSTCheckpointManager
from repro.data import LakeDataLoader, write_synth_corpus
from repro.lst import LakeTable


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "w": {"a": jax.random.normal(k, (64, 32), jnp.float32),
              "b": jax.random.normal(k, (8, 128), jnp.bfloat16)},
        "step_count": jnp.asarray(7, jnp.int32),
    }


def test_checkpoint_roundtrip_all_formats(fs):
    base = tempfile.mkdtemp() + "/ckpt"
    mgr = LSTCheckpointManager(fs, base, fmt="hudi",
                               sync_targets=("delta", "iceberg"))
    tree = _tree()
    mgr.save(10, tree)
    for fmt in (None, "delta", "iceberg"):     # None = native hudi
        step, back = mgr.restore_pytree(tree, fmt=fmt)
        assert step == 10
        np.testing.assert_array_equal(np.asarray(back["w"]["a"]),
                                      np.asarray(tree["w"]["a"]))
        assert back["w"]["b"].dtype == np.asarray(tree["w"]["b"]).dtype


def test_checkpoint_multiple_steps_and_latest(fs):
    base = tempfile.mkdtemp() + "/ckpt"
    mgr = LSTCheckpointManager(fs, base, fmt="delta", sync_targets=())
    for s in (1, 5, 9):
        mgr.save(s, {"x": jnp.full((4,), s, jnp.float32)})
    assert mgr.steps() == [1, 5, 9]
    step, flat = mgr.restore()
    assert step == 9
    np.testing.assert_array_equal(flat["x"], np.full((4,), 9, np.float32))
    step, flat = mgr.restore(5)
    np.testing.assert_array_equal(flat["x"], np.full((4,), 5, np.float32))


def test_checkpoint_resave_step_replaces(fs):
    base = tempfile.mkdtemp() + "/ckpt"
    mgr = LSTCheckpointManager(fs, base, fmt="hudi", sync_targets=())
    mgr.save(3, {"x": jnp.zeros((4,))})
    mgr.save(3, {"x": jnp.ones((4,))})
    step, flat = mgr.restore(3)
    np.testing.assert_array_equal(flat["x"], np.ones((4,)))


def test_checkpoint_sharding_large_leaf(fs, monkeypatch):
    import repro.checkpoint.manager as m
    monkeypatch.setattr(m, "MAX_CHUNK_BYTES", 1024)
    base = tempfile.mkdtemp() + "/ckpt"
    mgr = LSTCheckpointManager(fs, base, fmt="iceberg", sync_targets=())
    big = jnp.arange(4096, dtype=jnp.float32).reshape(64, 64)
    mgr.save(0, {"big": big})
    st = mgr.handle.snapshot()
    assert len(st.files) > 1                      # split into shards
    _, flat = mgr.restore(0)
    np.testing.assert_array_equal(flat["big"], np.asarray(big))


def test_gc_respects_translated_targets(fs):
    base = tempfile.mkdtemp() + "/ckpt"
    mgr = LSTCheckpointManager(fs, base, fmt="hudi",
                               sync_targets=("delta",), keep_last=1)
    for s in range(4):
        mgr.save(s, {"x": jnp.full((2,), s, jnp.float32)})
    # targets synced after each save -> gc may collect
    dropped = mgr.gc()
    assert dropped == [0, 1, 2]
    assert mgr.steps() == [3]
    # delta view (after the gc sync) also converges to step 3 only
    mgr.sync_now()
    t = LakeTable.open(fs, base, "delta")
    steps = {int(f.partition_values["step"]) for f in t.state().files.values()}
    assert steps == {3}


def test_gc_deferred_when_target_unsynced(fs, monkeypatch):
    base = tempfile.mkdtemp() + "/ckpt"
    mgr = LSTCheckpointManager(fs, base, fmt="hudi",
                               sync_targets=("delta",), keep_last=1)
    mgr.save(0, {"x": jnp.zeros((2,))})
    mgr.save(1, {"x": jnp.ones((2,))})
    # break the delta sync token by pretending sync never ran:
    # write extra commits without syncing
    monkeypatch.setattr(mgr, "sync_now", lambda: [])
    mgr.save(2, {"x": jnp.ones((2,))})
    assert mgr.gc() == []                         # deferred, not corrupted


# ------------------------------------------------------------ data pipeline
def test_loader_determinism_and_resume(fs):
    base = tempfile.mkdtemp() + "/corpus"
    write_synth_corpus(fs, base, fmt="delta", n_docs=16, pack_len=17,
                       vocab=64)
    l1 = LakeDataLoader(fs, base, "delta", batch_size=4, seq_len=16)
    batches1 = [l1.next_batch() for _ in range(3)]
    cursor = l1.state_dict()
    next1 = l1.next_batch()

    l2 = LakeDataLoader(fs, base, "delta", batch_size=4, seq_len=16)
    batches2 = [l2.next_batch() for _ in range(3)]
    for a, b in zip(batches1, batches2):
        np.testing.assert_array_equal(a["inputs"], b["inputs"])
    l3 = LakeDataLoader(fs, base, "delta", batch_size=4, seq_len=16)
    l3.load_state_dict(cursor)
    np.testing.assert_array_equal(l3.next_batch()["inputs"], next1["inputs"])


def test_loader_multi_host_striping(fs):
    base = tempfile.mkdtemp() + "/corpus"
    write_synth_corpus(fs, base, fmt="iceberg", n_docs=16, pack_len=17,
                       vocab=64)
    rows = []
    for host in range(2):
        ld = LakeDataLoader(fs, base, "iceberg", batch_size=4, seq_len=16,
                            host_id=host, n_hosts=2, loop=False)
        b = ld.next_batch()
        rows.append(b["inputs"][:, 0])
    # hosts see disjoint rows
    assert not set(map(tuple, rows[0][:, None])) & \
        set(map(tuple, rows[1][:, None]))


def test_loader_reads_any_format_after_sync(fs):
    """Write corpus as hudi, sync, read as delta — single copy of data."""
    from repro.core import SyncConfig, run_sync
    base = tempfile.mkdtemp() + "/corpus"
    write_synth_corpus(fs, base, fmt="hudi", n_docs=8, pack_len=17, vocab=64)
    run_sync(SyncConfig.from_dict({
        "sourceFormat": "HUDI", "targetFormats": ["DELTA"],
        "datasets": [{"tableBasePath": base}]}), fs)
    lh = LakeDataLoader(fs, base, "hudi", batch_size=2, seq_len=16)
    ld = LakeDataLoader(fs, base, "delta", batch_size=2, seq_len=16)
    np.testing.assert_array_equal(lh.next_batch()["inputs"],
                                  ld.next_batch()["inputs"])


def test_loader_prefetch_thread(fs):
    base = tempfile.mkdtemp() + "/corpus"
    write_synth_corpus(fs, base, fmt="delta", n_docs=8, pack_len=17, vocab=64)
    ld = LakeDataLoader(fs, base, "delta", batch_size=2, seq_len=16,
                        prefetch=2).start()
    b1 = ld.get()
    b2 = ld.get()
    assert b1["inputs"].shape == (2, 16)
    assert b2["cursor"] > b1["cursor"]
    ld.stop()


def _trained(fs, root):
    """A smoke-config trainer that has taken 2 steps and saved step 1."""
    from repro.configs import smoke_config
    from repro.models.model import Model
    from repro.train.trainer import Trainer, TrainerConfig
    write_synth_corpus(fs, f"{root}/corpus", fmt="delta", n_docs=8,
                       pack_len=17, vocab=256)
    model = Model(smoke_config("stablelm-3b"))

    def trainer():
        loader = LakeDataLoader(fs, f"{root}/corpus", "delta", batch_size=2,
                                seq_len=16)
        return Trainer(model, loader, fs, f"{root}/ckpt", TrainerConfig(
            steps=2, save_every=0, log_every=100, ce_chunk=16))

    tr = trainer()
    assert tr.init_or_restore() == 0
    tr.run()
    return trainer


@pytest.mark.parametrize("damage", ["leaf_dropped_from_table",
                                    "chunk_deleted_from_storage"])
def test_damaged_checkpoint_raises_instead_of_cold_start(fs, damage):
    """Only an absent checkpoint starts fresh: one that exists but lost a
    leaf must stop the restore, not silently reinitialize the weights."""
    import os
    root = tempfile.mkdtemp()
    trainer = _trained(fs, root)
    mgr = LSTCheckpointManager(fs, f"{root}/ckpt", fmt="hudi",
                               sync_targets=())
    victim = [p for p, f in mgr.handle.snapshot().files.items()
              if f.extra["leaf"] == "params/final_norm/scale"]
    assert victim
    if damage == "leaf_dropped_from_table":
        mgr.handle.commit([], victim, operation="damage")
        expected = KeyError
    else:
        os.remove(f"{root}/ckpt/{victim[0]}")
        expected = FileNotFoundError
    with pytest.raises(expected):
        trainer().init_or_restore()
