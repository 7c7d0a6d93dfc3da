"""Checkpoints as log-structured tables — XTable in action inside the trainer.

Every checkpoint save is an LST commit: immutable tensor-chunk data files +
a small metadata commit, partitioned by ``step``. The trainer writes through
ONE format (Hudi-style timeline: cheapest streaming commits); after each
save the XTable core translates the metadata so evaluators/servers can read
the same files through Iceberg/Delta readers (the paper's Scenario 2/3, with
engines = trainer/evaluator/server):

* save   = write chunks -> atomic commit -> (async) XTable incremental sync
* restore = pick a snapshot through ANY format's reader, reassemble, reshard
* crash-safety = a torn save never commits, so restart sees the previous
  snapshot (the LST ACID story is the checkpoint fault-tolerance story)
* GC     = replace-commit dropping old steps, but only steps already synced
  to every target (translated metadata keeps files alive — deleting a file
  still referenced by a target's snapshot would corrupt that format's view)
"""

from __future__ import annotations

import json
import threading
import time

import numpy as np

from repro.core import SyncConfig, Telemetry, run_sync
from repro.lst import chunkfile
from repro.lst.schema import Field, PartitionSpec, Schema
from repro.lst.table import FORMATS

try:
    import ml_dtypes
    _BF16 = np.dtype(ml_dtypes.bfloat16)
except ImportError:                                   # pragma: no cover
    _BF16 = None

CKPT_SCHEMA = Schema([Field("tensor", "binary"), Field("step", "int64")])
MAX_CHUNK_BYTES = 64 * 2**20


def _leaf_paths(pytree) -> list:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(pytree)
    out = []
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        out.append((name, leaf))
    return out


def _encode(arr: np.ndarray) -> tuple[np.ndarray, str]:
    if _BF16 is not None and arr.dtype == _BF16:
        return arr.view(np.uint16), "bfloat16"
    return arr, str(arr.dtype)


def _decode(arr: np.ndarray, logical: str) -> np.ndarray:
    if logical == "bfloat16" and _BF16 is not None:
        return arr.view(_BF16)
    return arr.astype(np.dtype(logical), copy=False) \
        if str(arr.dtype) != logical else arr


class LSTCheckpointManager:
    def __init__(self, fs, base_path: str, *, fmt: str = "hudi",
                 sync_targets: tuple = ("iceberg", "delta"),
                 keep_last: int = 3, async_sync: bool = False,
                 telemetry: Telemetry | None = None):
        self.fs = fs
        self.base = base_path
        self.fmt = fmt
        self.sync_targets = tuple(t for t in sync_targets if t != fmt)
        self.keep_last = keep_last
        self.async_sync = async_sync
        self.telemetry = telemetry or Telemetry()
        self._sync_thread: threading.Thread | None = None
        cls = FORMATS[fmt]
        if cls.exists(fs, base_path):
            self.handle = cls.open(fs, base_path)
        else:
            self.handle = cls.create(fs, base_path, CKPT_SCHEMA,
                                     PartitionSpec(["step"]),
                                     {"table.kind": "checkpoint"})

    # ------------------------------------------------------------------ save
    def save(self, step: int, pytree, extra_meta: dict | None = None) -> str:
        """Write one checkpoint commit; returns the commit id.

        Re-saving an existing step is a replace-commit (old chunk files of
        that step are dropped from the live set atomically with the new
        adds — readers never see a mixed step).
        """
        import uuid
        t0 = time.perf_counter()
        tag = uuid.uuid4().hex[:8]
        adds = []
        for name, leaf in _leaf_paths(pytree):
            arr = np.asarray(leaf)
            enc, logical = _encode(arr)
            flat = enc.reshape(-1)
            n_shards = max(1, -(-flat.nbytes // MAX_CHUNK_BYTES))
            per = -(-flat.size // n_shards)
            for si in range(n_shards):
                part = flat[si * per:(si + 1) * per]
                rel = (f"step={step}/{name.replace('/', '.')}"
                       f"_{si:03d}_{tag}.chunk")
                meta = chunkfile.write_chunk(
                    self.fs, self.base, rel, {"tensor": part},
                    partition_values={"step": str(step)},
                    extra={"leaf": name, "global_shape": list(arr.shape),
                           "dtype": logical, "offset": si * per,
                           "nshards": n_shards})
                adds.append(meta)
        stale = [p for p, f in self.handle.snapshot().files.items()
                 if int(f.partition_values["step"]) == step]
        commit = self.handle.commit(
            adds, stale, operation="checkpoint",
            extra_meta={"step": str(step), **(extra_meta or {})})
        self.telemetry.record("ckpt", self.fmt, "save",
                              f"step {step}: {len(adds)} chunks",
                              time.perf_counter() - t0)
        self._kick_sync()
        return commit

    # ------------------------------------------------------------------ sync
    def _sync_config(self) -> SyncConfig:
        return SyncConfig.from_dict({
            "sourceFormat": self.fmt.upper(),
            "targetFormats": [t.upper() for t in self.sync_targets],
            "datasets": [{"tableBasePath": self.base}]})

    def sync_now(self):
        """Run the XTable translation (trainer never blocks on this)."""
        if not self.sync_targets:
            return []
        return run_sync(self._sync_config(), self.fs, self.telemetry)

    def _kick_sync(self) -> None:
        if not self.sync_targets:
            return
        if not self.async_sync:
            self.sync_now()
            return
        if self._sync_thread and self._sync_thread.is_alive():
            return          # a sync is already running; next save re-kicks
        self._sync_thread = threading.Thread(target=self.sync_now,
                                             daemon=True)
        self._sync_thread.start()

    def wait_for_sync(self) -> None:
        if self._sync_thread:
            self._sync_thread.join()

    # --------------------------------------------------------------- restore
    def steps(self, fmt: str | None = None) -> list[int]:
        """Steps visible through ``fmt``'s view; [] while that view does
        not exist yet (nothing saved, or not translated to it)."""
        fmt = fmt or self.fmt
        if fmt != self.fmt and not FORMATS[fmt].exists(self.fs, self.base):
            return []
        handle = self._reader(fmt)
        st = handle.snapshot()
        return sorted({int(f.partition_values["step"])
                       for f in st.files.values()})

    def _reader(self, fmt: str | None):
        fmt = fmt or self.fmt
        if fmt == self.fmt:
            return self.handle
        return FORMATS[fmt].open(self.fs, self.base)

    def latest_meta(self, fmt: str | None = None) -> dict:
        """User metadata of the newest commit, via any format's reader
        (XTable carries source commit metadata through the IR)."""
        handle = self._reader(fmt)
        out = dict(handle.snapshot().properties)
        if hasattr(handle, "latest_extra_metadata"):
            out.update(handle.latest_extra_metadata())
        else:
            _, _, _, info = handle.changes(handle.current_version())
            out.update({k: v for k, v in info.items()
                        if isinstance(v, str)})
            if isinstance(info.get("xtable"), dict):
                out.update(info["xtable"])
        return out

    def restore(self, step: int | None = None, *, fmt: str | None = None,
                validate: bool = True, state=None) -> tuple[int, dict]:
        """Reassemble a checkpoint pytree (as a flat {leaf-path: ndarray}).

        ``fmt`` may be any synced format — restoring through a different
        format than was written is the XTable round-trip, exercised by the
        integration tests. Elastic resharding happens on the caller side via
        ``jax.device_put`` with the new mesh's shardings.

        ``state`` restores through a pre-resolved ``TableState`` (a read
        plane's pinned snapshot) instead of replaying the format's
        metadata here — the restore then spends storage requests only on
        the chunk bodies.
        """
        st = state if state is not None else self._reader(fmt).snapshot()
        steps = sorted({int(f.partition_values["step"])
                        for f in st.files.values()})
        if not steps:
            raise FileNotFoundError("no checkpoints")
        step = step if step is not None else steps[-1]
        by_leaf: dict[str, list] = {}
        for f in st.files.values():
            if int(f.partition_values["step"]) != step:
                continue
            by_leaf.setdefault(f.extra["leaf"], []).append(f)
        out = {}
        for leaf, metas in by_leaf.items():
            metas.sort(key=lambda m: m.extra["offset"])
            parts = []
            for m in metas:
                cols, extra = chunkfile.read_chunk(self.fs, self.base, m.path)
                arr = cols["tensor"]
                if validate:
                    st_ = m.column_stats.get("tensor")
                    if st_ is not None and st_.count != arr.shape[0]:
                        raise IOError(f"integrity: {m.path} count mismatch")
                parts.append(arr)
            extra = metas[0].extra
            full = np.concatenate(parts) if len(parts) > 1 else parts[0]
            out[leaf] = _decode(full, extra["dtype"]).reshape(
                [int(x) for x in extra["global_shape"]])
        return step, out

    def restore_pytree(self, template, step: int | None = None,
                       fmt: str | None = None, state=None):
        """Restore into the structure of ``template`` (shape-checked)."""
        import jax
        step, flat = self.restore(step, fmt=fmt, state=state)
        leaves = _leaf_paths(template)
        out = []
        for name, leaf in leaves:
            if name not in flat:
                raise KeyError(f"checkpoint missing leaf {name}")
            arr = flat[name]
            want = tuple(leaf.shape)
            if tuple(arr.shape) != want:
                raise ValueError(f"{name}: shape {arr.shape} != {want}")
            out.append(arr)
        treedef = jax.tree.structure(template)
        return step, jax.tree.unflatten(treedef, out)

    # -------------------------------------------------------------------- gc
    def gc(self) -> list[int]:
        """Drop old steps (keep_last), but never steps the targets still
        reference (GC safety across translated metadata)."""
        steps = self.steps()
        if len(steps) <= self.keep_last:
            return []
        candidates = steps[:-self.keep_last]
        # SAFETY: only collect when every target has been translated up to
        # the CURRENT source head — a lagging target's snapshot still
        # references the candidate steps' files, and deleting them would
        # corrupt that format's view of the single data copy.
        head = self.handle.current_version()
        token_ok = True
        for t in self.sync_targets:
            try:
                reader = self._reader(t)
                props = reader.properties() if t != "hudi" else \
                    reader.latest_extra_metadata()
                tok = props.get("xtable.lastSyncedSourceCommit")
                if tok != head:
                    token_ok = False
            except FileNotFoundError:
                token_ok = False
        if not token_ok:
            self.telemetry.record("ckpt", self.fmt, "gc",
                                  "deferred: targets not fully synced")
            return []
        st = self.handle.snapshot()
        removes = [p for p, f in st.files.items()
                   if int(f.partition_values["step"]) in set(candidates)]
        if removes:
            self.handle.commit([], removes, operation="gc",
                               extra_meta={"gc.steps": json.dumps(candidates)})
            self._kick_sync()
        return candidates
