"""Deterministic, resumable data loader over LST tables.

* Reads through ANY format's connector (the engine-flexibility story: the
  same corpus written once is consumed by loaders opening it as Delta,
  Iceberg, or Hudi after an XTable sync).
* Deterministic order: files sorted by path, rows in file order; the loader
  state is a single global row cursor — committed alongside the model
  checkpoint for exact-resume after preemption.
* Row-ranged reads: every file's footer is fetched once, at construction
  (two pipelined rounds).  A batch's rows are grouped by file in cursor
  order and, where the file's ``tokens`` column has a byte address per row
  (a v3 file, no codec, fixed width), read in ONE round of coalesced ranged
  GETs (span ``data.read_rows``) — a batch moves its rows' bytes, not its
  files'.  A file without that (a v2 file, a compressed column) has its
  whole ``tokens`` column read once (span ``data.read_chunk``) and kept
  until a row of another such file is needed.
* Straggler mitigation: a background prefetch thread keeps a bounded queue
  of ready batches per host; slow storage reads overlap compute.  It
  assembles batches exactly as ``next_batch`` does.
* Multi-host striping: host h of H takes rows where (row_idx % H) == h.
* Telemetry: the loader reads through an ``InstrumentedFS`` feeding its
  ``telemetry`` (``storage.get``, ``storage.bytes_read``, ...); counters
  ``data.rows_ranged`` and ``data.rows_whole`` count the rows served by
  ranged reads and from whole-column reads.
"""

from __future__ import annotations

import queue
import threading

import numpy as np

from repro.core.telemetry import Telemetry
from repro.lst.chunkfile import (read_chunks_columns, read_chunks_footers,
                                 read_chunks_rows)
from repro.lst.storage import InstrumentedFS
from repro.lst.table import LakeTable

COLUMN = "tokens"


class LakeDataLoader:
    def __init__(self, fs, base_path: str, fmt: str, *, batch_size: int,
                 seq_len: int, host_id: int = 0, n_hosts: int = 1,
                 start_row: int = 0, prefetch: int = 2, loop: bool = True,
                 telemetry: Telemetry | None = None):
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self.table = LakeTable.open(InstrumentedFS(fs, self.telemetry),
                                    base_path, fmt)
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.host_id = host_id
        self.n_hosts = n_hosts
        self.row = start_row
        self.loop = loop
        self._files = sorted(self.table.state().files.values(),
                             key=lambda f: f.path)
        counts = np.array([f.record_count for f in self._files], np.int64)
        self._ends = np.cumsum(counts)          # file i: [ends - counts, ends)
        self._starts = self._ends - counts
        self.total_rows = int(counts.sum())
        self._footers = read_chunks_footers(
            self.table.fs, self.table.base, [f.path for f in self._files])
        self._ranged = [ftr.row_stride(COLUMN) is not None
                        for ftr in self._footers]
        self._whole: tuple[int, np.ndarray] | None = None
        self._q: queue.Queue = queue.Queue(maxsize=prefetch)
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()

    # --------------------------------------------------------------- cursor
    def state_dict(self) -> dict:
        return {"row": self.row}

    def load_state_dict(self, d: dict) -> None:
        self.row = int(d["row"])

    # ---------------------------------------------------------------- reads
    def _whole_column(self, fi: int) -> np.ndarray:
        """File ``fi``'s whole ``tokens`` column, read once while its rows
        keep coming."""
        if self._whole is None or self._whole[0] != fi:
            with self.telemetry.span("data.read_chunk"):
                (cols, _), = read_chunks_columns(
                    self.table.fs, self.table.base, [self._files[fi].path],
                    [COLUMN], footers=[self._footers[fi]])
            self._whole = (fi, cols[COLUMN])
        return self._whole[1]

    def _read_rows(self, idx: list[int]) -> np.ndarray:
        """The rows at global indices ``idx``, stacked in that order."""
        idx = np.asarray(idx)
        fis = np.searchsorted(self._ends, idx, side="right")
        local = idx - self._starts[fis]
        runs: list[list[int]] = []           # [file, lo, hi) in cursor order
        for fi, r in zip(fis.tolist(), local.tolist()):
            if runs and runs[-1][0] == fi and runs[-1][2] == r:
                runs[-1][2] += 1
            else:
                runs.append([fi, r, r + 1])
        pieces: list = [None] * len(runs)
        ranged = [i for i, (fi, _, _) in enumerate(runs) if self._ranged[fi]]
        if ranged:
            with self.telemetry.span("data.read_rows"):
                rows, _ = read_chunks_rows(
                    self.table.fs, self.table.base,
                    [(self._files[runs[i][0]].path, runs[i][1], runs[i][2])
                     for i in ranged], COLUMN,
                    [self._footers[runs[i][0]] for i in ranged])
            for i, r in zip(ranged, rows):
                pieces[i] = r
            self.telemetry.bump("data.rows_ranged",
                                sum(len(r) for r in rows))
        for i, (fi, lo, hi) in enumerate(runs):
            if pieces[i] is None:
                pieces[i] = self._whole_column(fi)[lo:hi]
                self.telemetry.bump("data.rows_whole", hi - lo)
        return np.concatenate(pieces)

    # ---------------------------------------------------------------- batch
    def _assemble(self) -> np.ndarray | None:
        """The next batch's ``(batch, seq_len + 1)`` int32 tokens, advancing
        the cursor; ``None`` once a ``loop=False`` pass is spent."""
        idx = []
        while len(idx) < self.batch_size:
            if not self.loop and self.row >= self.total_rows:
                return None
            if self.row % self.n_hosts == self.host_id:
                idx.append(self.row % self.total_rows)
            self.row += 1
        return self._read_rows(idx)[:, :self.seq_len + 1].astype(np.int32)

    def next_batch(self) -> dict:
        """Synchronous batch (deterministic; used by tests)."""
        toks = self._assemble()
        if toks is None:
            raise StopIteration
        return {"inputs": toks[:, :-1], "targets": toks[:, 1:]}

    # ------------------------------------------------------------- prefetch
    def _producer(self) -> None:
        while not self._stop.is_set():
            toks = self._assemble()
            if toks is None:
                self._q.put(None)
                return
            self._q.put({"inputs": toks[:, :-1], "targets": toks[:, 1:],
                         "cursor": self.row})

    def start(self) -> "LakeDataLoader":
        self._thread = threading.Thread(target=self._producer, daemon=True)
        self._thread.start()
        return self

    def get(self, timeout: float = 60.0) -> dict:
        b = self._q.get(timeout=timeout)
        if b is None:
            raise StopIteration
        return b

    def stop(self) -> None:
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
