"""The training corpus a train cell reads, made from the seed.

A copy of the program's synthetic corpus (``data/synth.py``: packed rows of
a bigram Markov chain, ``t+1 = (31 t + 7) mod V`` with probability 0.85,
else a uniform draw), made here with whole-array numpy draws and no loop
over tokens, and written into a lake table the way the traffic file says:
commits, shard partitions, rows per file, table format.

File names in the lake come from ``uuid.uuid4``; during the write they are
drawn from the seed, so that the same seed gives the same files in the
same order, and with them the same batches.

Each row carries its ``doc_id`` (its index in the corpus), so that the order
in which the loader is due to hand rows out can be read back from the table.
"""

from __future__ import annotations

import uuid
from contextlib import contextmanager
from unittest import mock

import numpy as np

SUCC_PROB = 0.85


def markov_rows(rng: np.random.Generator, n_rows: int, length: int,
                vocab: int) -> np.ndarray:
    """(n_rows, length) int32 tokens: each row restarts the chain at 0."""
    base = rng.integers(0, vocab, size=(n_rows, length), dtype=np.int64)
    follow = rng.random((n_rows, length)) < SUCC_PROB
    follow[:, 0] = False
    # succ^k(t) = a_k t + b_k (mod V): a_{k+1} = 31 a_k, b_{k+1} = 31 b_k + 7
    a = np.empty(length, np.int64)
    b = np.empty(length, np.int64)
    a[0], b[0] = 1, 0
    for k in range(1, length):           # one step per position, not token
        a[k] = (31 * a[k - 1]) % vocab
        b[k] = (31 * b[k - 1] + 7) % vocab
    pos = np.arange(length)
    start = np.maximum.accumulate(np.where(follow, 0, pos), axis=1)
    k = pos[None, :] - start
    origin = np.take_along_axis(base, start, axis=1)
    return ((a[k] * origin + b[k]) % vocab).astype(np.int32)


@contextmanager
def seeded_uuid4(rng: np.random.Generator):
    with mock.patch.object(uuid, "uuid4",
                           lambda: uuid.UUID(bytes=rng.bytes(16), version=4)):
        yield


def write(fs, path: str, traffic: dict, vocab: int, seed: int) -> np.ndarray:
    """Write the cell's corpus at ``path``; returns its rows (doc_id order)."""
    from repro.lst.schema import Field, PartitionSpec, Schema
    from repro.lst.table import LakeTable

    rng = np.random.default_rng([seed, 0xC0])
    c = traffic["corpus"]
    n, commits, shards = c["rows"], c["commits"], c["shards"]
    rows = markov_rows(rng, n, traffic["seq_len"] + 1, vocab)
    schema = Schema([Field("tokens", "int32"), Field("doc_id", "int64"),
                     Field("shard", "string")])
    doc_id = np.arange(n, dtype=np.int64)
    shard = np.array([f"s{i % shards}" for i in range(n)])
    with seeded_uuid4(np.random.default_rng([seed, 0xF1])):
        table = LakeTable.create(fs, path, schema, c["format"],
                                 PartitionSpec(["shard"]))
        for part in np.array_split(np.arange(n), commits):
            table.append({"tokens": rows[part], "doc_id": doc_id[part],
                          "shard": shard[part]},
                         rows_per_file=c.get("rows_per_file"))
    return rows


def cursor_order(fs, path: str, fmt: str) -> np.ndarray:
    """Doc ids in the order the loader's cursor is due to hand rows out:
    the live files of the table as ``fmt`` lists them, sorted by path, rows
    in file order. Read from the lake's metadata and data files; the check
    holds the result against the seed's corpus (every row exactly once)."""
    from repro.lst.chunkfile import read_chunk
    from repro.lst.table import LakeTable

    table = LakeTable.open(fs, path, fmt)
    files = sorted(table.state().files.values(), key=lambda f: f.path)
    ids = [read_chunk(fs, table.base, f.path)[0]["doc_id"] for f in files]
    return np.concatenate(ids).astype(np.int64) if ids else \
        np.zeros(0, np.int64)
