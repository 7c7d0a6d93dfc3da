"""The loader's row-ranged reads: the rows a batch hands out are exactly
those of a whole-file read in cursor order, whatever the cursor, striping,
file boundaries or wrap-around; files whose ``tokens`` column has no byte
address per row fall back to one whole-column read; and the chunk file's
row-range reader coalesces adjacent rows into one range per file in a
single batch round."""

from __future__ import annotations

import tempfile

import numpy as np
import pytest

from repro.data import LakeDataLoader, write_synth_corpus
from repro.lst import chunkfile
from repro.lst.schema import Field, Schema
from repro.lst.storage import MemoryFS
from repro.lst.table import LakeTable

PACK = 17


def _corpus(fs, fmt="delta", n_docs=14, n_shards=4):
    """14 rows over 4 shard files of 4, 4, 3 and 3 rows."""
    base = tempfile.mkdtemp() + "/corpus"
    write_synth_corpus(fs, base, fmt=fmt, n_docs=n_docs, pack_len=PACK,
                       vocab=256, n_shards=n_shards)
    return base


def _whole_file_rows(fs, base, fmt):
    """Every row in the loader's order, each file read whole."""
    t = LakeTable.open(fs, base, fmt)
    files = sorted(t.state().files.values(), key=lambda f: f.path)
    return np.concatenate([chunkfile.read_chunk(fs, t.base, f.path)[0]
                           ["tokens"] for f in files])


def _expected(rows, start, n_batches, batch, host_id=0, n_hosts=1):
    """Batches a cursor at ``start`` hands out, row by row as the cursor
    walks: host ``h`` of ``H`` takes rows with ``row % H == h``."""
    out, row = [], start
    for _ in range(n_batches):
        take = []
        while len(take) < batch:
            if row % n_hosts == host_id:
                take.append(rows[row % len(rows)])
            row += 1
        out.append(np.stack(take).astype(np.int32))
    return out


def _tokens(b):
    return np.concatenate([b["inputs"], b["targets"][:, -1:]], axis=1)


@pytest.mark.parametrize("fmt", ["delta", "iceberg", "hudi"])
def test_ranged_rows_equal_whole_file_rows_from_every_cursor_start(fs, fmt):
    base = _corpus(fs, fmt)
    rows = _whole_file_rows(fs, base, fmt)
    ld = LakeDataLoader(fs, base, fmt, batch_size=3, seq_len=PACK - 1)
    total = ld.total_rows
    assert total == 14 and len(ld._files) == 4
    n = -(-2 * total // 3) + 1                 # two passes and a wrap
    for start in range(2 * total):
        ld.load_state_dict({"row": start})
        got = [_tokens(ld.next_batch()) for _ in range(n)]
        for g, e in zip(got, _expected(rows, start, n, 3)):
            np.testing.assert_array_equal(g, e)
            assert g.dtype == np.int32
        assert ld.state_dict() == {"row": start + 3 * n}
    assert ld.telemetry.counters["data.rows_ranged"] == 2 * total * n * 3
    assert "data.rows_whole" not in ld.telemetry.counters


@pytest.mark.parametrize("n_hosts", [2, 3])
def test_striped_hosts_get_the_rows_of_a_whole_file_read(fs, n_hosts):
    base = _corpus(fs)
    rows = _whole_file_rows(fs, base, "delta")
    for host in range(n_hosts):
        ld = LakeDataLoader(fs, base, "delta", batch_size=2,
                            seq_len=PACK - 1, host_id=host, n_hosts=n_hosts,
                            start_row=5)
        got = [_tokens(ld.next_batch()) for _ in range(12)]
        for g, e in zip(got, _expected(rows, 5, 12, 2, host, n_hosts)):
            np.testing.assert_array_equal(g, e)


@pytest.mark.parametrize("loop", [False, True])
def test_next_batch_and_the_producer_hand_out_the_same_batches(fs, loop):
    base = _corpus(fs)
    kw = dict(batch_size=3, seq_len=PACK - 1, start_row=2, loop=loop)
    sync = LakeDataLoader(fs, base, "delta", **kw)
    pre = LakeDataLoader(fs, base, "delta", **kw).start()
    n = 0
    try:
        for _ in range(10):
            try:
                a = sync.next_batch()
            except StopIteration:
                with pytest.raises(StopIteration):
                    pre.get(timeout=30)
                break
            b = pre.get(timeout=30)
            np.testing.assert_array_equal(a["inputs"], b["inputs"])
            np.testing.assert_array_equal(a["targets"], b["targets"])
            assert b["cursor"] == sync.row
            n += 1
    finally:
        pre.stop()
    assert n == (4 if not loop else 10)


def _fallback_table(fs, base, kind, n_files=2, rows=5):
    """A table whose files' ``tokens`` columns have no row addresses:
    compressed (``zlib``) or in a v2 file (``v2``)."""
    schema = Schema([Field("tokens", "int32"), Field("doc_id", "int64")])
    t = LakeTable.create(fs, base, schema, "delta")
    rng = np.random.default_rng(7)
    metas = []
    for i in range(n_files):
        cols = {"tokens": rng.integers(0, 256, (rows, PACK), dtype=np.int32),
                "doc_id": np.arange(i * rows, (i + 1) * rows)}
        metas.append(chunkfile.write_chunk(
            fs, base, f"data/f{i}.chunk", cols, compress=kind == "zlib",
            version=2 if kind == "v2" else 3))
    t.handle.commit(metas, operation="WRITE")
    return base


@pytest.mark.parametrize("kind", ["zlib", "v2"])
def test_fallback_reads_each_files_column_once_and_counts_its_rows(fs, kind):
    base = _fallback_table(fs, tempfile.mkdtemp() + "/t", kind)
    rows = _whole_file_rows(fs, base, "delta")
    ld = LakeDataLoader(fs, base, "delta", batch_size=2, seq_len=PACK - 1,
                        loop=False)
    assert not any(ld._ranged)
    got = [_tokens(ld.next_batch()) for _ in range(5)]
    with pytest.raises(StopIteration):
        ld.next_batch()
    for g, e in zip(got, _expected(rows, 0, 5, 2)):
        np.testing.assert_array_equal(g, e)
    c = ld.telemetry.counters
    assert c["data.rows_whole"] == 10 and c.get("data.rows_ranged", 0) == 0
    assert ld.telemetry.spans["data.read_chunk"][0] == 2     # once a file
    assert "data.read_rows" not in ld.telemetry.spans


def test_mixed_table_ranges_v3_rows_and_reads_v2_whole(fs):
    base = _fallback_table(fs, tempfile.mkdtemp() + "/t", "v2", n_files=1)
    t = LakeTable.open(fs, base, "delta")
    rng = np.random.default_rng(8)
    t.handle.commit([chunkfile.write_chunk(
        fs, base, "data/g.chunk",
        {"tokens": rng.integers(0, 256, (4, PACK), dtype=np.int32),
         "doc_id": np.arange(5, 9)})], operation="WRITE")
    rows = _whole_file_rows(fs, base, "delta")
    ld = LakeDataLoader(fs, base, "delta", batch_size=3, seq_len=PACK - 1)
    assert ld._ranged == [False, True]
    got = [_tokens(ld.next_batch()) for _ in range(6)]      # two passes
    for g, e in zip(got, _expected(rows, 0, 6, 3)):
        np.testing.assert_array_equal(g, e)
    c = ld.telemetry.counters
    assert (c["data.rows_whole"], c["data.rows_ranged"]) == (10, 8)


# ------------------------------------------------------------- chunkfile
class RangeRoundFS(MemoryFS):
    """Records each ``read_many_ranges`` call's requests."""

    def __init__(self):
        super().__init__()
        self.rounds: list[list] = []

    def read_many_ranges(self, requests):
        self.rounds.append(list(requests))
        return super().read_many_ranges(requests)


def _two_files(fs):
    rng = np.random.default_rng(3)
    data = {}
    for name in ("a", "b"):
        cols = {"doc_id": np.arange(6),
                "tokens": rng.integers(0, 1000, (6, 5), dtype=np.int32),
                "s": np.array([f"r{i}" for i in range(6)])}
        chunkfile.write_chunk(fs, "bkt/t", f"data/{name}.chunk", cols)
        data[name] = cols
    return data


def test_read_chunks_rows_coalesces_adjacent_rows_one_range_a_file():
    fs = RangeRoundFS()
    data = _two_files(fs)
    paths = ["data/a.chunk", "data/b.chunk"]
    ftrs = chunkfile.read_chunks_footers(fs, "bkt/t", paths)
    by = dict(zip(paths, ftrs))
    reqs = [("data/a.chunk", 1, 3), ("data/b.chunk", 4, 5),
            ("data/a.chunk", 3, 4), ("data/b.chunk", 2, 4)]
    fs.rounds.clear()
    rows, nbytes = chunkfile.read_chunks_rows(
        fs, "bkt/t", reqs, "tokens", [by[p] for p, _, _ in reqs])
    assert len(fs.rounds) == 1                      # one batch round
    (ra, rb) = sorted(fs.rounds[0])                 # one range a file
    stride = 5 * 4
    assert by["data/a.chunk"].row_stride("tokens") == stride
    assert (ra[2], rb[2]) == (3 * stride, 3 * stride)
    assert nbytes == 6 * stride
    for (p, lo, hi), r in zip(reqs, rows):
        np.testing.assert_array_equal(r, data[p[5]]["tokens"][lo:hi])
        assert r.dtype == np.int32 and r.shape == (hi - lo, 5)
    ids, _ = chunkfile.read_chunks_rows(fs, "bkt/t", [("data/b.chunk", 2, 5)],
                                        "doc_id", [by["data/b.chunk"]])
    np.testing.assert_array_equal(ids[0], np.arange(2, 5))


def test_row_stride_only_where_a_row_has_a_byte_address():
    fs = MemoryFS()
    cols = {"tokens": np.arange(12, dtype=np.int32).reshape(4, 3),
            "s": np.array(["a", "b", "c", "d"])}
    chunkfile.write_chunk(fs, "bkt/t", "v3.chunk", cols)
    chunkfile.write_chunk(fs, "bkt/t", "z.chunk", cols, compress=True)
    chunkfile.write_chunk(fs, "bkt/t", "v2.chunk", cols, version=2)
    v3, z, v2 = chunkfile.read_chunks_footers(
        fs, "bkt/t", ["v3.chunk", "z.chunk", "v2.chunk"])
    assert v3.row_stride("tokens") == 12
    assert v3.row_stride("s") is None and v3.row_stride("nope") is None
    assert z.row_stride("tokens") is None and v2.row_stride("tokens") is None
    with pytest.raises(ValueError, match="no row byte addresses"):
        chunkfile.read_chunks_rows(fs, "bkt/t", [("z.chunk", 0, 1)],
                                   "tokens", [z])
