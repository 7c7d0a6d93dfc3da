"""CPU tests of the chip benchmark's harness: resolution by name, the
contract of ``BENCHMARK.json``, generators, counts and the TPU guard."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

import smoke  # noqa: F401  (puts the harness and src on sys.path)
from chipbench import cell as cellmod
from chipbench import check, corpus, device, flops
from chipbench.cell import BENCH_DIR, REPO, load_benchmark, resolve

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = load_benchmark()
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_cell_resolves_its_parts_by_name(workload):
    c = resolve(workload)
    assert callable(cellmod.load_driver(c.traffic["kind"]).run)
    assert cellmod.load_reference(c.config["reference"]).logits
    for m in c.per_layer:
        assert callable(cellmod.load_reader(m["name"]))
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.traffic["throughput_metric"] in names
    assert c.per_layer, "every cell reports a per-layer metric"
    assert c.limits


def test_benchmark_json_keeps_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check of 24 cells fits its 43,200 s
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [x["name"] for x in BENCH["configs"] + BENCH["workloads"]
             + metrics]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    moves = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in moves
        for w in m["workloads"]:
            e2e = resolve(w).end_to_end
            assert m["moves"] in {x["name"] for x in e2e}
    for c in BENCH["configs"]:
        assert (REPO / c["file"]).is_file()
        assert c["file"].startswith("benchmarks/chip/")
        cfg = json.loads((REPO / c["file"]).read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert set(cfg["published"]) == set(cfg["reduced"])
    for w in BENCH["workloads"]:
        assert w["chips"] == 1 and len(w["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_new_config_traffic_metric_and_limits_need_no_edit(tmp_path):
    """A cell added as new files and new entries, with a new configuration,
    traffic mix, metric and limits, resolves and runs at smoke size in a
    copy of the benchmark, with no existing file edited; so do a new model
    family and a new kind of traffic."""
    shutil.copytree(BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    chip = tmp_path / "benchmarks" / "chip"
    base = json.loads((chip / "configs" / "stablelm3b.json").read_text())
    (chip / "configs" / "newmodel.json").write_text(
        json.dumps({**base, "name": "newmodel", "num_hidden_layers": 2}))
    mix = json.loads((chip / "traffic" / "lake_stream.json").read_text())
    (chip / "traffic" / "newmix.json").write_text(
        json.dumps({**mix, "batch": 2}))
    (chip / "metrics" / "new_metric.py").write_text(
        "def read(r):\n    return 1.5\n")
    (chip / "families" / "newfam.py").write_text(
        "def matmul_params(c):\n    return 7\n")
    (chip / "chipbench" / "newkind.py").write_text(
        "def run(*args):\n    return 'ran'\n")
    shutil.copy(chip / "limits" / "stablelm3b.train_lake.json",
                chip / "limits" / "newmodel.newmix.json")
    bench["configs"].append({"name": "newmodel", "source": "x",
                             "file": "benchmarks/chip/configs/newmodel.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "newmodel.newmix", "config": "newmodel",
                               "traffic": "newmix", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "new_metric", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "x", "moves": "setup_s",
                               "workloads": ["newmodel.newmix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    code = textwrap.dedent("""
        import sys
        sys.path[:0] = [sys.argv[1] + "/tests", sys.argv[2]]
        import smoke
        from chipbench import check
        from chipbench.cell import load_driver, load_family, load_reader, \\
            resolve
        c = resolve("newmodel.newmix")
        assert c.config["num_hidden_layers"] == 2 and c.traffic["batch"] == 2
        assert [m["name"] for m in c.per_layer] == ["new_metric"]
        assert load_reader("new_metric")(None) == 1.5
        assert load_family("newfam").matmul_params({}) == 7
        assert load_driver("newkind").run() == "ran"
        cell = smoke.smoke_cell("newmodel.newmix")
        out = smoke.run_smoke(cell, seed=2**40 + 5)
        ok, checks = check.judge(out.numbers, cell.limits)
        assert ok and out.failed == 0, checks
        print("ok")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code, str(chip),
                        str(REPO / "src")], capture_output=True, text=True,
                       timeout=300, env=env, cwd=tmp_path)
    assert r.stdout.strip().endswith("ok"), r.stderr[-4000:]


def _markov_rows_loop(rng, n_rows, length, vocab):
    """``corpus.markov_rows`` token by token: the plain form."""
    base = rng.integers(0, vocab, size=(n_rows, length), dtype=np.int64)
    follow = rng.random((n_rows, length)) < corpus.SUCC_PROB
    out = np.empty((n_rows, length), np.int32)
    for r in range(n_rows):
        out[r, 0] = base[r, 0]
        for i in range(1, length):
            out[r, i] = ((31 * int(out[r, i - 1]) + 7) % vocab
                         if follow[r, i] else base[r, i])
    return out


def test_corpus_is_deterministic_in_seed_and_matches_the_loop_form():
    def rows(seed, fn=corpus.markov_rows):
        return fn(np.random.default_rng(seed), 5, 300, 1000)
    a, b = rows(2**40 + 3), rows(2**40 + 3)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, rows(2**40 + 4))
    assert np.array_equal(a, rows(2**40 + 3, _markov_rows_loop))
    follows = np.mean(a[:, 1:] == (31 * a[:, :-1].astype(np.int64) + 7) % 1000)
    assert 0.8 < follows < 0.9


def test_written_corpus_and_its_files_are_the_same_for_a_seed(tmp_path):
    from repro.lst import LocalFS
    cell = smoke.smoke_cell("stablelm3b.train_lake")
    seen = []
    for i in range(2):
        path = str(tmp_path / f"c{i}")
        rows = corpus.write(LocalFS(), path, cell.traffic, 256, seed=9)
        files = sorted(str(p.relative_to(path))
                       for p in Path(path).rglob("*.chunk"))
        seen.append((rows, files))
    assert np.array_equal(seen[0][0], seen[1][0])
    assert seen[0][1] == seen[1][1] and len(seen[0][1]) == 6


@pytest.mark.parametrize("consumed,expected", [
    ([4, 5, 0, 1, 2, 3], 0),            # the cursor's order
    ([4, 5, 0, 1, 2, 3, 4], 0),         # and round again
    ([4, 5, 1, 2, 3, 4], 4),            # a row skipped shifts the rest
    ([4, 5, 5, 0, 1, 2], 4),            # a row handed out twice
    ([4, 0, 5, 1, 2, 3], 2),            # two rows swapped
    ([4, 5, -1, 1, 2, 3], 1),           # a row that is no corpus row
])
def test_rows_wrong_holds_consumed_rows_to_the_cursor_order(consumed,
                                                             expected):
    order = np.array([4, 5, 0, 1, 2, 3])       # files sorted by path
    assert check.rows_wrong(np.array(consumed), order, 6) == expected


def test_rows_wrong_counts_a_view_that_lists_a_row_other_than_once():
    assert check.rows_wrong(np.array([0, 1, 1]), np.array([0, 1, 1]), 3) == 2
    assert check.rows_wrong(np.array([0]), np.zeros(0, np.int64), 3) == 4


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cursor_order_is_the_order_the_loader_hands_rows_out(tmp_path,
                                                             workload):
    """Witness for ``corpus.cursor_order``: the program's loader, walked
    from row 0 over the whole view of a smoke corpus, hands out the corpus
    rows in that order."""
    from chipbench import train
    from repro.core import Telemetry
    from repro.data import LakeDataLoader
    from repro.lst import LocalFS
    cell = smoke.smoke_cell(workload)
    t, fs, path = cell.traffic, LocalFS(), str(tmp_path / "corpus")
    rows = corpus.write(fs, path, t, 256, seed=2**40 + 17)
    src, dst = t["corpus"]["format"], t["corpus"]["read_format"]
    if dst != src:
        train._translate(fs, path, src, dst, Telemetry())
    loader = LakeDataLoader(fs, path, dst, batch_size=1, seq_len=t["seq_len"])
    handed = [np.concatenate([b["inputs"], b["targets"][:, -1:]], 1)
              for b in (loader.next_batch() for _ in range(len(rows)))]
    docs = train.consumed_docs(handed, rows)
    order = corpus.cursor_order(fs, path, dst)
    assert sorted(order.tolist()) == list(range(len(rows)))
    assert np.array_equal(docs, order)


def test_peaks_known_kind_and_unknown_kind_is_an_error():
    assert device.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert device.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")


def test_flop_and_byte_counts_match_hand_counts():
    c = resolve("stablelm3b.train_lake").config
    matmul_params = cellmod.load_family(c["family"]).matmul_params
    # per layer: q, k, v, o 4 x 2560^2 = 26,214,400; MLP 3 x 2560 x 6912 =
    # 53,084,160; 4 layers + head 2560 x 50304 = 128,778,240
    assert matmul_params(c) == 4 * (26_214_400 + 53_084_160) + 128_778_240
    assert matmul_params(c) == 445_972_480
    # causal attention: 6 x 32 x 80 x 2049 x 4 a token
    per_tok = 6 * 445_972_480 + 6 * 2560 * 2049 * 4
    assert flops.train_flops_per_token(c, 2048) == per_tok
    assert 22.9e12 < 8192 * per_tok < 23.0e12


def _bare_copy(tmp_path) -> Path:
    shutil.copytree(BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path


@pytest.mark.parametrize("where", ["repo", "bare"])
def test_run_exits_nonzero_without_a_tpu(tmp_path, where):
    root = REPO if where == "repo" else _bare_copy(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         WORKLOADS[0], "--seed", str(2**40 + 1), "--seconds", "1",
         "--trace", "0"], cwd=root, env=env, capture_output=True,
        text=True, timeout=120)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
    assert "needs a TPU" in r.stderr
