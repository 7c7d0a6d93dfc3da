"""The program-span probe: its window arithmetic by hand, and one run of a
cell at smoke size on the CPU."""

from __future__ import annotations

import json
import subprocess
import sys

import smoke  # noqa: F401  (puts the harness on sys.path)

import probe_program_spans as probe

STEP_SPANS = ("train.step", "train.batch", "train.h2d", "train.dispatch",
              "train.loss_sync")


def test_spans_inside_window_sum_only_whole_program_spans():
    host = [("bench.window", 100, 1100),
            ("train.step", 50, 150),          # starts before the window
            ("train.step", 200, 400),
            ("train.loss_sync", 300, 400),
            ("data.read_chunk", 210, 260),
            ("bench.next_batch", 205, 270),   # a harness span: not summed
            ("train.step", 1000, 1200)]       # ends after the window
    out = probe._spans_inside_window(host)
    assert out.keys() == {"train.step", "train.loss_sync", "data.read_chunk"}
    assert out["train.step"][0] == 1
    assert abs(out["train.step"][1] - 200e-9) < 1e-15
    assert out["train.loss_sync"][0] == out["data.read_chunk"][0] == 1


def test_probe_reads_the_programs_spans_and_counters_at_smoke_size(
        tmp_path):
    out = tmp_path / "probe.json"
    subprocess.run([sys.executable, probe.__file__, "--workload",
                    "stablelm3b.train_hudi_compacted", "--seed", "3000000002",
                    "--seconds", "0.5", "--out", str(out), "--smoke"],
                   check=True, capture_output=True, timeout=600)
    r = json.loads(out.read_text())
    assert r["correct"]
    steps = r["window_steps"]
    assert steps > 0
    assert {k: r["host_spans"][k][0] for k in STEP_SPANS} == \
        dict.fromkeys(STEP_SPANS, steps)
    assert r["compiles_in_window"] == 0
    assert round(r["loader_read_mb_per_step"] * 1e6 * steps) == \
        r["window_counters"]["storage.bytes_read"] > 0
    assert 0 < r["train_host_ms_per_step"] < 1e3 * r["stretch"]["seconds"]
    names = {k for k, _ in r["idle_gaps"]}
    assert names & {"train.h2d", "train.dispatch", "train.loss_sync"}
    assert 0 <= r["unexplained_idle_share"] <= 1
