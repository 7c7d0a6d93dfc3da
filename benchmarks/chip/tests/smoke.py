"""Cells at smoke size, for driving the harness on the CPU in the tests.

Each cell's own files carry its smoke sizes under a ``smoke`` key: the
configuration's widths and depth, the traffic's batch, sequence and corpus,
and the limits of the numbers compared. Nothing here is a benchmark
measurement: the tests call the drivers directly, past ``run.py``'s look
for a TPU.

The smoke limits are looser than the cells', which were set on the chip at
published widths: at a width of 64 a leaf holds a few hundred elements, so
one element whose Adam step flips sign between bf16 and float32 moves the
leaf's change by a visible share (update gaps of about 7e-4 here against
under 1e-4 on the chip). A fault that reads above these limits reads above
the cells' too.
"""

from __future__ import annotations

import json
import sys
from dataclasses import replace
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parents[1]
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench.cell import limits_path, load_driver, resolve  # noqa: E402

PEAKS = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(base[k], v) if isinstance(v, dict) else v
    return out


def smoke_cell(workload: str):
    cell = resolve(workload)
    limits = json.loads(limits_path(workload).read_text())["smoke"]
    assert set(limits) == set(cell.limits), "smoke limits name every number"
    return replace(cell, config=_merge(cell.config, cell.config["smoke"]),
                   traffic=_merge(cell.traffic, cell.traffic["smoke"]),
                   limits={k: float(v) for k, v in limits.items()})


def run_smoke(cell, seed: int = 7, seconds: float = 0.5, trace_dir=None):
    import jax
    import time
    return load_driver(cell.traffic["kind"]).run(
        cell, seed, seconds, trace_dir, time.perf_counter(),
        jax.devices()[:1], PEAKS)
