"""Readings that the limits of a cell's ``correct`` are set from.

    python3 benchmarks/chip/calibrate.py --workload <name> \
        --seeds 1,2,...,12 [--control-seeds 1,2,3]

On the chip, at the cell's own sizes, for each seed: the numbers that the
benchmark's run compares for the program (the lower readings); on the
control seeds also the same numbers for the control, the plain reference
computed one precision below the configuration's (float8 e4m3 matmul
operands for bfloat16), and the half-batch fault (the reference on the
first half of each batch). The benchmark's own runs
never run these. One JSON line per seed and reading goes to standard
output, each judged by ``check.judge`` against the cell's limits
(``correct``; the control and the fault have to come out false), then how
many seeds of each reading came out correct, and the largest program
reading and the smallest control or fault reading of each number.
"""

from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import check, reftrain  # noqa: E402
from chipbench.cell import Cell, load_reference, resolve  # noqa: E402
from chipbench.spans import Spans  # noqa: E402


def train_seed(cell: Cell, seed: int, control: bool) -> list[dict]:
    from chipbench import train
    t = cell.traffic
    root = tempfile.mkdtemp(prefix="chipbench-cal-")
    try:
        trainer, proxy, rows, make_w, view = train.build(cell, seed, root,
                                                         Spans())
        trainer.stamps = []
        trainer.save = lambda step: trainer.stamps.append(time.perf_counter())
        prog, _ = train.first_steps(trainer, make_w, t["optimizer"]["b1"])
        trainer.params = trainer.opt_state = None
        del trainer
        gc.collect()
        docs, wrong = train.rows_wrong(proxy, *view, rows)
        batches = train.reference_batches(proxy.consumed, docs, rows)
        ref_mod = load_reference(cell.config["reference"])

        def reference(**kw):
            return reftrain.train_readings(ref_mod, cell.config, make_w,
                                           batches, t["optimizer"],
                                           t["z_loss"], **kw)

        def reading(name: str, numbers: dict) -> dict:
            numbers["rows_wrong"] = wrong
            correct, _ = check.judge(numbers, cell.limits)
            return {"seed": seed, "reading": name, "correct": correct,
                    **numbers}
        ref = reference()
        out = [reading("program", check.train_numbers(prog, ref))]
        if control:
            out.append(reading("control_fp8", check.train_numbers(
                reference(lowp="fp8"), ref)))
            out.append(reading("fault_half_batch", check.train_numbers(
                reference(rows=t["batch"] // 2), ref)))
        return out
    finally:
        shutil.rmtree(root, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args()
    cell = resolve(args.workload)
    from chipbench.device import require_chips
    require_chips(cell.chips)
    import run
    run.enable_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    lines = []
    for seed in seeds + sorted(controls - set(seeds)):
        t0 = time.perf_counter()
        got = train_seed(cell, seed, seed in controls)
        for g in got:
            if g["reading"] == "program" and seed not in seeds:
                continue
            g["seconds"] = time.perf_counter() - t0
            print(json.dumps(g), flush=True)
            lines.append(g)
    for kind in sorted({g["reading"] for g in lines}):
        got = [g["correct"] for g in lines if g["reading"] == kind]
        print(json.dumps({"reading": kind, "seeds": len(got),
                          "correct": sum(got), "limits": cell.limits}),
              flush=True)
    names = [k for k in cell.limits if any(k in g for g in lines)]
    for name in names:
        prog = [g[name] for g in lines if g["reading"] == "program"]
        summary = {"number": name, "program_max": max(prog)}
        for kind in sorted({g["reading"] for g in lines} - {"program"}):
            vals = [g[name] for g in lines if g["reading"] == kind
                    and name in g]
            if vals:
                summary[f"{kind}_min"] = min(vals)
        print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
