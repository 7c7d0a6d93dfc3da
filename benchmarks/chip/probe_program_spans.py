"""Traced run of one train cell that also reads the program's own spans and
counters: a measuring aid beside the benchmark, which ``run.py`` never loads.

It runs the cell's driver as ``run.py --trace 1`` does, with two additions
made from outside the harness's files: gaps of device idle time are also put
down to the program's ``train.*`` and ``data.*`` spans (by widening
``chipbench.trace.SPAN_PREFIX``), and ``Trainer.run`` is wrapped to take the
change of the run's ``Telemetry`` counters over each call. From the kept
trace it writes, as JSON to ``--out``:

* ``train_host_ms_per_step``: (sum of ``train.step`` - sum of the
  ``train.loss_sync`` spans) / count of ``train.step``, over the spans that
  lie wholly inside ``bench.window``;
* ``loader_read_mb_per_step``: the window's ``storage.bytes_read`` change /
  its steps / 1e6;
* ``compiles_in_window``: the window's ``jax.compiles`` change;
* ``idle_gaps`` (attributed to program spans too), ``host_spans``,
  ``device_ops`` with the op metadata of each (its named scopes), and
  ``unexplained_idle_share``, the share of idle time under no program span.

A program without telemetry (no ``Trainer.telemetry``) reads ``None`` for
the counters. Run from the root of a checkout, on the chip:

    python3 benchmarks/chip/probe_program_spans.py \\
        --workload stablelm3b.train_lake --seed 1234567890123 \\
        --seconds 12 --out probe.json

``--smoke`` runs the cell at its smoke size on the CPU, with the CPU
client's ops standing in for the device's.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
for p in (str(BENCH), str(BENCH.parents[1] / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import check  # noqa: E402
from chipbench import trace as tr  # noqa: E402
from chipbench.cell import load_driver, resolve  # noqa: E402

PROGRAM = ("train.", "data.")
UNEXPLAINED = ("bench.step_loop", "bench.window")


def _cpu_ops_as_device(reduce_planes):
    """``reduce_planes`` with the CPU client's XLA ops as a TPU plane."""
    def reduce(planes, top=10):
        planes = list(planes)
        ops = [e for p in planes if p.name == "/host:CPU" for ln in p.lines
               if ln.name.startswith("tf_XLA") for e in ln.events]
        return reduce_planes(planes + [SimpleNamespace(
            name="/device:TPU:0",
            lines=[SimpleNamespace(name=tr.OPS_LINE, events=ops)])], top)
    return reduce


def _watch_trainer_runs() -> list:
    """Wrap ``Trainer.run``: each call appends its steps and the change of
    the trainer's telemetry counters (None without telemetry)."""
    from repro.train.trainer import Trainer
    calls, run = [], Trainer.run

    def counted(self):
        tel = getattr(self, "telemetry", None)
        before = dict(tel.counters) if tel is not None else None
        steps = self.cfg.steps - self.start_step
        out = run(self)
        calls.append({"steps": steps, "counters": None if tel is None else
                      {k: v - before.get(k, 0)
                       for k, v in tel.counters.items()}})
        return out
    Trainer.run = counted
    return calls


def _read_trace(trace_dir: str, smoke: bool):
    """Host spans as (name, start_ns, end_ns) and the metadata of the first
    device op of each label."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(tr.newest_xplane(trace_dir)))
    host, op_stats = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                     for line in plane.lines for e in line.events
                     if e.name.startswith(("bench.",) + PROGRAM)]
        if plane.name == "/device:TPU:0" or smoke:
            for line in plane.lines:
                if line.name != tr.OPS_LINE and \
                        not line.name.startswith("tf_XLA"):
                    continue
                for e in line.events:
                    op_stats.setdefault(tr.op_label(e.name), {
                        str(k): str(v)[:300] for k, v in e.stats})
    return host, op_stats


def _spans_inside_window(host: list) -> dict:
    lo, hi = next((s, e) for n, s, e in host if n == tr.WINDOW)
    out: dict = {}
    for n, s, e in host:
        if n.startswith(PROGRAM) and s >= lo and e <= hi:
            tot = out.setdefault(n, [0, 0.0])
            tot[0] += 1
            tot[1] += (e - s) * 1e-9
    return out


def probe(workload: str, seed: int, seconds: float, smoke: bool) -> dict:
    tr.SPAN_PREFIX = ("bench.",) + PROGRAM
    if smoke:
        tr.reduce_planes = _cpu_ops_as_device(tr.reduce_planes)
    calls = _watch_trainer_runs()
    if smoke:
        sys.path.insert(0, str(BENCH / "tests"))
        import jax
        import smoke as smoke_cells
        cell = smoke_cells.smoke_cell(workload)
        devs, pk = jax.devices()[:1], smoke_cells.PEAKS
    else:
        import run as runmod
        from chipbench.device import describe, peaks, require_chips
        cell = resolve(workload)
        devs = require_chips(cell.chips)
        runmod.enable_cache()
        pk = peaks(describe(devs)["kind"])
    trace_dir = tempfile.mkdtemp(prefix="probe-")
    out = load_driver(cell.traffic["kind"]).run(cell, seed, seconds,
                                                trace_dir, T0, devs, pk)
    correct, checks = check.judge(out.numbers, cell.limits)
    r, ts = out.readings, out.readings.trace
    host, op_stats = _read_trace(trace_dir, smoke)
    host_spans = _spans_inside_window(host)
    win = calls[-1]
    res = {"workload": workload, "seed": seed, "correct": correct,
           "checks": {k: v["value"] for k, v in checks.items()},
           "end_to_end": out.end_to_end, "window_steps": win["steps"],
           "window_counters": win["counters"], "stretch": r.window,
           "window_s": ts.window_s, "busy_s": ts.busy_s,
           "idle_gaps": ts.idle_gaps, "device_ops": ts.device_ops,
           "host_spans": host_spans,
           "op_stats": {k: op_stats.get(k) for k, _ in ts.device_ops},
           "stretch_tokens_per_s": r.window["tokens"] / r.window["seconds"]}
    step = host_spans.get("train.step")
    if step:
        sync = host_spans.get("train.loss_sync", [0, 0.0])
        res["train_host_ms_per_step"] = (step[1] - sync[1]) / step[0] * 1e3
    c = win["counters"]
    if c is not None:
        res["loader_read_mb_per_step"] = \
            c.get("storage.bytes_read", 0) / win["steps"] / 1e6
        res["compiles_in_window"] = c.get("jax.compiles")
    idle = ts.window_s - ts.busy_s
    res["unexplained_idle_share"] = sum(
        v for k, v in ts.idle_gaps if k in UNEXPLAINED) / idle \
        if idle > 0 else None
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--smoke", action="store_true")
    a = ap.parse_args(argv)
    res = probe(a.workload, a.seed, a.seconds, a.smoke)
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps({k: res.get(k) for k in (
        "workload", "seed", "correct", "end_to_end", "train_host_ms_per_step",
        "loader_read_mb_per_step", "compiles_in_window", "idle_gaps",
        "unexplained_idle_share", "stretch_tokens_per_s", "window_steps")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
