"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

from the root of a checkout on a machine with the chips the cell asks
for. The run loads, warms up every shape its traffic uses, measures for
``--seconds``, checks what the timed path produced against a plain
reference, and prints as the last line of standard output one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: every
number compared, beside its limit (also the last lines of standard error).

It exits non-zero and prints no result where JAX finds no TPU, or fewer
chips than the cell asks for. JAX's compilation cache lives in
``.jax_cache/`` at the root of the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench.cell import REPO, load_driver, load_reader, resolve  # noqa: E402

CACHE_DIR = REPO / ".jax_cache"


def enable_cache() -> str:
    """JAX's persistent cache at the fixed path ``.jax_cache/`` at the root
    of the checkout (the path is part of the cache's key), for every
    program however quick to compile, so that only a cell's first run in a
    checkout compiles."""
    import jax
    path = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def per_layer(cell, readings) -> dict:
    out = {}
    for m in cell.per_layer:
        value = load_reader(m["name"])(readings) if readings else None
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = resolve(args.workload)
    from chipbench import check
    from chipbench.device import NoChip, describe, peaks, require_chips
    try:
        devs = require_chips(cell.chips)
    except NoChip as e:
        print(f"chip benchmark: {e}", file=sys.stderr)
        return 1
    enable_cache()
    driver = load_driver(cell.traffic["kind"])
    device = describe(devs)
    chip_peaks = peaks(device["kind"])

    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tdir:
        out = driver.run(cell, args.seed, args.seconds,
                         tdir if args.trace else None, T_START, devs,
                         chip_peaks)
    correct, checks = check.judge(out.numbers, cell.limits)
    correct = correct and out.failed == 0
    device["memory_peak_bytes"] = out.memory_peak_bytes
    result = {"correct": correct, "attempted": out.attempted,
              "failed": out.failed}
    if args.trace:
        tsum = out.readings.trace
        device["busy_s"] = tsum.busy_s
        device["window_s"] = tsum.window_s
        result["metrics"] = per_layer(cell, out.readings)
        result["device"] = device
        result["breakdown"] = {"device_ops": tsum.device_ops,
                               "idle_gaps": tsum.idle_gaps}
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in out.end_to_end.items() if k in units}
        result["device"] = device
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct = {correct}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
