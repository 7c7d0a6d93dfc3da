"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

``main`` gives the CPU backend 512 placeholder devices (``XLA_FLAGS``)
before anything asks jax for a device: jax fixes the device count when
its backend first starts, and the production meshes need 512.

Per cell this proves:
* the sharding config is coherent (SPMD partitioning succeeds),
* the memory footprint fits (``memory_analysis`` per device),
* and extracts the roofline raw terms (FLOPs / HBM bytes / collective bytes)
  via the scan-aware HLO walker (``hlo_analysis``).

Results land in ``experiments/dryrun/<arch>__<shape>__<mesh>.json``.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun [--arch ID|all]
        [--shape NAME|all] [--mesh single|multi|both] [--out DIR]
"""

import argparse
import json
import os
import time
import traceback

import jax

from repro.configs import ARCHS
from repro.launch.cells import CellBuild, build_cell, is_applicable
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import make_production_mesh, pod_size
from repro.models.config import SHAPE_CELLS


def compile_cell(cb: CellBuild, mesh):
    """Lower + compile one cell on ``mesh`` -> (compiled, lower_s, compile_s).

    The cell's shardings are bare ``PartitionSpec``s, resolved against
    ``mesh`` through ``jax.set_mesh``; so are the model's activation
    constraints."""
    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted = jax.jit(cb.fn, in_shardings=cb.in_shardings,
                         out_shardings=cb.out_shardings,
                         donate_argnums=cb.donate_argnums)
        lowered = jitted.lower(*cb.args)
        t1 = time.time()
        compiled = lowered.compile()
    return compiled, t1 - t0, time.time() - t1


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str,
             grad_accum: int = 1, save: bool = True,
             overrides=None) -> dict:
    mesh_name = "pod2" if multi_pod else "pod1"
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name, "ok": False}
    ok, why = is_applicable(arch, shape)
    if not ok:
        rec.update({"skipped": True, "reason": why})
        if save:
            _save(out_dir, rec)
        return rec
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        cb = build_cell(arch, shape, mesh, grad_accum=grad_accum)
        if overrides:
            cb = overrides(cb)
        compiled, lower_s, compile_s = compile_cell(cb, mesh)
        mem = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        hlo = analyze(compiled.as_text(), pod_size(mesh))
        rec.update({
            "ok": True,
            "step": cb.step_name,
            "n_params": cb.n_params,
            "n_active_params": cb.n_active_params,
            "attn_hbm_bytes": cb.attn_hbm_bytes,
            "tokens_per_step": cb.cell.global_batch *
            (cb.cell.seq_len if cb.cell.step != "decode" else 1),
            "lower_s": round(lower_s, 2),
            "compile_s": round(compile_s, 2),
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "peak_bytes_est": mem.argument_size_in_bytes +
                mem.temp_size_in_bytes - mem.alias_size_in_bytes,
            },
            "xla_cost": {"flops": ca.get("flops"),
                         "bytes": ca.get("bytes accessed")},
            "hlo": hlo,
        })
    except Exception as e:  # record the failure, keep sweeping
        rec.update({"error": f"{type(e).__name__}: {e}",
                    "trace": traceback.format_exc()[-2000:]})
    if save:
        _save(out_dir, rec)
    return rec


def _save(out_dir: str, rec: dict) -> None:
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(
        out_dir, f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)


def summarize(rec: dict) -> str:
    if rec.get("skipped"):
        return (f"{rec['arch']:22s} {rec['shape']:12s} {rec['mesh']:5s} "
                f"SKIP ({rec['reason'][:40]}...)")
    if not rec.get("ok"):
        return (f"{rec['arch']:22s} {rec['shape']:12s} {rec['mesh']:5s} "
                f"FAIL {rec.get('error', '?')[:80]}")
    m = rec["memory"]
    h = rec["hlo"]
    return (f"{rec['arch']:22s} {rec['shape']:12s} {rec['mesh']:5s} OK "
            f"compile={rec['compile_s']:6.1f}s "
            f"mem/dev={(m['peak_bytes_est']) / 2**30:7.2f}GiB "
            f"flops/dev={h['flops']:.3e} hbm={h['hbm_bytes']:.3e} "
            f"ici={h['coll_ici_bytes']:.3e} dcn={h['coll_dcn_bytes']:.3e}")


def main() -> None:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--grad-accum", type=int, default=1)
    args = ap.parse_args()

    archs = list(ARCHS) if args.arch == "all" else [args.arch]
    shapes = [c.name for c in SHAPE_CELLS] if args.shape == "all" \
        else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                rec = run_cell(arch, shape, multi, args.out,
                               grad_accum=args.grad_accum)
                print(summarize(rec), flush=True)
                if not rec.get("ok") and not rec.get("skipped"):
                    n_fail += 1
    print(f"\ndry-run complete, {n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
