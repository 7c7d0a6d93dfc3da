"""The chips a run measures, their peaks and their memory."""

from __future__ import annotations

import json

from chipbench.cell import BENCH_DIR

PEAKS_JSON = BENCH_DIR / "peaks.json"


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


def require_chips(n: int) -> list:
    """The first ``n`` TPU devices; raises :class:`NoChip` otherwise.

    There is no fallback: a run that finds another platform measures
    nothing."""
    import jax
    devs = jax.devices()
    if not devs or devs[0].platform != "tpu":
        plat = devs[0].platform if devs else "none"
        raise NoChip(f"needs a TPU, JAX found platform {plat!r}")
    if len(devs) < n:
        raise NoChip(f"cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def describe(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peaks(kind: str) -> dict:
    """Published peaks of one chip of ``kind``; an unknown kind is an error."""
    table = json.loads(PEAKS_JSON.read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS_JSON}; "
                       f"known: {sorted(table)}")
    return table[kind]


def memory_peak_bytes(devs) -> int | None:
    """Peak bytes in use on the fullest chip, where the backend reports it."""
    peaks_ = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks_.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_) if peaks_ else None
