"""The numbers that decide ``correct``, and their limits.

Training (program against the reference, after three steps on the same
rows from the same weights):

* ``loss_gap``    worst step of |loss - ref| / |ref|
* ``grad_gap``    worst leaf of | |g| - |g_ref| | / max(|g_ref|, median leaf)
                  for the first clipped gradient as the optimizer got it
* ``update_gap``  the same for each leaf's change over the three steps
* ``rows_wrong``  rows the steps consumed, the first steps' and every
                  one of the window's, that are not exactly the seed's
                  corpus row that the loader's cursor was due to hand out
                  (the read view's files sorted by path, rows in file
                  order, from row 0), plus corpus rows that the view holds
                  other than exactly once (limit 0)

Leaves whose reference gradient is under a thousandth of the median
leaf's move under Adam by round-off alone; they are left out of the
gradient and update gaps by that rule, never by name.
"""

from __future__ import annotations

import math
import statistics

import numpy as np

TINY_LEAF = 1e-3


def kept_leaves(ref_grad_norms: dict) -> list[str]:
    med = statistics.median(ref_grad_norms.values())
    return [k for k, v in ref_grad_norms.items() if v >= TINY_LEAF * med]


def _worst_leaf_gap(prog: dict, ref: dict, keep: list[str]) -> float:
    med = statistics.median(ref[k] for k in keep)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def train_numbers(prog: dict, ref: dict) -> dict:
    keep = kept_leaves(ref["grad_norms"])
    return {
        "loss_gap": max(abs(p - r) / abs(r)
                        for p, r in zip(prog["losses"], ref["losses"])),
        "grad_gap": _worst_leaf_gap(prog["grad_norms"], ref["grad_norms"],
                                    keep),
        "update_gap": _worst_leaf_gap(prog["change_norms"],
                                      ref["change_norms"], keep),
    }


def rows_wrong(docs: np.ndarray, order: np.ndarray, n_rows: int) -> int:
    """``docs``: corpus index of each consumed row, -1 where it is no corpus
    row; ``order``: corpus index of each row of the view in cursor order."""
    listed = (order >= 0) & (order < n_rows)
    table = int(np.sum(~listed)) + int(np.sum(
        np.bincount(order[listed], minlength=n_rows) != 1))
    if not len(order):
        return table + len(docs)
    due = order[np.arange(len(docs)) % len(order)]
    return table + int(np.sum(np.asarray(docs) != due))


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): every number present, finite
    and within its limit, and every limit given a number."""
    ok = set(numbers) == set(limits)
    out = {}
    for name in sorted(set(numbers) | set(limits)):
        value, limit = numbers.get(name), limits.get(name)
        out[name] = {"value": value, "limit": limit}
        if value is None or limit is None or not math.isfinite(value) \
                or value > limit:
            ok = False
    return ok, out
