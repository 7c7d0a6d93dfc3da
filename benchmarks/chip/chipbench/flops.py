"""Operations the algorithm needs, counted from shapes.

Each family's counts live in ``families/<family>.py``; this module picks
the configuration's family and adds what all families share: a trained
token costs its forward pass three times (forward and backward).
"""

from __future__ import annotations

from chipbench.cell import load_family


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward and backward (3 x forward) per trained token."""
    fam = load_family(c["family"])
    return 6 * fam.matmul_params(c) + 3 * fam.mixer_fwd_per_token(c, seq)
