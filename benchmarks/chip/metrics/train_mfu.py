"""Model FLOP/s utilisation of training, in % of the chips' bf16 peak.

Operations a trained token requires (forward and backward of every matrix
multiplication, causal attention or SSD terms; recomputation not
counted, from ``chipbench.flops``) times the tokens trained in the traced
stretch, over its host-clock seconds and the chips' peak."""

from chipbench import flops


def read(r):
    w = r.window
    if not w.get("tokens") or not w.get("seconds"):
        return None
    ops = w["tokens"] * flops.train_flops_per_token(r.config,
                                                   r.traffic["seq_len"])
    return ops / w["seconds"] / (r.peaks["bf16_flops_per_s"] * r.chips) * 100
