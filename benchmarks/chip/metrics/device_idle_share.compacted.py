"""``device_idle_share.train`` in a cell that reports
``train_tokens_per_s.compacted``."""

from chipbench.cell import load_reader

read = load_reader("device_idle_share.train")
