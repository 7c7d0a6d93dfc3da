"""``train_mfu`` in a cell that reports ``train_tokens_per_s.compacted``."""

from chipbench.cell import load_reader

read = load_reader("train_mfu")
