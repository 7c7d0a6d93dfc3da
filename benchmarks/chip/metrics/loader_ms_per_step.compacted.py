"""``loader_ms_per_step`` in a cell that reports
``train_tokens_per_s.compacted``."""

from chipbench.cell import load_reader

read = load_reader("loader_ms_per_step")
