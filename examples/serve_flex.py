"""Scenario 3 — engine flexibility: the server restores weights through the
Iceberg view of checkpoints the trainer wrote as Hudi (snapshot+manifest
metadata with file statistics = the right shape for serving-fleet scan
planning), then serves batched requests.

Run: PYTHONPATH=src python examples/serve_flex.py
"""

import sys
import tempfile
from dataclasses import replace

sys.path.insert(0, "src")

import numpy as np

from repro.configs import smoke_config
from repro.data import LakeDataLoader, write_synth_corpus
from repro.launch.compile_cache import enable_compile_cache
from repro.lst import LocalFS
from repro.models.model import Model
from repro.serve.engine import Request, ServeEngine
from repro.train.trainer import Trainer, TrainerConfig

enable_compile_cache()
fs = LocalFS()
root = tempfile.mkdtemp()

# quick training run to produce checkpoints (trainer = Hudi engine)
write_synth_corpus(fs, f"{root}/corpus", fmt="delta", n_docs=64,
                   pack_len=65, vocab=256)
cfg = replace(smoke_config("stablelm-3b"), vocab_size=256)
model = Model(cfg)
trainer = Trainer(
    model,
    LakeDataLoader(fs, f"{root}/corpus", "delta", batch_size=8, seq_len=64),
    fs, f"{root}/ckpt",
    TrainerConfig(steps=60, save_every=30, log_every=20, ce_chunk=64,
                  ckpt_format="hudi", sync_targets=("iceberg",)))
trainer.init_or_restore()
trainer.run()

# the serving engine opens the SAME checkpoint directory as ICEBERG
engine = ServeEngine.from_lake(model, fs, f"{root}/ckpt", fmt="iceberg",
                               cache_len=96)
rng = np.random.default_rng(0)
requests = [Request(prompt=rng.integers(0, 256, size=n).tolist(),
                    max_new=12) for n in (5, 3, 8, 2)]
outs = engine.generate(requests, temperature=0.0)
for i, (req, out) in enumerate(zip(requests, outs)):
    print(f"req{i} prompt={req.prompt} -> {out}")
print("served from the Iceberg view of Hudi-written checkpoints — "
      "no weight files copied.")
