"""End-to-end driver — train a (reduced) LM a few hundred steps on a data
lake, with LST checkpoints + XTable sync + kill/restore (paper Scenario 2
inside the training framework: trainer writes Hudi, evaluator reads Iceberg).

Run: PYTHONPATH=src python examples/train_lake.py [--steps 200] [--arch yi-9b]
"""

import argparse
import sys
import tempfile
from dataclasses import replace

sys.path.insert(0, "src")

from repro.configs import smoke_config
from repro.data import LakeDataLoader, write_synth_corpus
from repro.launch.compile_cache import enable_compile_cache
from repro.lst import LocalFS
from repro.models.model import Model
from repro.train.trainer import Trainer, TrainerConfig

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=200)
ap.add_argument("--arch", default="yi-9b")
args = ap.parse_args()
enable_compile_cache()

fs = LocalFS()
root = tempfile.mkdtemp()
print("world dir:", root)

# corpus lives in a Delta table (could be any format)
write_synth_corpus(fs, f"{root}/corpus", fmt="delta", n_docs=128,
                   pack_len=65, vocab=256, n_shards=4)

cfg = replace(smoke_config(args.arch), vocab_size=256)
model = Model(cfg)
loader = LakeDataLoader(fs, f"{root}/corpus", "delta", batch_size=8,
                        seq_len=64)

trainer = Trainer(model, loader, fs, f"{root}/ckpt", TrainerConfig(
    steps=args.steps, save_every=50, log_every=20, ce_chunk=64,
    ckpt_format="hudi", sync_targets=("iceberg", "delta")))
trainer.init_or_restore()
history = trainer.run()
print(f"loss: {history[0][1]:.3f} -> {history[-1][1]:.3f}")

# --- simulate preemption + restart reading the ICEBERG view ---------------
loader2 = LakeDataLoader(fs, f"{root}/corpus", "delta", batch_size=8,
                         seq_len=64)
restarted = Trainer(model, loader2, fs, f"{root}/ckpt", TrainerConfig(
    steps=args.steps + 20, save_every=50, log_every=20, ce_chunk=64,
    restore_format="iceberg"))
step = restarted.init_or_restore()
print(f"restarted from step {step} (restored via ICEBERG metadata, "
      f"loader cursor {loader2.row})")
restarted.run()
print("done; checkpoints visible as:",
      restarted.ckpt.steps(), "(hudi) ==",
      restarted.ckpt.steps(fmt="delta"), "(delta)")
