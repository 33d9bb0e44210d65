"""End-to-end training driver: real on-disk dataset -> tunable pipeline ->
fault-tolerant trainer (checkpoints + autotune) for any LM-family arch.

This drives a few hundred steps of a reduced-config model on CPU.

Run: PYTHONPATH=src python examples/train_lm.py [--arch codeqwen1.5-7b]
     PYTHONPATH=src python examples/train_lm.py --arch falcon-mamba-7b --steps 50
"""

import argparse

import numpy as np

from repro.configs import get_config, reduced
from repro.data import (
    BACKENDS,
    DataPipeline,
    PipelineConfig,
    TokenRecordCodec,
    open_dataset,
    write_dataset,
)
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="codeqwen1.5-7b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    args = ap.parse_args()

    cfg = reduced(get_config(args.arch))
    print(f"== training {cfg.name} (reduced: {cfg.n_layers}L d{cfg.d_model}) ==")

    # real storage-backed dataset (the thing the paper optimizes)
    seq = args.seq_len + 1
    codec = TokenRecordCodec(seq)
    rng = np.random.default_rng(0)
    records = [
        codec.encode(rng.integers(0, cfg.vocab_size, seq, dtype=np.int32))
        for _ in range(2048)
    ]
    backend = BACKENDS["tmpfs"]
    manifest = write_dataset(backend, f"ex_train_{args.arch}", records, "packed")
    reader = open_dataset(backend, manifest)
    pipe = DataPipeline.from_reader(
        reader, seq, PipelineConfig(batch_size=args.batch_size, num_workers=0)
    )

    trainer = Trainer(
        cfg, pipe,
        TrainerConfig(num_steps=args.steps, ckpt_every=50,
                      ckpt_dir=f"/tmp/repro_ckpt_{args.arch}", log_every=20),
    )
    out = trainer.run()
    h = out["history"]
    k = max(len(h) // 10, 1)
    print(f"loss: first10={np.mean(h[:k]):.4f} last10={np.mean(h[-k:]):.4f} "
          f"(steps={out['final_step']})")
    assert np.mean(h[-k:]) < np.mean(h[:k]), "loss should decrease"
    print("OK — loss decreased; checkpoints in", trainer.tcfg.ckpt_dir)
    pipe.close()
    reader.close()


if __name__ == "__main__":
    main()
