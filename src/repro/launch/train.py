"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs a reduced config (``--full`` for published widths) end-to-end on this
host with the real data pipeline + autotuner + checkpointing.
"""

from __future__ import annotations

import argparse
import pathlib

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--backend", default="tmpfs", choices=["tmpfs", "disk"])
    ap.add_argument("--format", default="packed")
    ap.add_argument("--num-workers", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--no-autotune", action="store_true")
    ap.add_argument("--n-records", type=int, default=2048)
    args = ap.parse_args()

    from ..configs import get_config, reduced
    from ..data import (
        BACKENDS, DataPipeline, PipelineConfig, TokenRecordCodec, write_dataset,
        open_dataset,
    )
    from ..train.trainer import Trainer, TrainerConfig

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    assert cfg.family in ("dense", "moe", "ssm", "hybrid"), (
        "the token-LM launcher covers LM families; whisper/vlm use examples/")

    # build a real on-disk dataset
    seq = args.seq_len + 1  # +1 for the shifted labels
    codec = TokenRecordCodec(seq)
    rng = np.random.default_rng(0)
    records = [
        codec.encode(rng.integers(0, cfg.vocab_size, size=seq, dtype=np.int32))
        for _ in range(args.n_records)
    ]
    backend = BACKENDS[args.backend]
    manifest = write_dataset(backend, f"train_{args.arch}", records, args.format)
    reader = open_dataset(backend, manifest)
    pipe = DataPipeline.from_reader(
        reader, seq,
        PipelineConfig(batch_size=args.batch_size, num_workers=args.num_workers),
    )

    tcfg = TrainerConfig(
        num_steps=args.steps,
        ckpt_dir=args.ckpt_dir,
        autotune=not args.no_autotune,
    )
    trainer = Trainer(cfg, pipe, tcfg)
    out = trainer.run()
    h = out["history"]
    print(f"[train] done at step {out['final_step']}; "
          f"loss {h[0]:.4f} -> {h[-1]:.4f} over {len(h)} steps")
    pipe.close()
    reader.close()


if __name__ == "__main__":
    from ..compile_cache import use_compile_cache

    use_compile_cache()
    main()
