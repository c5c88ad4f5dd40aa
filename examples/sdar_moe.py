"""SDAR-MoE decoder (models/sdar_moe.py): a softmax-routed expert layer in
every block, trained by diffusion over blocks through the Trainer's normal
step (the model's `corrupt` draws each step's noise; attention runs under the
block-diffusion mask).

  python -m examples.sdar_moe --device=cpu --steps=20
  python -m examples.sdar_moe --device=tpu --attention=flash --remat --seq-len=2048

The preset is test-sized (`SdarMoeConfig.tiny`: two layers, eight experts, all
of them held here).
"""

from __future__ import annotations

import argparse


def main(argv: list[str] | None = None) -> float:
    p = argparse.ArgumentParser()
    p.add_argument("--device", default="auto", choices=["tpu", "cpu", "auto"])
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch-size", type=int, default=4)
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--block-length", type=int, default=4)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--attention", default="dense", choices=["dense", "flash"])
    p.add_argument("--remat", action="store_true")
    p.add_argument("--checkpoint-dir", default=None)
    args = p.parse_args(argv)

    from kubeflow_tpu.utils import select_device

    select_device(args.device)

    from kubeflow_tpu.models import (SdarMoeConfig, SdarMoeLM,
                                    sdar_eval_metrics, sdar_loss)
    from kubeflow_tpu.train import Trainer, TrainerConfig
    from kubeflow_tpu.train.data import synthetic_lm_dataset

    cfg = SdarMoeConfig.tiny(attention=args.attention, remat=args.remat,
                             block_length=args.block_length)
    ds = synthetic_lm_dataset(
        n_train=args.batch_size * 8, n_test=args.batch_size * 2,
        seq_len=args.seq_len, vocab_size=cfg.vocab_size,
    )
    trainer = Trainer(
        SdarMoeLM(cfg),
        TrainerConfig(
            batch_size=args.batch_size, steps=args.steps,
            learning_rate=args.lr, warmup_steps=min(100, args.steps // 10),
            checkpoint_dir=args.checkpoint_dir, log_every_steps=5,
        ),
        loss_fn=sdar_loss,
        eval_metrics_fn=sdar_eval_metrics,
    )
    _, metrics = trainer.fit(ds)
    return metrics.get("final_loss", float("inf"))


if __name__ == "__main__":
    main()
