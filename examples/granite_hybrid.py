"""Granite 4.0-H decoder (models/granite_hybrid.py): Mamba-2 mixers (chunked
state-space scan, causal depthwise convolution, gated norm: parallel/ssm.py)
with a grouped-query attention layer without positions among them, trained
through the Trainer's normal step.

  python -m examples.granite_hybrid --device=cpu --steps=20
  python -m examples.granite_hybrid --device=tpu --attention=flash --remat --seq-len=2048

The preset is test-sized (`GraniteHybridConfig.tiny`: a Mamba-2 layer, the
attention layer, another Mamba-2 layer, chunks of 8).
"""

from __future__ import annotations

import argparse


def main(argv: list[str] | None = None) -> float:
    p = argparse.ArgumentParser()
    p.add_argument("--device", default="auto", choices=["tpu", "cpu", "auto"])
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch-size", type=int, default=4)
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--attention", default="dense", choices=["dense", "flash"])
    p.add_argument("--remat", action="store_true")
    p.add_argument("--checkpoint-dir", default=None)
    args = p.parse_args(argv)

    from kubeflow_tpu.utils import select_device

    select_device(args.device)

    from kubeflow_tpu.models import (GraniteHybridConfig, GraniteHybridLM,
                                    causal_lm_eval_metrics, causal_lm_loss)
    from kubeflow_tpu.train import Trainer, TrainerConfig
    from kubeflow_tpu.train.data import synthetic_lm_dataset

    cfg = GraniteHybridConfig.tiny(attention=args.attention, remat=args.remat)
    ds = synthetic_lm_dataset(
        n_train=args.batch_size * 8, n_test=args.batch_size * 2,
        seq_len=args.seq_len, vocab_size=cfg.vocab_size,
    )
    trainer = Trainer(
        GraniteHybridLM(cfg),
        TrainerConfig(
            batch_size=args.batch_size, steps=args.steps,
            learning_rate=args.lr, warmup_steps=min(100, args.steps // 10),
            checkpoint_dir=args.checkpoint_dir, log_every_steps=5,
        ),
        loss_fn=causal_lm_loss,
        eval_metrics_fn=causal_lm_eval_metrics,
    )
    _, metrics = trainer.fit(ds)
    return metrics.get("final_loss", float("inf"))


if __name__ == "__main__":
    main()
