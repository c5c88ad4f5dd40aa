"""GPT-2 between the benchmark and the program: how a `configs/*.json` of this family
becomes the program's `GPTLM`, how the program's parameter tree becomes the flat dict of
`reference.py`, and what the family's shapes cost."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks import flops, reference
from benchmarks.families.common import blocks, f32


def _program_config(cfg: dict, **over):
    from kubeflow_tpu.models.gpt import GPTConfig

    return GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"], num_layers=cfg["n_layer"],
        num_heads=cfg["n_head"], mlp_dim=cfg["n_inner"], max_len=cfg["n_positions"],
        norm_eps=cfg["layer_norm_epsilon"], dropout_rate=cfg["resid_pdrop"], **over)


def train_model(cfg: dict, mix: dict) -> dict:
    from kubeflow_tpu.models.gpt import GPTLM, causal_lm_eval_metrics, causal_lm_loss

    if mix["task"] != "causal_lm":
        raise ValueError(f"family gpt2 trains causal_lm, not {mix['task']!r}")
    return {"module": GPTLM(_program_config(cfg, attention=mix["attention"])),
            "loss_fn": causal_lm_loss, "eval_metrics_fn": causal_lm_eval_metrics}


def reference_params(params) -> dict:
    """The program's flax tree as `reference.gpt2_logits` wants it, in float32."""
    wte = f32(params["token_embed"]["embedding"])
    return {"wte": wte, "wpe": f32(params["position_embed"]["embedding"]),
            "blocks": blocks(params, wte.shape[1]),
            "lnf_g": f32(params["ln_final"]["scale"]), "lnf_b": f32(params["ln_final"]["bias"])}


def reference_loss_fn(cfg: dict, mix: dict):
    """jitted (reference params, x, y) -> (summed loss, summed weight) of the rows given."""
    def sums(p, x, y):
        logits = reference.gpt2_logits(p, x, cfg["n_head"], cfg["layer_norm_epsilon"])
        w = (y[:, 1:] != 0).sum().astype(jnp.float32)
        return reference.causal_lm_loss(logits, y) * w, w
    return jax.jit(sums)


def train_flop_per_token(cfg: dict, mix: dict) -> int:
    """Blocks and the tied head multiply; the two embedding lookups do not."""
    weights = (flops.block_matmul_params(cfg["n_layer"], cfg["n_embd"], cfg["n_inner"])
               + cfg["vocab_size"] * cfg["n_embd"])
    return flops.train_flop_per_token(weights, cfg["n_layer"], cfg["n_embd"], mix["seq_len"])
