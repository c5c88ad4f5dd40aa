"""BERT between the benchmark and the program: see `families/gpt2.py`."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks import flops, reference
from benchmarks.families.common import blocks, f32

#: the program computes GELU in its tanh form (`flax.linen.gelu`); the source's is erf
PROGRAM_GELU_APPROXIMATE = True


def train_model(cfg: dict, mix: dict) -> dict:
    from kubeflow_tpu.models import BertConfig, BertForSequenceClassification

    if mix["task"] != "classification":
        raise ValueError(f"family bert trains classification, not {mix['task']!r}")
    program = BertConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"], num_heads=cfg["num_attention_heads"],
        mlp_dim=cfg["intermediate_size"], max_len=cfg["max_position_embeddings"],
        dropout_rate=cfg["hidden_dropout_prob"], pad_token_id=cfg["pad_token_id"])
    from kubeflow_tpu.train.trainer import classification_eval_metrics, cross_entropy_loss

    return {"module": BertForSequenceClassification(program, num_classes=int(mix["num_classes"])),
            "loss_fn": cross_entropy_loss, "eval_metrics_fn": classification_eval_metrics}


def reference_params(params) -> dict:
    emb = params["encoder"]["embeddings"]
    wte = f32(emb["token_embed"]["embedding"])
    return {"wte": wte, "wpe": f32(emb["position_embed"]["embedding"]),
            "wtt": f32(emb["type_embed"]["embedding"]),
            "lne_g": f32(emb["ln_embed"]["scale"]), "lne_b": f32(emb["ln_embed"]["bias"]),
            "blocks": blocks(params["encoder"], wte.shape[1]),
            "w_pool": f32(params["pooler"]["kernel"]), "b_pool": f32(params["pooler"]["bias"]),
            "w_cls": f32(params["classifier"]["kernel"]), "b_cls": f32(params["classifier"]["bias"])}


def reference_loss_fn(cfg: dict, mix: dict):
    """jitted (reference params, x, y) -> (summed loss, rows) of the rows given."""
    def sums(p, x, y):
        logits = reference.bert_classifier_logits(
            p, x, cfg["num_attention_heads"], cfg["layer_norm_eps"], PROGRAM_GELU_APPROXIMATE)
        n = jnp.float32(x.shape[0])
        return reference.classification_loss(logits, y) * n, n
    return jax.jit(sums)


def train_flop_per_token(cfg: dict, mix: dict) -> int:
    """The encoder blocks multiply at every token. The embeddings are lookups, and the
    pooler and classifier see one position a row (0.1 % of a row's work): left out."""
    weights = flops.block_matmul_params(
        cfg["num_hidden_layers"], cfg["hidden_size"], cfg["intermediate_size"])
    return flops.train_flop_per_token(
        weights, cfg["num_hidden_layers"], cfg["hidden_size"], mix["seq_len"])
