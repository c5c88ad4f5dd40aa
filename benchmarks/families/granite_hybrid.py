"""Granite 4.0-H (`model_type: granitemoehybrid`, dense) between the benchmark and the
program: how a `configs/*.json` of this family becomes the program's `GraniteHybridLM`, how
the program's parameter tree becomes the flat dict of `reference_granite.py`, and what the
family's shapes cost."""

from __future__ import annotations

import jax
import numpy as np

from benchmarks import flops_mla, flops_ssm, reference_granite
from benchmarks.families.common import f32

#: the form the program builds; a file that states another is refused, not ignored
FORM = {"num_local_experts": 0, "position_embedding_type": "nope", "mamba_proj_bias": False,
        "mamba_conv_bias": True, "normalization_function": "rmsnorm", "hidden_act": "silu",
        "tie_word_embeddings": True, "attention_bias": False, "rope_scaling": None}


def _program_config(cfg: dict, mix: dict):
    from kubeflow_tpu.models.granite_hybrid import GraniteHybridConfig

    other = {k: cfg[k] for k, v in FORM.items() if cfg[k] != v}
    if other:
        raise ValueError(f"the program's granitemoehybrid block has {FORM}; the file says {other}")
    if cfg["mamba_expand"] * cfg["hidden_size"] != cfg["mamba_n_heads"] * cfg["mamba_d_head"]:
        raise ValueError("mamba_expand x hidden_size is not mamba_n_heads x mamba_d_head")
    return GraniteHybridConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"]), num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        mlp_dim=cfg["shared_intermediate_size"], mamba_heads=cfg["mamba_n_heads"],
        mamba_head_dim=cfg["mamba_d_head"], mamba_state=cfg["mamba_d_state"],
        mamba_groups=cfg["mamba_n_groups"], mamba_conv=cfg["mamba_d_conv"],
        mamba_chunk=cfg["mamba_chunk_size"], norm_eps=cfg["rms_norm_eps"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        attention=mix["attention"], remat=bool(mix.get("remat", False)))


def train_model(cfg: dict, mix: dict) -> dict:
    from kubeflow_tpu.models.granite_hybrid import GraniteHybridLM
    from kubeflow_tpu.models.gpt import causal_lm_eval_metrics, causal_lm_loss

    if mix["task"] != "causal_lm":
        raise ValueError(f"family granite_hybrid trains causal_lm, not {mix['task']!r}")
    return {"module": GraniteHybridLM(_program_config(cfg, mix)),
            "loss_fn": causal_lm_loss, "eval_metrics_fn": causal_lm_eval_metrics}


def reference_spec(cfg: dict) -> dict:
    """What `reference_granite` needs of the configuration beside the weights."""
    return {"mamba_heads": cfg["mamba_n_heads"], "mamba_head_dim": cfg["mamba_d_head"],
            "state": cfg["mamba_d_state"], "groups": cfg["mamba_n_groups"],
            "chunk": cfg["mamba_chunk_size"], "eps": cfg["rms_norm_eps"],
            "heads": cfg["num_attention_heads"], "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
            "attention_multiplier": float(cfg["attention_multiplier"]),
            "residual_multiplier": float(cfg["residual_multiplier"]),
            "embedding_multiplier": float(cfg["embedding_multiplier"]),
            "logits_scaling": float(cfg["logits_scaling"]), "carry_state": True}


def reference_params(params) -> dict:
    """The program's flax tree as `reference_granite` wants it, in float32. Nothing is
    reshaped or copied here: the float32 weights stay the training state's own buffers."""
    layers = []
    while f"layer_{len(layers)}" in params:
        b = params[f"layer_{len(layers)}"]
        layer = {"g1": f32(b["ln_mixer"]["scale"]), "g2": f32(b["ln_mlp"]["scale"]),
                 "w_gate": f32(b["mlp_gate"]["kernel"]), "w_up": f32(b["mlp_up"]["kernel"]),
                 "w_down": f32(b["mlp_down"]["kernel"])}
        if "mamba" in b:
            m = b["mamba"]
            layer.update(w_in=f32(m["in_proj"]["kernel"]), conv_w=f32(m["conv_weight"]),
                         conv_b=f32(m["conv_bias"]), dt_bias=f32(m["dt_bias"]),
                         a_log=f32(m["A_log"]), d=f32(m["D"]), g_m=f32(m["norm_gain"]),
                         w_o=f32(m["out_proj"]["kernel"]))
        else:
            a = b["attention"]
            layer.update(wq=f32(a["query"]["kernel"]), wk=f32(a["key"]["kernel"]),
                         wv=f32(a["value"]["kernel"]), wo=f32(a["attn_out"]["kernel"]))
        layers.append(layer)
    return {"emb": f32(params["token_embed"]["embedding"]), "layers": layers,
            "gf": f32(params["ln_final"]["scale"])}


def reference_loss_fn(cfg: dict, mix: dict):
    """jitted (reference params, x, y) -> (total, weight) of the rows given: their quotient
    is the mean cross entropy, the loss the step reports."""
    spec = reference_spec(cfg)
    return jax.jit(lambda p, x, y: reference_granite.causal_lm_loss_sums(p, x, y, spec))


def reference_state(state) -> dict:
    """The program's `TrainState` as `reference_granite.first_update` takes it and gives it
    back: the parameters (the mixers' counter is no state a step moves)."""
    return reference_params(state.params)


def reference_update_fn(cfg: dict, mix: dict):
    """(reference state, x, y) -> (total, weight, the reference state after the first step
    of training as the mix states it: Adam at its `learning_rate`, no warm-up). The
    gradient is one jitted call; Adam's step is taken on the host a leaf at a time, each
    leaf of the gradient let go as it is read, so the device never holds the gradient and
    the new state beside the training state (4.5 GB of the reference's temporaries and 3.1
    GB of its output at this configuration's size: more than the chip has left). The
    arithmetic is `reference_granite.first_update`'s."""
    if int(mix["warmup_steps"]):
        raise ValueError("the reference's first step takes the whole learning rate: no warm-up")
    spec, lr = reference_spec(cfg), float(mix["learning_rate"])
    gradient = jax.jit(lambda p, x, y: reference_granite.first_gradient(p, x, y, spec))

    def update(params, x, y):
        total, weight, grads = gradient(params, x, y)
        leaves, tree = jax.tree.flatten(grads)
        del grads
        after = []
        for i, p in enumerate(jax.tree.leaves(params)):
            g, leaves[i] = np.asarray(leaves[i]), None
            after.append(reference_granite.adam_first_step(np.asarray(p), g, lr))
        return total, weight, jax.tree.unflatten(tree, after)

    return update


def train_flop_per_token(cfg: dict, mix: dict, step_counters: dict | None = None) -> int:
    """6 a matrix weight a token (`flops_ssm.matmul_params_per_token`), the attention layers'
    cores over the VISIBLE pairs a token (the causal mask taken off: forward `2 d + 2 d`,
    backward `2 (3 d + 2 d)` FLOP a pair and head, `flops_mla.py`, no recomputed forward),
    and the Mamba-2 layers' scans, forward and backward (`flops_ssm.py`). The step's counters
    change nothing here: the work is fixed by the shapes."""
    seq_len = int(mix["seq_len"])
    kinds, d = cfg["layer_types"], cfg["hidden_size"] // cfg["num_attention_heads"]
    shape = (int(mix["batch"]), cfg["num_attention_heads"], d, d, flops_mla.visible_pairs(seq_len))
    attention = kinds.count("attention") * (
        flops_mla.attention_fwd_flop(*shape) + flops_mla.attention_bwd_flop(*shape))
    scan = flops_ssm.scan_shape(cfg, mix)
    scans = kinds.count("mamba") * (flops_ssm.scan_fwd_flop(*scan) + flops_ssm.scan_bwd_flop(*scan))
    rows = int(mix["batch"]) * seq_len
    if (attention + scans) % rows:
        raise ValueError(f"{attention + scans} FLOP a step are no whole number a token of {rows}")
    return 6 * flops_ssm.matmul_params_per_token(cfg) + (attention + scans) // rows
