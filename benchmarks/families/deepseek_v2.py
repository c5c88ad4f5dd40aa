"""DeepSeek-V2 (`model_type: deepseek_v2`) between the benchmark and the program: how a
`configs/*.json` of this family becomes the program's `DeepseekV2LM`, how the program's
parameter tree becomes the flat dict of `reference_deepseek_v2.py`, and what the family's
shapes cost. The loss both sides report carries the routers' balance term."""

from __future__ import annotations

import jax

from benchmarks import flops_mla, flops_moe, reference_deepseek_v2
from benchmarks.families.afmoe import experts_held
from benchmarks.families.common import f32


def _program_config(cfg: dict, mix: dict):
    from kubeflow_tpu.models.deepseek_v2 import DeepseekV2Config

    yarn = cfg["rope_scaling"]
    form = {"q_lora_rank": None, "scoring_func": "softmax", "topk_method": "greedy",
            "n_group": 1, "topk_group": 1, "seq_aux": True, "moe_layer_freq": 1}
    other = {k: cfg[k] for k, v in form.items() if cfg[k] != v}
    if other or yarn["type"] != "yarn":
        raise ValueError(f"the program's deepseek_v2 block has {form} and YaRN; the file says "
                         f"{other}, rope_scaling {yarn['type']!r}")
    return DeepseekV2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"], num_heads=cfg["num_attention_heads"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"], qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], kv_lora_rank=cfg["kv_lora_rank"],
        mlp_dim=cfg["intermediate_size"], num_dense_layers=cfg["first_k_dense_replace"],
        num_experts=cfg["router_width"], experts_held=experts_held(cfg),
        top_k=cfg["num_experts_per_tok"], expert_dim=cfg["moe_intermediate_size"],
        num_shared_experts=cfg["n_shared_experts"], route_scale=float(cfg["routed_scaling_factor"]),
        renormalise=bool(cfg["norm_topk_prob"]), balance_loss=float(cfg["aux_loss_alpha"]),
        norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        rope_factor=float(yarn["factor"]),
        rope_original_max_position=yarn["original_max_position_embeddings"],
        rope_beta_fast=float(yarn["beta_fast"]), rope_beta_slow=float(yarn["beta_slow"]),
        rope_mscale=float(yarn["mscale"]), rope_mscale_all_dim=float(yarn["mscale_all_dim"]),
        attention=mix["attention"], remat=bool(mix.get("remat", False)))


def train_model(cfg: dict, mix: dict) -> dict:
    from kubeflow_tpu.models.deepseek_v2 import DeepseekV2LM
    from kubeflow_tpu.models.gpt import causal_lm_eval_metrics, causal_lm_loss

    if mix["task"] != "causal_lm":
        raise ValueError(f"family deepseek_v2 trains causal_lm, not {mix['task']!r}")
    return {"module": DeepseekV2LM(_program_config(cfg, mix)),
            "loss_fn": causal_lm_loss, "eval_metrics_fn": causal_lm_eval_metrics}


def reference_spec(cfg: dict) -> dict:
    """What `reference_deepseek_v2` needs of the configuration beside the weights."""
    return {"num_heads": cfg["num_attention_heads"], "nope": cfg["qk_nope_head_dim"],
            "rope": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"], "rank": cfg["kv_lora_rank"],
            "theta": float(cfg["rope_theta"]), "yarn": dict(cfg["rope_scaling"]),
            "eps": cfg["rms_norm_eps"], "top_k": cfg["num_experts_per_tok"],
            "route_scale": float(cfg["routed_scaling_factor"]),
            "experts_held": experts_held(cfg), "balance_loss": float(cfg["aux_loss_alpha"])}


def reference_params(params) -> dict:
    """The program's flax tree as `reference_deepseek_v2` wants it, in float32. Nothing is
    reshaped or copied here: the float32 weights stay the training state's own buffers."""
    layers = []
    while f"layer_{len(layers)}" in params:
        b = params[f"layer_{len(layers)}"]
        a, c = b["attention"], b["kv_latent"]
        layer = {"g1": f32(b["ln_attn"]["scale"]), "g2": f32(b["ln_mlp"]["scale"]),
                 "wq": f32(a["query"]["kernel"]), "wo": f32(a["attn_out"]["kernel"]),
                 "wdkv": f32(c["down"]["kernel"]), "gc": f32(c["norm"]["scale"]),
                 "wukv": f32(c["up"]["kernel"])}
        if "moe" in b:
            m = b["moe"]
            layer.update(router=f32(m["router"]), w_gate=f32(m["w_gate"]), w_up=f32(m["w_up"]),
                         w_down=f32(m["w_down"]), shared_gate=f32(m["shared_gate"]["kernel"]),
                         shared_up=f32(m["shared_up"]["kernel"]),
                         shared_down=f32(m["shared_down"]["kernel"]))
        else:
            layer.update(w_gate=f32(b["mlp_gate"]["kernel"]), w_up=f32(b["mlp_up"]["kernel"]),
                         w_down=f32(b["mlp_down"]["kernel"]))
        layers.append(layer)
    return {"emb": f32(params["token_embed"]["embedding"]), "layers": layers,
            "gf": f32(params["ln_final"]["scale"]), "head": f32(params["lm_head"]["kernel"])}


def reference_loss_fn(cfg: dict, mix: dict):
    """jitted (reference params, x, y) -> (total, weight) of the rows given: their quotient
    is the mean cross entropy plus the balance term, the loss the step reports."""
    spec = reference_spec(cfg)
    return jax.jit(lambda p, x, y: reference_deepseek_v2.causal_lm_loss_sums(p, x, y, spec))


def reference_state(state) -> dict:
    """The program's `TrainState` as `reference_deepseek_v2.first_update` takes it and
    gives it back: the parameters (the routers keep no state a step moves)."""
    return reference_params(state.params)


def reference_update_fn(cfg: dict, mix: dict):
    """jitted (reference state, x, y) -> (total, weight, the reference state after the
    first step of training as the mix states it: Adam at its `learning_rate`, no warm-up)."""
    if int(mix["warmup_steps"]):
        raise ValueError("the reference's first step takes the whole learning rate: no warm-up")
    spec, lr = reference_spec(cfg), float(mix["learning_rate"])
    return jax.jit(lambda p, x, y: reference_deepseek_v2.first_update(p, x, y, spec, lr))


def train_flop_per_token(cfg: dict, mix: dict, step_counters: dict | None = None) -> int:
    """6 a matrix weight a token and, a layer, the attention core over the VISIBLE pairs a
    token (the causal mask taken off, `trinity-mini`'s scale): forward `2 qk + 2 v`,
    backward `2 (3 qk + 2 v)` FLOP a pair and head (`flops_mla.py`), and no recomputed
    forward. The routed experts count at the balanced load, or, where the run's
    `step_counters` say how many rows the held experts computed a step (`moe_rows_here`,
    all expert layers), at those rows."""
    seq_len, tokens = int(mix["seq_len"]), int(mix["batch"]) * int(mix["seq_len"])
    shape = (1, cfg["num_attention_heads"], cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
             cfg["v_head_dim"], flops_mla.visible_pairs(seq_len))
    attention = cfg["num_hidden_layers"] * (
        flops_mla.attention_fwd_flop(*shape) + flops_mla.attention_bwd_flop(*shape))
    if attention % seq_len:
        raise ValueError(f"{attention} attention FLOP a row are no whole number a token of {seq_len}")
    weights = flops_mla.matmul_params_per_token(cfg)
    if step_counters and "moe_rows_here" in step_counters:
        layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
        expert = flops_moe.swiglu_params(cfg["hidden_size"], cfg["moe_intermediate_size"])
        balanced = layers * flops_moe.grouped_rows(tokens, cfg)
        weights += round((step_counters["moe_rows_here"] - balanced) * expert / tokens)
    return 6 * weights + attention // seq_len
