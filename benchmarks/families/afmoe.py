"""AFMoE (`model_type: afmoe`, Arcee's Trinity family) between the benchmark and the
program: how a `configs/*.json` of this family becomes the program's `AfmoeLM`, how the
program's parameter tree becomes the flat dict of `reference_afmoe.py`, and what the
family's shapes cost."""

from __future__ import annotations

import jax

from benchmarks import flops_moe, reference_afmoe
from benchmarks.families.common import f32


def experts_held(cfg: dict) -> tuple[int, int]:
    lo, hi = cfg.get("experts_held", (0, cfg["num_experts"]))
    return int(lo), int(hi)


def _program_config(cfg: dict, **over):
    from kubeflow_tpu.models.afmoe import AfmoeConfig

    return AfmoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        mlp_dim=cfg["intermediate_size"], num_dense_layers=cfg["num_dense_layers"],
        layer_types=tuple(cfg["layer_types"]), sliding_window=cfg["sliding_window"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        num_experts=cfg["router_width"], experts_held=experts_held(cfg),
        top_k=cfg["num_experts_per_tok"], expert_dim=cfg["moe_intermediate_size"],
        num_shared_experts=cfg["num_shared_experts"], route_scale=cfg["route_scale"],
        bias_update_rate=cfg["load_balance_coeff"], scale_embedding=cfg["mup_enabled"], **over)


def train_model(cfg: dict, mix: dict) -> dict:
    from kubeflow_tpu.models.afmoe import AfmoeLM
    from kubeflow_tpu.models.gpt import causal_lm_eval_metrics, causal_lm_loss

    if mix["task"] != "causal_lm":
        raise ValueError(f"family afmoe trains causal_lm, not {mix['task']!r}")
    over = {"attention": mix["attention"], "remat": bool(mix.get("remat", False))}
    return {"module": AfmoeLM(_program_config(cfg, **over)),
            "loss_fn": causal_lm_loss, "eval_metrics_fn": causal_lm_eval_metrics}


def reference_spec(cfg: dict) -> dict:
    """What `reference_afmoe` needs of the configuration beside the weights."""
    return {"layer_types": tuple(cfg["layer_types"]), "num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
            "window": cfg["sliding_window"], "theta": float(cfg["rope_theta"]),
            "eps": cfg["rms_norm_eps"], "top_k": cfg["num_experts_per_tok"],
            "route_scale": cfg["route_scale"], "experts_held": experts_held(cfg)}


def reference_params(params, router_state=None) -> dict:
    """The program's flax tree as `reference_afmoe` wants it, in float32. Nothing is
    reshaped or copied here: the float32 weights stay the training state's own buffers.
    `router_state` is the program's collection of that name, for its selection bias;
    without it the bias is 0, as it is before the first step."""
    layers = []
    while f"layer_{len(layers)}" in params:
        name = f"layer_{len(layers)}"
        b = params[name]
        a = b["attention"]
        layer = {"g1": f32(b["ln_attn"]["scale"]), "g2": f32(b["ln_attn_post"]["scale"]),
                 "g3": f32(b["ln_mlp"]["scale"]), "g4": f32(b["ln_mlp_post"]["scale"]),
                 "wq": f32(a["query"]["kernel"]), "wk": f32(a["key"]["kernel"]),
                 "wv": f32(a["value"]["kernel"]), "wo": f32(a["attn_out"]["kernel"]),
                 "wz": f32(b["attn_gate"]["kernel"]),
                 "gq": f32(a["q_norm"]["scale"]), "gk": f32(a["k_norm"]["scale"])}
        if "moe" in b:
            m = b["moe"]
            layer.update(router=f32(m["router"]), w_gate=f32(m["w_gate"]), w_up=f32(m["w_up"]),
                         w_down=f32(m["w_down"]), shared_gate=f32(m["shared_gate"]["kernel"]),
                         shared_up=f32(m["shared_up"]["kernel"]),
                         shared_down=f32(m["shared_down"]["kernel"]))
            if router_state is not None:
                layer["bias"] = f32(router_state[name]["moe"]["bias"])
        else:
            layer.update(w_gate=f32(b["mlp_gate"]["kernel"]), w_up=f32(b["mlp_up"]["kernel"]),
                         w_down=f32(b["mlp_down"]["kernel"]))
        layers.append(layer)
    return {"emb": f32(params["token_embed"]["embedding"]), "layers": layers,
            "gf": f32(params["ln_final"]["scale"]), "head": f32(params["lm_head"]["kernel"])}


def reference_loss_fn(cfg: dict, mix: dict):
    """jitted (reference params, x, y) -> (summed loss, summed weight) of the rows given."""
    spec = reference_spec(cfg)
    return jax.jit(lambda p, x, y: reference_afmoe.causal_lm_loss_sums(p, x, y, spec))


def reference_state(state) -> dict:
    """The program's `TrainState` as `reference_afmoe.first_update` takes it and gives it
    back: `reference_params` with every expert layer's selection bias."""
    from kubeflow_tpu.parallel.moe import ROUTER_STATE

    return reference_params(state.params, state.extra[ROUTER_STATE])


def reference_update_fn(cfg: dict, mix: dict):
    """jitted (reference state, x, y) -> (summed loss, summed weight, the reference state
    after the first step of training as the mix and the configuration state it: Adam at
    the mix's `learning_rate`, no warm-up, and the router's bias rule)."""
    if int(mix["warmup_steps"]):
        raise ValueError("the reference's first step takes the whole learning rate: no warm-up")
    spec = reference_spec(cfg)
    lr, rate = float(mix["learning_rate"]), float(cfg["load_balance_coeff"])
    return jax.jit(lambda p, x, y: reference_afmoe.first_update(p, x, y, spec, lr, rate))


def visible_pairs_by_layer(cfg: dict, seq_len: int) -> list[int]:
    return [flops_moe.visible_pairs(
        seq_len, cfg["sliding_window"] if kind == reference_afmoe.SLIDING else 0)
        for kind in cfg["layer_types"]]


def train_flop_per_token(cfg: dict, mix: dict, step_counters: dict | None = None) -> int:
    """6 a matrix weight a token and, a layer, 12 x heads x head size x the visible keys a
    token: causal and window masks taken off, unlike GPT-2's convention, which counts the
    whole square. The routed experts count at the balanced load (one held expert a token),
    or, where the run's `step_counters` say how many rows the held experts computed a step
    (`moe_rows_here`, all expert layers), at those rows."""
    seq_len = int(mix["seq_len"])
    pairs = sum(visible_pairs_by_layer(cfg, seq_len))
    attention = 12 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs
    if attention % seq_len:
        raise ValueError(f"{pairs} visible pairs are no whole number a token of {seq_len}")
    weights = flops_moe.matmul_params_per_token(cfg)
    if step_counters and "moe_rows_here" in step_counters:
        layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
        expert = flops_moe.swiglu_params(cfg["hidden_size"], cfg["moe_intermediate_size"])
        balanced = layers * flops_moe.grouped_rows(int(mix["batch"]) * seq_len, cfg)
        extra = step_counters["moe_rows_here"] - balanced  # rows a step, over all layers
        weights += round(extra * expert / (int(mix["batch"]) * seq_len))
    return 6 * weights + attention // seq_len
