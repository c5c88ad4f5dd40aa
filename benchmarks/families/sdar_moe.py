"""SDAR-MoE (`model_type: sdar_moe`) between the benchmark and the program: how a
`configs/*.json` of this family becomes the program's `SdarMoeLM`, how the program's
parameter tree and its step's noise become what `reference_sdar.py` takes, and what the
family's shapes cost. The traffic is the causal-LM generator's rows of real tokens; the
mix adds `block_length` and `mask_rate_min`, which the model's `corrupt` draws by."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks import flops_blockdiff, flops_moe, reference_sdar
from benchmarks.families.afmoe import experts_held
from benchmarks.families.common import f32

#: the key the reference's state carries the training state's rng under: the first
#: step's noise is drawn from it, by the program's own `corrupt`
RNG = "rng"


def _program_config(cfg: dict, mix: dict):
    from kubeflow_tpu.models.sdar_moe import SdarMoeConfig

    return SdarMoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        num_experts=cfg["router_width"], experts_held=experts_held(cfg),
        top_k=cfg["num_experts_per_tok"], expert_dim=cfg["moe_intermediate_size"],
        block_length=int(mix["block_length"]), mask_rate_min=float(mix["mask_rate_min"]),
        attention=mix["attention"], remat=bool(mix.get("remat", False)))


def train_model(cfg: dict, mix: dict) -> dict:
    from kubeflow_tpu.models.sdar_moe import SdarMoeLM, sdar_eval_metrics, sdar_loss

    if mix["task"] != "causal_lm":
        raise ValueError(f"family sdar_moe trains on causal_lm rows, not {mix['task']!r}")
    return {"module": SdarMoeLM(_program_config(cfg, mix)),
            "loss_fn": sdar_loss, "eval_metrics_fn": sdar_eval_metrics}


def reference_spec(cfg: dict, mix: dict) -> dict:
    """What `reference_sdar` needs of the configuration and the mix beside the weights."""
    return {"num_heads": cfg["num_attention_heads"], "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "theta": float(cfg["rope_theta"]),
            "eps": cfg["rms_norm_eps"], "top_k": cfg["num_experts_per_tok"],
            "experts_held": experts_held(cfg), "block_length": int(mix["block_length"]),
            "mask_id": cfg["vocab_size"] - 1}


def reference_params(params) -> dict:
    """The program's flax tree as `reference_sdar` wants it, in float32. Nothing is
    reshaped or copied here: the float32 weights stay the training state's own buffers."""
    layers = []
    while f"layer_{len(layers)}" in params:
        b = params[f"layer_{len(layers)}"]
        a, m = b["attention"], b["moe"]
        layers.append({
            "g1": f32(b["ln_attn"]["scale"]), "g2": f32(b["ln_mlp"]["scale"]),
            "wq": f32(a["query"]["kernel"]), "wk": f32(a["key"]["kernel"]),
            "wv": f32(a["value"]["kernel"]), "wo": f32(a["attn_out"]["kernel"]),
            "gq": f32(a["q_norm"]["scale"]), "gk": f32(a["k_norm"]["scale"]),
            "router": f32(m["router"]), "w_gate": f32(m["w_gate"]), "w_up": f32(m["w_up"]),
            "w_down": f32(m["w_down"])})
    return {"emb": f32(params["token_embed"]["embedding"]), "layers": layers,
            "gf": f32(params["ln_final"]["scale"]), "head": f32(params["lm_head"]["kernel"])}


def reference_state(state) -> dict:
    """The program's `TrainState` as `reference_update_fn`'s function takes it and gives it
    back: `reference_params`, and the state's rng (which no step changes)."""
    return {**reference_params(state.params), RNG: state.rng}


def first_step_noise(cfg: dict, mix: dict, rng, x, y):
    """(masked, weights), each (B, L): what the program's `corrupt` draws at step 0 of a
    state whose rng is `rng`, as `Trainer._train_step` calls it. The noise is data, like
    the weights: the reference takes the program's draw, and a CPU test holds `corrupt`
    to its law."""
    with jax.threefry_partitionable(True):  # as the Trainer traces its step
        _, noise = train_model(cfg, mix)["module"].corrupt(
            jax.random.fold_in(rng, 0), jnp.asarray(x), jnp.asarray(y))
    return noise["masked"], noise["weights"]


def reference_update_fn(cfg: dict, mix: dict):
    """jitted (reference state, x, y) -> (summed loss, its divisor, the reference state
    after the first step of training as the mix states it: the step's noise as the
    program draws it, the float32 gradient, one step of Adam at the mix's
    `learning_rate`, no warm-up)."""
    if int(mix["warmup_steps"]):
        raise ValueError("the reference's first step takes the whole learning rate: no warm-up")
    spec, lr = reference_spec(cfg, mix), float(mix["learning_rate"])

    def update(state, x, y):
        params = {k: v for k, v in state.items() if k != RNG}
        masked, weights = first_step_noise(cfg, mix, state[RNG], x, y)
        total, weight, after = reference_sdar.first_update(
            params, jnp.asarray(x), masked, weights, spec, lr)
        return total, weight, {**after, RNG: state[RNG]}

    return jax.jit(update)


def matmul_params_per_position(cfg: dict) -> int:
    """Matrix weights one POSITION multiplies in a forward pass, the head apart: the four
    attention projections, the router, and the routed experts this share computes for it
    at the balanced load (`num_experts_per_tok` x experts held / the router's width)."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    attention = h * d * 2 * (cfg["num_attention_heads"] + cfg["num_key_value_heads"])
    expert = flops_moe.swiglu_params(h, cfg["moe_intermediate_size"])
    routed = cfg["num_experts_per_tok"] * cfg["num_experts"] * expert // cfg["router_width"]
    return cfg["num_hidden_layers"] * (attention + h * cfg["router_width"] + routed)


def train_flop_per_token(cfg: dict, mix: dict, step_counters: dict | None = None) -> int:
    """A DATA token (the unit `train_tokens_per_s` counts) costs two positions, its clean
    and its noisy copy, through every matrix but the head, and the head once: 6 a matrix
    weight; and, a layer, 12 x heads x head size x the visible pairs a token (`L + B`: the
    mask taken off). The routed experts count at the balanced load, or, where the run's
    `step_counters` say how many rows the held experts computed a step (`moe_rows_here`,
    all layers), at those rows. Recomputed work counts for nothing."""
    seq_len, tokens = int(mix["seq_len"]), int(mix["batch"]) * int(mix["seq_len"])
    pairs = flops_blockdiff.visible_pairs(seq_len, int(mix["block_length"]))
    attention = 12 * cfg["num_attention_heads"] * cfg["head_dim"] * cfg["num_hidden_layers"] * pairs
    weights = 2 * matmul_params_per_position(cfg) + cfg["hidden_size"] * cfg["vocab_size"]
    if step_counters and "moe_rows_here" in step_counters:
        expert = flops_moe.swiglu_params(cfg["hidden_size"], cfg["moe_intermediate_size"])
        balanced = cfg["num_hidden_layers"] * flops_moe.grouped_rows(2 * tokens, cfg)
        weights += round((step_counters["moe_rows_here"] - balanced) * expert / tokens)
    return 6 * weights + attention // seq_len
