"""Per-layer metrics of latent attention (MLA) in training, read as `train_parts.py` and
`train_moe.py` read theirs: the attention kernels' shares of their roofline over the
KERNELS' OWN device time (the forward's `flash_fwd_*` `pallas_call` events, the time under
the backward's `flash_bwd_xla_*` scope: not the `attn_core_*_ms.train` parts, which carry
the rotation, the concatenations and the copies around them), the device time of the latent
path (the scopes `mla.kv_down`, `mla.kv_norm`, `mla.kv_up`), and the routers' balance loss
(the step counter `moe_balance_loss`). A configuration without `kv_lora_rank`, a program
without the scopes or a kind without the counter reads nothing here."""

from __future__ import annotations

import re

from benchmarks import flops_mla, trace_names
from benchmarks.layer_metrics import train_parts

#: the forward kernel's own events, and whatever runs under the XLA backward's scope
FLASH_FWD = re.compile(r"/flash_fwd_[^/]*/pallas_call")
FLASH_BWD = re.compile(r"/flash_bwd_xla_[^/]*(?:/|$)")
LATENT = re.compile(r"/layer_\d+/(?:[^/]*/)*mla\.kv_(?:down|norm|up)(?:/|$)")
FWD, RECOMPUTED, BWD, LATENT_PATH = "fwd", "fwd_in_bwd", "bwd", "latent"


def kind_of(op_name: str | None) -> str | None:
    """Which of this file's times an operation's `op_name` belongs to, or None."""
    if not op_name or not train_parts.STEP_PROGRAM.search(op_name):
        return None
    if FLASH_FWD.search(op_name):
        return RECOMPUTED if train_parts.BACKWARD.search(op_name) else FWD
    if FLASH_BWD.search(op_name):
        return BWD
    return LATENT_PATH if LATENT.search(op_name) else None


def kernel_times(ctx) -> dict | None:
    """A whole step's device milliseconds by `kind_of`, and `recomputed`: the forward
    kernel's events a step under `transpose(` (a forward the backward pass ran again).
    None where the trace holds no whole step or none of these operations."""
    if "_mla_kernel_times" not in ctx:
        whole = train_parts._whole_steps(ctx)
        out = None
        if whole:
            t0, t1, steps = whole
            names = trace_names.of_run(ctx)
            kinds = {name: kind_of(op_name) for name, op_name in names.items()}
            dev = ctx["events"]["devices"][min(ctx["events"]["devices"])]
            total = train_parts.exclusive_ns(dev["ops"], t0, t1, kinds.get)
            total.pop(None, None)
            if total:
                out = {kind: ns / steps / 1e6 for kind, ns in total.items()}
                out["recomputed"] = sum(
                    1 for e in dev["ops"] if t0 <= e["start_ns"] < t1
                    and kinds.get(e["name"]) == RECOMPUTED) / steps
        ctx["_mla_kernel_times"] = out
    return ctx["_mla_kernel_times"]


def _shape(cfg: dict, mix: dict) -> tuple[int, int, int, int]:
    return (mix["batch"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def _roofline_share(backward: bool):
    def reader(ctx):
        """The least time the chip could take for the step's attention kernels over the
        visible pairs (`flops_mla.py`: the larger of FLOP over the bf16 peak and bytes
        over the memory's peak, every layer) over the kernels' own device time. The
        backward's count and time take in a forward kernel only where the trace shows
        one under `transpose(`."""
        cfg, mix = ctx["config"], ctx["traffic"]
        times = kernel_times(ctx) if "kv_lora_rank" in cfg else None
        if not times or not times.get(BWD if backward else FWD):
            return None
        shape, layers = _shape(cfg, mix), cfg["num_hidden_layers"]
        pairs = flops_mla.visible_pairs(mix["seq_len"])
        fwd = (flops_mla.attention_fwd_flop(*shape, pairs),
               flops_mla.attention_fwd_bytes(*shape, mix["seq_len"]))
        if backward:
            again = times["recomputed"]
            flop = layers * flops_mla.attention_bwd_flop(*shape, pairs) + again * fwd[0]
            moved = layers * flops_mla.attention_bwd_bytes(*shape, mix["seq_len"]) + again * fwd[1]
            ms = times[BWD] + times.get(RECOMPUTED, 0.0)
        else:
            flop, moved, ms = layers * fwd[0], layers * fwd[1], times[FWD]
        least_s = max(flop / ctx["peaks"]["flops_per_s_bf16"], moved / ctx["peaks"]["hbm_bytes_per_s"])
        return 100.0 * least_s / (ms / 1e3)
    return reader


def mla_latent_ms(ctx):
    """Device milliseconds a whole step spends under `mla.kv_down`, `mla.kv_norm` and
    `mla.kv_up`, forward, recomputed and backward."""
    times = kernel_times(ctx)
    return times.get(LATENT_PATH) if times else None


def moe_balance_loss(ctx):
    """The window's mean of the step counter `moe_balance_loss`: the sown balance loss,
    summed over the expert layers."""
    return ctx["facts"].get("step_counters", {}).get("moe_balance_loss")


METRICS = {
    "mla_attn_fwd_roofline_share.train": _roofline_share(False),
    "mla_attn_bwd_roofline_share.train": _roofline_share(True),
    "mla_latent_ms.train": mla_latent_ms,
    "moe_balance_loss.train": moe_balance_loss,
}
