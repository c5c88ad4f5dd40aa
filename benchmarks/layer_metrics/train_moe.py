"""Per-layer metrics of an expert layer (`parallel.moe`) and of attention whose layers
differ, read from the device trace of a `train_job` run by the names the program gave its
operations, as `train_parts.py` reads the five parts of a step.

An expert layer is the module `layer_N/moe`; inside it the program scopes `moe.route`
(router product, sigmoid, top-k, weights), `moe.dispatch` (sort, gather), `moe.experts`
(the grouped products), `moe.combine` (scatter-add) and `moe.shared`. Times are summed
over the whole steps of the traced window and divided by their number, each instant
charged to the operation that started last (`train_parts.exclusive_ns`). A program
without such a module, as every configuration before this family, reads nothing here."""

from __future__ import annotations

import re

from benchmarks import flops_moe, trace_names
from benchmarks.layer_metrics import train_parts

#: an operation under an expert layer, and the scope inside it where the name carries one
MOE = re.compile(r"/layer_\d+/moe/(?:(moe\.(?:route|dispatch|experts|combine|shared))(?:/|$))?")
OTHER = "moe.other"
#: the scopes whose work is matrix products; the rest is bound by memory and latency
PRODUCTS = ("moe.experts", "moe.shared")


def scope_of(op_name: str | None) -> str | None:
    """The expert layer's scope an operation's `op_name` puts it in, OTHER for one under
    the layer and under no scope, None for one outside any expert layer."""
    found = MOE.search(op_name) if op_name and train_parts.STEP_PROGRAM.search(op_name) else None
    return (found.group(1) or OTHER) if found else None


def scope_times_ms(ctx) -> dict[str, float] | None:
    """Device milliseconds a whole step spends in each scope of the expert layers, all
    layers together; None where the trace holds no whole step or no expert layer."""
    if "_moe_scope_times_ms" not in ctx:
        whole = train_parts._whole_steps(ctx)
        out = None
        if whole:
            t0, t1, steps = whole
            names = trace_names.of_run(ctx)
            dev = ctx["events"]["devices"][min(ctx["events"]["devices"])]
            total = train_parts.exclusive_ns(dev["ops"], t0, t1, lambda name: scope_of(names.get(name)))
            total.pop(None, None)
            out = {scope: ns / steps / 1e6 for scope, ns in total.items()} or None
        ctx["_moe_scope_times_ms"] = out
    return ctx["_moe_scope_times_ms"]


def moe_ms(ctx):
    times = scope_times_ms(ctx)
    return sum(times.values()) if times else None


def moe_route_ms(ctx):
    """All of the expert layers' time but the grouped products and the shared expert."""
    times = scope_times_ms(ctx)
    return sum(ms for scope, ms in times.items() if scope not in PRODUCTS) if times else None


def _passes(mix: dict) -> int:
    """Forward passes' worth of work a step: forward 1, backward 2, and the forward
    again where the mix recomputes each block in the backward pass."""
    return 4 if mix.get("remat") else 3


def expert_mm_roofline_share(ctx):
    """The least time the chip could take for the step's grouped products (the larger of
    FLOP over the bf16 peak and bytes over the memory's peak; forward, recomputed forward
    and backward of every expert layer, `flops_moe.py`) over the time under `moe.experts`.
    The rows are the run's own where its kind hands on the step's counters
    (`moe_rows_here`, the mean a step over the window, all layers), else the BALANCED
    load's."""
    times = scope_times_ms(ctx)
    cfg, mix = ctx["config"], ctx["traffic"]
    if not times or not times.get("moe.experts") or "moe_intermediate_size" not in cfg:
        return None
    layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    rows = ctx["facts"].get("step_counters", {}).get(
        "moe_rows_here", layers * flops_moe.grouped_rows(mix["batch"] * mix["seq_len"], cfg)) / layers
    h, m = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flop = layers * flops_moe.expert_products_flop(rows, h, m, _passes(mix))
    moved = layers * flops_moe.expert_products_bytes(rows, cfg["num_experts"], h, m, _passes(mix))
    least_s = max(flop / ctx["peaks"]["flops_per_s_bf16"], moved / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (times["moe.experts"] / 1e3)


def _attention_share(part: str):
    def reader(ctx):
        """FLOP of the attention cores over the VISIBLE pairs of each layer's kind (causal
        and window masks taken off) over the part's time, over the bf16 peak. Under
        `remat` the recomputed forward is timed with the backward (its names carry
        `transpose(`), so the backward's count adds the forward's."""
        from benchmarks.families import afmoe

        cfg, mix = ctx["config"], ctx["traffic"]
        times = train_parts.part_times_ms(ctx)
        if not times or not times[part] or "layer_types" not in cfg:
            return None
        pairs = sum(afmoe.visible_pairs_by_layer(cfg, mix["seq_len"]))
        shape = (mix["batch"], cfg["num_attention_heads"], cfg["head_dim"], pairs)
        fwd = flops_moe.attention_fwd_flop(*shape)
        flop = fwd if part == "attn_core_fwd" else (
            flops_moe.attention_bwd_flop(*shape) + (fwd if mix.get("remat") else 0))
        return 100.0 * flop / (times[part] / 1e3) / ctx["peaks"]["flops_per_s_bf16"]
    return reader


METRICS = {
    "moe_ms.train": moe_ms,
    "moe_route_ms.train": moe_route_ms,
    "expert_mm_roofline_share.train": expert_mm_roofline_share,
    "attn_fwd_visible_mxu_share.train": _attention_share("attn_core_fwd"),
    "attn_bwd_visible_mxu_share.train": _attention_share("attn_core_bwd"),
}
