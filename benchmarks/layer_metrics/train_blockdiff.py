"""Per-layer metrics of training by diffusion over blocks, read as `train_parts.py` and
`train_moe.py` read theirs: the attention cores' share of their roofline under the
block-diffusion mask, the device time of the step's corruption (the scope
`train.corrupt` of `Trainer._train_step`), and the share of positions it masked (the
step counter `diffusion_masked_share`). A mix without `block_length`, a program without
the scope or a kind without the counter reads nothing here."""

from __future__ import annotations

import re

from benchmarks import flops_blockdiff, trace_names
from benchmarks.layer_metrics import train_parts

CORRUPT = re.compile(r"^jit\(_train_step\)/(?:[^/]*/)*train\.corrupt(?:/|$)")


def _attention_roofline_share(part: str):
    def reader(ctx):
        """The least time the chip could take for the step's attention cores over the
        visible pairs (`flops_blockdiff.py`: the larger of FLOP over the bf16 peak and
        bytes over the memory's peak, every layer) over the part's time. Under `remat`
        the recomputed forward is timed with the backward, and counted with it."""
        cfg, mix = ctx["config"], ctx["traffic"]
        times = train_parts.part_times_ms(ctx)
        if not times or not times[part] or "block_length" not in mix:
            return None
        backward, remat = part == "attn_core_bwd", bool(mix.get("remat"))
        shape = (mix["batch"], cfg["num_attention_heads"], cfg["head_dim"], mix["seq_len"])
        flop = flops_blockdiff.attention_flop(*shape, mix["block_length"], backward, remat)
        moved = flops_blockdiff.attention_bytes(*shape, backward, remat)
        least_s = cfg["num_hidden_layers"] * max(
            flop / ctx["peaks"]["flops_per_s_bf16"], moved / ctx["peaks"]["hbm_bytes_per_s"])
        return 100.0 * least_s / (times[part] / 1e3)
    return reader


def corrupt_ms(ctx):
    """Device milliseconds a whole step spends under the scope `train.corrupt`."""
    whole = train_parts._whole_steps(ctx)
    if not whole:
        return None
    t0, t1, steps = whole
    names = trace_names.of_run(ctx)
    dev = ctx["events"]["devices"][min(ctx["events"]["devices"])]
    total = train_parts.exclusive_ns(
        dev["ops"], t0, t1, lambda name: bool(CORRUPT.search(names.get(name) or "")))
    return total[True] / steps / 1e6 if total.get(True) else None


def masked_share(ctx):
    """The window's mean share of a step's positions that the corruption masked, in %."""
    share = ctx["facts"].get("step_counters", {}).get("diffusion_masked_share")
    return None if share is None else 100.0 * share


METRICS = {
    "blockdiff_attn_fwd_roofline_share.train": _attention_roofline_share("attn_core_fwd"),
    "blockdiff_attn_bwd_roofline_share.train": _attention_roofline_share("attn_core_bwd"),
    "corrupt_ms.train": corrupt_ms,
    "masked_share.train": masked_share,
}
