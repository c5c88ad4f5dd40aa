"""Per-layer metrics of the Mamba-2 mixer (`parallel.ssm`) in training, read from the device
trace of a `train_job` run by the names the program gave its operations, as `train_moe.py`
reads the expert layer's.

A mixer is the module `layer_N/mamba`; inside it the program scopes `ssm.in_proj`,
`ssm.conv`, `ssm.scan`, `ssm.gate_norm` and `ssm.out_proj`. Of the scan's operations, those
outside the backward pass are its forward; under `transpose(` those under
`rematted_computation` are the forward that `remat` runs again, the rest its backward.
Times are summed over the whole steps of the traced window and divided by their number,
each instant charged to the operation that started last (`train_parts.exclusive_ns`). A
configuration without Mamba-2 keys, a program without the module, or a kind without the
counter `ssm_chunk_decay` reads nothing here."""

from __future__ import annotations

import re

from benchmarks import flops_ssm, trace_names
from benchmarks.layer_metrics import train_parts

#: an operation under a mixer, and under its scan
MIXER = re.compile(r"/layer_(\d+)/mamba(?:/|$)")
SCAN = re.compile(r"/layer_\d+/mamba/ssm\.scan(?:/|$)")
RECOMPUTED_MARK = re.compile(r"/rematted_computation/")
FWD, RECOMPUTED, BWD, OTHER = "scan_fwd", "scan_fwd_in_bwd", "scan_bwd", "mixer_other"
SCAN_KINDS = (FWD, RECOMPUTED, BWD)


def kind_of(op_name: str | None) -> str | None:
    """Which of this file's times an operation's `op_name` belongs to, or None."""
    if not op_name or not train_parts.STEP_PROGRAM.search(op_name) or not MIXER.search(op_name):
        return None
    if not SCAN.search(op_name):
        return OTHER
    if not train_parts.BACKWARD.search(op_name):
        return FWD
    return RECOMPUTED if RECOMPUTED_MARK.search(op_name) else BWD


def mixer_times(ctx) -> dict | None:
    """A whole step's device milliseconds by `kind_of`, and `recomputed`: the number of
    layers whose scan the backward pass ran again (operations of theirs under `transpose(`
    and `rematted_computation` in the window). None where the trace holds no whole step or
    none of these operations."""
    if "_ssm_mixer_times" not in ctx:
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
                out["recomputed"] = len({
                    MIXER.search(names[e["name"]]).group(1) for e in dev["ops"]
                    if t0 <= e["start_ns"] < t1 and kinds.get(e["name"]) == RECOMPUTED})
        ctx["_ssm_mixer_times"] = out
    return ctx["_ssm_mixer_times"]


def ssm_ms(ctx):
    """Device milliseconds a whole step spends under `layer_N/mamba`, every mixer, forward,
    recomputed and backward."""
    times = mixer_times(ctx)
    return sum(v for k, v in times.items() if k != "recomputed") if times else None


def ssm_scan_ms(ctx):
    """Of it, the time under the scope `ssm.scan`."""
    times = mixer_times(ctx)
    scan = sum(times.get(k, 0.0) for k in SCAN_KINDS) if times else 0.0
    return scan or None


def ssm_scan_roofline_share(ctx):
    """The least time the chip could take for the step's scans (`flops_ssm.py`: the larger
    of FLOP over the bf16 peak and bytes over the memory's peak; forward and backward of
    every Mamba-2 layer, and a forward more for each layer whose scan the trace shows run
    again under `transpose(`) over the time under `ssm.scan`. The counts are fixed by the
    configuration, not by how the program computes the scan."""
    cfg, mix = ctx["config"], ctx["traffic"]
    ms = ssm_scan_ms(ctx) if "mamba_d_state" in cfg else None
    if not ms:
        return None
    shape, layers = flops_ssm.scan_shape(cfg, mix), cfg["layer_types"].count("mamba")
    again = mixer_times(ctx)["recomputed"]
    flop = (layers * (flops_ssm.scan_fwd_flop(*shape) + flops_ssm.scan_bwd_flop(*shape))
            + again * flops_ssm.scan_fwd_flop(*shape))
    moved = (layers * (flops_ssm.scan_fwd_bytes(*shape) + flops_ssm.scan_bwd_bytes(*shape))
             + again * flops_ssm.scan_fwd_bytes(*shape))
    least_s = max(flop / ctx["peaks"]["flops_per_s_bf16"], moved / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)


def ssm_chunk_decay(ctx):
    """The window's mean of the step counter `ssm_chunk_decay`, in %: the share of a
    state that survives one chunk, over the mixers, heads and chunks."""
    value = ctx["facts"].get("step_counters", {}).get("ssm_chunk_decay")
    return None if value is None else 100.0 * value


METRICS = {
    "ssm_ms.train": ssm_ms,
    "ssm_scan_ms.train": ssm_scan_ms,
    "ssm_scan_roofline_share.train": ssm_scan_roofline_share,
    "ssm_chunk_decay.train": ssm_chunk_decay,
}
