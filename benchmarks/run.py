"""The benchmark's entry: one run of one cell of `BENCHMARK.json`.

    python3 -m benchmarks.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Earlier lines of the output are for people; the last line is the result. `--trace 0`
reports the cell's end-to-end metrics, `--trace 1` its per-layer metrics from a run in
which the profiler has about three seconds of the window. There is no CPU mode."""

from __future__ import annotations

import argparse
import importlib
import json
import pkgutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:  # `python3 benchmarks/run.py` puts benchmarks/ there instead
    sys.path.insert(0, str(ROOT))

from benchmarks import runtime  # noqa: E402

MANIFEST = ROOT / "BENCHMARK.json"


def log(text: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {text}", flush=True)


def load_cell(manifest: dict, workload: str) -> dict:
    """The cell's entry with its configuration and traffic files read."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r}; there are {sorted(cells)}")
    cell = dict(cells[workload])
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    cell["config_data"] = json.loads((ROOT / entry["file"]).read_text())
    cell["traffic_data"] = json.loads(
        (runtime.HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell


def metrics_of(manifest: dict, group: str, workload: str) -> list[dict]:
    return [m for m in manifest[group] if workload in m.get("workloads", [workload])]


def layer_readers() -> dict:
    """name -> reader, from every module of `layer_metrics/` (each has a dict `METRICS`)."""
    from benchmarks import layer_metrics

    readers: dict = {}
    for info in pkgutil.iter_modules(layer_metrics.__path__):
        mod = importlib.import_module(f"benchmarks.layer_metrics.{info.name}")
        for name, reader in mod.METRICS.items():
            if name in readers:
                raise SystemExit(f"benchmark: two readers for the per-layer metric {name!r}")
            readers[name] = reader
    return readers


def main(argv: list[str] | None = None) -> int:
    t_origin = time.perf_counter() - runtime.process_age_s()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = json.loads(MANIFEST.read_text())
    cell = load_cell(manifest, args.workload)
    device = runtime.require_chips(int(cell["chips"]))
    log(f"platform={device['platform']} device_kind={device['kind']!r} device_count={device['count']} "
        f"cell={cell['name']} seed={args.seed} seconds={args.seconds} trace={args.trace}")
    log(f"compile cache at {runtime.enable_compile_cache()}")
    builds = runtime.Builds()
    window = runtime.TraceWindow(bool(args.trace), cell["name"])
    kind = importlib.import_module(f"benchmarks.kinds.{cell['traffic_data']['kind']}")
    out = kind.run(cell["config_data"], cell["traffic_data"], args.seed, args.seconds, window,
                   {"log": log, "builds": builds})

    setup_s = out["t_window_start"] - t_origin
    log(f"programs built in set-up {out['setup_builds']} and in the window {out['window_builds']}; "
        f"setup_s={setup_s:.2f}")
    peak = runtime.memory_peak_bytes(device["count"])
    log(f"memory_peak_bytes={peak}: the runtime's own statistics of chip 0 are {runtime.memory_stats(0)}")
    result_device = {"platform": device["platform"], "kind": device["kind"],
                     "count": device["count"], "memory_peak_bytes": peak}
    result = {"correct": bool(out["correct"]), "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": {}, "device": result_device}
    if args.trace:
        from benchmarks import trace as trace_lib

        path = window.xplane()
        if path is None:
            raise SystemExit("benchmark: the traced run left no .xplane.pb")
        t_read = time.perf_counter()
        events = trace_lib.load(path, device["count"])
        reduced = trace_lib.reduce(events)
        if not reduced:
            raise SystemExit("benchmark: no operation ran on the device in the traced window")
        log(f"trace {path.stat().st_size} bytes reduced in {time.perf_counter() - t_read:.1f} s: "
            f"busy {reduced['busy_s']:.4f} of {reduced['window_s']:.4f} s, "
            f"longest gap {reduced['longest_gap_s'] * 1e3:.3f} ms")
        ctx = {"facts": out["facts"], "events": events, "reduced": reduced, "peaks": device["peaks"],
               "memory_peak_bytes": peak, "config": cell["config_data"], "traffic": cell["traffic_data"]}
        readers = layer_readers()
        for m in metrics_of(manifest, "per_layer", cell["name"]):
            value = readers[m["name"]](ctx) if m["name"] in readers else None
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value), "unit": m["unit"]}
        result_device["busy_s"] = reduced["busy_s"]
        result_device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
    else:
        values = dict(out["end_to_end"], setup_s=setup_s)
        for m in metrics_of(manifest, "end_to_end", cell["name"]):
            result["metrics"][m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
