"""Trace analytics — turning flight-recorder spans into answers.

PR 2 gave the platform raw spans (tracing/); this module is the layer that
computes from them:

  - a per-step time breakdown: for every `train.step` (or `train.chunk`)
    span, the step CYCLE is the wall-clock from the end of the previous
    step to the end of this one. Inside the cycle, `data_load` is the
    host-side fetch time (train.data_load spans), `checkpoint` the
    checkpoint.save/restore time, `compute` the step span's own duration,
    and `stall` is DEFINED as the remainder — so the four phases sum to
    the cycle wall-time exactly and unattributed time is visible instead
    of silently vanishing (the MLPerf-tuning loop of 1909.09756 runs on
    exactly this accounting);
  - goodput per job incarnation: productive step time vs rendezvous /
    checkpoint / restart overhead, attributed to the causal chain the
    cross-process parent links carry (chaos kill -> pod exit -> gang
    restart -> create -> first post-restore step);
  - control-plane latency: reconcile-duration and watch-delivery
    percentiles per controller, derived from the EXISTING reconcile /
    http.request spans — no new instrumentation (2011.03641: at fleet
    scale the control plane, not the chips, caps concurrency).

Everything operates on plain span dicts (tracing/core.Span.to_dict):
{"name", "trace", "span", "parent", "ts", "dur", "pid", "tid", "attrs"}.
"""

from __future__ import annotations

#: span names that delimit a training step cycle
STEP_NAMES = ("train.step", "train.chunk")
#: host-side input-pipeline spans accounted inside a cycle
DATA_NAMES = ("train.data_load",)
#: checkpoint I/O spans accounted inside a cycle
CKPT_NAMES = ("checkpoint.save", "checkpoint.restore")
#: gradient-communication spans accounted inside a cycle: host-visible
#: time spent waiting on gradient collectives that did NOT overlap the
#: backward pass (no producer in the package emits these today; on
#: hardware a step with full comm/compute overlap shows ~zero here)
COMM_NAMES = ("train.comm",)
#: span names that only the PLATFORM process emits — used to tell a
#: platform-bearing trace apart from a workers-only flush directory
PLATFORM_SPAN_NAMES = frozenset((
    "reconcile", "http.request", "http.watch", "gang.bind", "gang.preempt",
    "job.create_pods", "job.rendezvous", "job.gang_restart",
    "pod.launch", "pod.exit", "pod.kill",
))

#: shared histogram buckets for the kftpu_prof_* families (seconds)
PROF_BUCKETS: tuple[float, ...] = (0.001, 0.005, 0.02, 0.1, 0.5, 2.0, 10.0)

#: the serving request root span (serving/fleet/router, serving/continuous)
REQUEST_ROOT = "request"
#: request child-span name -> breakdown phase it is charged to
REQUEST_PHASE_NAMES = {
    "request.admission": "admission",
    "engine.queue_wait": "queue",
    "engine.prefill_chunk": "prefill",
    "engine.decode": "decode",
}
#: the phases of a request cycle, in charge order (stall = remainder)
REQUEST_PHASES = ("admission", "queue", "prefill", "decode", "stall")


def percentile(sorted_values: list[float], q: float) -> float:
    """Nearest-rank percentile over an already-sorted list (0 when empty).
    Nearest-rank (not interpolated) so a percentile is always a value that
    actually occurred — the honest form for latency reporting."""
    if not sorted_values:
        return 0.0
    idx = max(0, min(len(sorted_values) - 1,
                     int(round(q * (len(sorted_values) - 1)))))
    return sorted_values[idx]


def _end(s: dict) -> float:
    return s["ts"] + s["dur"]


# ------------------------------------------------------ step-time breakdown


def step_breakdown(spans: list[dict]) -> list[dict]:
    """Per-step phase accounting, one dict per step cycle.

    Steps are grouped per worker process (pid): the cycle window runs from
    the end of the worker's previous step (or its first span's start, for
    the first step) to the end of this step. A phase span is charged to the
    cycle its END falls inside — fetch/save work is sequential with the
    step dispatch on the worker thread, so windows partition the phases.
    Each returned dict satisfies
    ``data_load + compute + checkpoint + comm + stall == wall`` (stall is
    the remainder, floored at 0 against float noise). ``comm`` counts
    `train.comm` spans — gradient-collective time left ON the critical
    path; a fully overlapped step charges ~nothing here.

    data_load itself splits sum-exactly into ``data_wait + data_assemble
    == data_load``: when the async host loader stamps a ``wait_s`` attr
    (queue-blocked time — what the critical path actually paid),
    data_wait is that portion (clamped to the span) and data_assemble the
    in-span remainder; spans without the attr (the inline loader) are all
    assemble — the split shows how much host work the background thread
    moved OFF the critical path.
    """
    by_pid: dict[int, list[dict]] = {}
    for s in spans:
        by_pid.setdefault(s.get("pid", 0), []).append(s)
    out: list[dict] = []
    for pid in sorted(by_pid):
        ss = sorted(by_pid[pid], key=lambda s: s["ts"])
        steps = [s for s in ss if s["name"] in STEP_NAMES]
        if not steps:
            continue
        data = sorted((s for s in ss if s["name"] in DATA_NAMES),
                      key=_end)
        ckpt = sorted((s for s in ss if s["name"] in CKPT_NAMES),
                      key=_end)
        comm = sorted((s for s in ss if s["name"] in COMM_NAMES),
                      key=_end)
        prev_end = ss[0]["ts"]
        for st in sorted(steps, key=_end):
            end = _end(st)
            # a degenerate window (clock step between processes) still
            # charges at least the step's own duration
            wall = max(end - prev_end, st["dur"])
            in_window = lambda s: prev_end < _end(s) <= end  # noqa: E731
            d = wait = 0.0
            for s in data:
                if in_window(s):
                    d += s["dur"]
                    # wait is clamped to the span so the split can never
                    # exceed what the cycle was actually charged
                    wait += min(float(s["attrs"].get("wait_s", 0.0)),
                                s["dur"])
            c = sum(s["dur"] for s in ckpt if in_window(s))
            cm = sum(s["dur"] for s in comm if in_window(s))
            compute = st["dur"]
            stall = max(wall - compute - d - c - cm, 0.0)
            out.append({
                "pid": pid,
                "step": st["attrs"].get("step"),
                "ts": st["ts"],
                "wall": wall,
                "data_load": d,
                "data_wait": wait,
                "data_assemble": d - wait,
                "compute": compute,
                "checkpoint": c,
                "comm": cm,
                "stall": stall,
            })
            prev_end = end
    return out


def aggregate_steps(steps: list[dict]) -> dict:
    """Totals + per-step distribution over step_breakdown() output."""
    phases = ("data_load", "compute", "checkpoint", "comm", "stall")
    totals = {p: sum(s[p] for s in steps) for p in phases}
    wall = sum(s["wall"] for s in steps)
    walls = sorted(s["wall"] for s in steps)
    data = totals["data_load"]
    wait = sum(s["data_wait"] for s in steps)
    return {
        "count": len(steps),
        "wall_s": round(wall, 6),
        "phases_s": {p: round(v, 6) for p, v in totals.items()},
        # the async-loader split of data_load (wait + assemble == load):
        # assemble is host work still ON the critical path — the number
        # the AsyncLoader exists to drive toward zero
        "data_load_split": {
            "queue_wait_s": round(wait, 6),
            "assemble_s": round(data - wait, 6),
        },
        "fractions": {
            p: (round(v / wall, 4) if wall else 0.0)
            for p, v in totals.items()
        },
        "per_step": {
            "mean_s": round(wall / len(steps), 6) if steps else 0.0,
            "p50_s": round(percentile(walls, 0.50), 6),
            "p99_s": round(percentile(walls, 0.99), 6),
        },
    }


# ------------------------------------------------------- goodput accounting


def goodput(spans: list[dict], steps: list[dict] | None = None) -> dict:
    """Productive step time vs overhead, per job incarnation.

    Incarnations are keyed by `job.create_pods` spans (their `restart`
    attribute); worker spans parent-link to the create span that launched
    them via the pod-env traceparent, so attribution needs no name
    heuristics. Without any create span (an in-process training run) all
    steps form one implicit incarnation. The window is the whole span
    snapshot's extent; goodput = productive / window.
    """
    if steps is None:
        steps = step_breakdown(spans)
    if not spans:
        return {"window_s": 0.0, "productive_s": 0.0, "overhead_s": 0.0,
                "restart_overhead_s": 0.0, "goodput": 0.0,
                "incarnations": []}
    t0 = min(s["ts"] for s in spans)
    t1 = max(_end(s) for s in spans)
    window = max(t1 - t0, 0.0)

    creates = sorted((s for s in spans if s["name"] == "job.create_pods"),
                     key=lambda s: s["ts"])
    by_parent: dict[str, list[dict]] = {}
    for s in spans:
        by_parent.setdefault(s.get("parent", ""), []).append(s)

    def _overheads(children: list[dict]) -> tuple[float, float]:
        rdv = sum(s["dur"] for s in children
                  if s["name"] in ("rendezvous", "runtime.rendezvous"))
        ck = sum(s["dur"] for s in children if s["name"] in CKPT_NAMES)
        return rdv, ck

    incarnations: list[dict] = []
    if creates:
        for c in creates:
            kids = by_parent.get(c["span"], [])
            kid_steps = [s for s in kids if s["name"] in STEP_NAMES]
            rdv, ck = _overheads(kids)
            incarnations.append({
                "restart": c["attrs"].get("restart", 0),
                "steps": len(kid_steps),
                "productive_s": round(sum(s["dur"] for s in kid_steps), 6),
                "rendezvous_s": round(rdv, 6),
                "checkpoint_s": round(ck, 6),
            })
    else:
        rdv, ck = _overheads(spans)
        incarnations.append({
            "restart": 0,
            "steps": len(steps),
            "productive_s": round(sum(s["compute"] for s in steps), 6),
            "rendezvous_s": round(rdv, 6),
            "checkpoint_s": round(ck, 6),
        })
    productive = sum(i["productive_s"] for i in incarnations)
    overhead = sum(i["rendezvous_s"] + i["checkpoint_s"]
                   for i in incarnations)
    # restart overhead: wall-clock each recovery chain spent between the
    # root cause (the kill) and the first post-restore step
    chains = restart_chains(spans)
    restart_s = sum(ch["overhead_s"] for ch in chains)
    # total overhead must not double-count: the restarted incarnation's
    # rendezvous lies INSIDE its restart window (it precedes the first
    # post-restore step by definition), so subtract it from the window's
    # contribution — overhead can then never exceed elapsed wall-clock
    by_restart = {i["restart"]: i for i in incarnations}
    non_overlap_restart = sum(
        max(ch["overhead_s"]
            - by_restart.get(ch["restart"], {}).get("rendezvous_s", 0.0),
            0.0)
        for ch in chains
    )
    return {
        "window_s": round(window, 6),
        "productive_s": round(productive, 6),
        "overhead_s": round(overhead + non_overlap_restart, 6),
        "restart_overhead_s": round(restart_s, 6),
        "goodput": round(productive / window, 4) if window else 0.0,
        "incarnations": incarnations,
    }


# ------------------------------------------------- control-plane analytics


def control_plane_stats(spans: list[dict]) -> dict:
    """Reconcile + watch-delivery percentiles per controller, and
    http.request latency — all from the spans PR 2 already emits.

    Watch-delivery latency is the gap between the END of the span whose
    write published the triggering event (the reconcile span's parent,
    when it is still in the snapshot) and the reconcile pass starting.
    """
    by_id = {s["span"]: s for s in spans}
    recs: dict[str, list[dict]] = {}
    for s in spans:
        if s["name"] != "reconcile":
            continue
        recs.setdefault(str(s["attrs"].get("controller", "?")), []).append(s)
    out: dict = {"reconcile": {}, "http": {}}
    for ctrl in sorted(recs):
        group = recs[ctrl]
        durs = sorted(s["dur"] for s in group)
        delays = []
        depths = [s["attrs"]["queue_depth"] for s in group
                  if "queue_depth" in s["attrs"]]
        for s in group:
            parent = by_id.get(s.get("parent", ""))
            if parent is not None:
                delays.append(max(s["ts"] - _end(parent), 0.0))
        delays.sort()
        out["reconcile"][ctrl] = {
            "count": len(group),
            "p50_s": round(percentile(durs, 0.50), 6),
            "p90_s": round(percentile(durs, 0.90), 6),
            "p99_s": round(percentile(durs, 0.99), 6),
            "watch_delay_p50_s": round(percentile(delays, 0.50), 6),
            "watch_delay_p99_s": round(percentile(delays, 0.99), 6),
            "watch_delay_samples": len(delays),
            "mean_queue_depth": (
                round(sum(depths) / len(depths), 2) if depths else 0.0),
        }
    https = sorted(s["dur"] for s in spans if s["name"] == "http.request")
    if https:
        out["http"] = {
            "count": len(https),
            "p50_s": round(percentile(https, 0.50), 6),
            "p99_s": round(percentile(https, 0.99), 6),
        }
    return out


# ---------------------------------------------- restart causal attribution


def ancestry(spans: list[dict], leaf: dict) -> list[dict]:
    """The parent chain of `leaf`, root first, leaf last — following the
    cross-process links the carriers threaded through. Stops at a parent
    that fell off the ring (renders as a root, same as the text tree)."""
    by_id = {s["span"]: s for s in spans}
    chain = [leaf]
    seen = {leaf["span"]}
    cur = leaf
    while True:
        parent = by_id.get(cur.get("parent", ""))
        if parent is None or parent["span"] in seen:
            break
        chain.append(parent)
        seen.add(parent["span"])
        cur = parent
    chain.reverse()
    return chain


def _resolve_chains(spans: list[dict]) -> list[dict]:
    """The shared restart-chain resolution both restart_chains() (the
    numeric summary) and restart_shape() (the golden text) render from —
    one matching rule, so a fix to it can never leave the two surfaces
    disagreeing. Each record carries the actual span dicts:
    {"rs", "up", "create", "kids", "steps", "rendezvous", "first_step"}.

    A restart decision is matched to its `job.create_pods` span by the
    restart counter AND the job key (both spans carry `key`): two jobs
    restarting concurrently both have restart=1, and counter-only
    matching would attribute one job's recovery to the other's pods.
    """
    by_parent: dict[str, list[dict]] = {}
    for s in spans:
        by_parent.setdefault(s.get("parent", ""), []).append(s)
    creates = sorted((s for s in spans if s["name"] == "job.create_pods"),
                     key=lambda s: s["ts"])
    out = []
    for rs in sorted((s for s in spans if s["name"] == "job.gang_restart"),
                     key=lambda s: s["ts"]):
        restart = rs["attrs"].get("restart")
        key = rs["attrs"].get("key")
        create = next(
            (c for c in creates
             if c["attrs"].get("restart") == restart
             and (key is None or c["attrs"].get("key") in (None, key))),
            None,
        )
        kids = by_parent.get(create["span"], []) if create else []
        kid_steps = sorted((s for s in kids if s["name"] in STEP_NAMES),
                           key=lambda s: s["ts"])
        out.append({
            "rs": rs,
            "up": ancestry(spans, rs),
            "create": create,
            "kids": kids,
            "steps": kid_steps,
            "rendezvous": [s for s in kids if s["name"] in
                           ("rendezvous", "runtime.rendezvous")],
            "first_step": kid_steps[0] if kid_steps else None,
        })
    return out


def restart_chains(spans: list[dict]) -> list[dict]:
    """One record per gang restart: the upward causal chain (e.g. chaos
    kill -> pod exit -> restart decision), the matching restart
    incarnation's create/rendezvous/step spans, the wall-clock overhead
    from the chain root to the first post-restore step, and whether the
    whole path is monotonic in wall-clock.

    overhead_s splits sum-exactly into ``compile_s + restore_s +
    rendezvous_s + schedule_s``: compile is the incarnation's
    train.compile span(s) (the re-trace+recompile cost the restart-warm
    cache exists to erase), restore its checkpoint.restore, rendezvous
    its gang bring-up, and schedule the remainder — the control-plane
    path from the root cause through pod exit, restart decision, create,
    bind, and process start (each floored at 0 against clock skew)."""
    chains = []
    for r in _resolve_chains(spans):
        up, create, first_step = r["up"], r["create"], r["first_step"]
        path = up + ([create] if create else []) \
            + ([first_step] if first_step else [])
        stamps = [s["ts"] for s in path]
        overhead = (round(max(first_step["ts"] - up[0]["ts"], 0.0), 6)
                    if first_step and up else 0.0)
        # phase spans of THIS incarnation that precede its first step:
        # only time inside the overhead window can be attributed to it
        pre = [s for s in r["kids"]
               if first_step is None or s["ts"] < first_step["ts"]]
        compile_s = min(sum(s["dur"] for s in pre
                            if s["name"] == "train.compile"), overhead)
        restore_s = min(sum(s["dur"] for s in pre
                            if s["name"] == "checkpoint.restore"),
                        max(overhead - compile_s, 0.0))
        rdv_s = min(sum(s["dur"] for s in r["rendezvous"]
                        if first_step is None
                        or s["ts"] < first_step["ts"]),
                    max(overhead - compile_s - restore_s, 0.0))
        compile_s = round(compile_s, 6)
        restore_s = round(restore_s, 6)
        rdv_s = round(rdv_s, 6)
        chains.append({
            "restart": r["rs"]["attrs"].get("restart"),
            "chain": [s["name"] for s in path],
            "root": up[0]["name"] if up else "",
            "overhead_s": overhead,
            "compile_s": compile_s,
            "restore_s": restore_s,
            "rendezvous_s": rdv_s,
            "schedule_s": max(round(
                overhead - compile_s - restore_s - rdv_s, 6), 0.0),
            "rendezvous": len(r["rendezvous"]),
            "steps": len(r["steps"]),
            "monotonic": stamps == sorted(stamps),
        })
    return chains


def restart_shape(spans: list[dict]) -> str:
    """Canonical, golden-pinnable text form of every restart chain: span
    NAMES and PARENTAGE only (no ids, no times), repeated worker spans
    collapsed to `name xN`, plus a monotonicity verdict — so a structural
    regression in the causal links (a dropped carrier, a reparented
    restart) diffs loudly while timing noise never does."""
    lines: list[str] = []
    for rec, r in zip(restart_chains(spans), _resolve_chains(spans)):
        for depth, s in enumerate(r["up"]):
            extra = ""
            if s["name"] == "pod.exit":
                extra = f" exit_code={s['attrs'].get('exit_code')}"
            elif s["name"] == "job.gang_restart":
                extra = f" restart={s['attrs'].get('restart')}"
            lines.append("  " * depth + s["name"] + extra)
        if r["create"] is not None:
            lines.append(
                "job.create_pods restart="
                f"{r['create']['attrs'].get('restart')}")
            # WORKER children only: platform spans can legitimately race
            # onto either parent (a pod.launch parents to the bind OR the
            # create depending on watch-delivery order), and the shape pin
            # must never flake on a benign race
            counts: dict[str, int] = {}
            for s in r["kids"]:
                if s["name"] not in PLATFORM_SPAN_NAMES:
                    counts[s["name"]] = counts.get(s["name"], 0) + 1
            for name in sorted(counts):
                lines.append(f"  {name} x{counts[name]}")
        lines.append("order: " + ("monotonic" if rec["monotonic"]
                                  else "OUT-OF-ORDER"))
    return "\n".join(lines) + ("\n" if lines else "")


# ------------------------------------------------- serving request breakdown


def request_breakdown(spans: list[dict]) -> list[dict]:
    """Per-request phase accounting — the serving analogue of
    step_breakdown, one dict per `request` root span.

    The request's wall time is its root span's duration (fleet submit →
    done, requeues included). Child spans are charged to their phase
    (REQUEST_PHASE_NAMES: the admission decision, engine queue wait,
    prefill chunks, decode windows — a requeued request's second attempt
    contributes additional queue/prefill/decode time under the SAME
    root) and ``stall`` is DEFINED as the remainder, so

        admission + queue + prefill + decode + stall == wall

    holds EXACTLY on every row (the acceptance pin,
    tests/test_slo.py). Phase charges are clamped in time order so a
    child that overruns the root (clock noise at the requeue seam) can
    never drive stall negative. Rows also carry the reuse ledger
    (reused/computed prefill tokens off the chunk spans' attrs) and the
    request's identity attrs.
    """
    by_parent: dict[str, list[dict]] = {}
    for s in spans:
        if s["name"] in REQUEST_PHASE_NAMES:
            by_parent.setdefault(s.get("parent", ""), []).append(s)
    out: list[dict] = []
    for root in sorted((s for s in spans if s["name"] == REQUEST_ROOT),
                       key=lambda s: s["ts"]):
        wall = root["dur"]
        phases = {p: 0.0 for p in REQUEST_PHASES}
        computed = reused = 0
        remaining = wall
        for child in sorted(by_parent.get(root["span"], []),
                            key=lambda s: s["ts"]):
            phase = REQUEST_PHASE_NAMES[child["name"]]
            charge = min(child["dur"], remaining)
            phases[phase] += charge
            remaining -= charge
            if child["name"] == "engine.prefill_chunk":
                computed += int(child["attrs"].get("tokens_computed", 0))
                reused += int(child["attrs"].get("tokens_reused", 0))
        phases["stall"] = max(remaining, 0.0)
        out.append({
            "request_id": root["attrs"].get("request_id", ""),
            "trace": root["trace"],
            "ts": root["ts"],
            "wall": wall,
            **phases,
            "outcome": root["attrs"].get("outcome", ""),
            "attempts": root["attrs"].get("attempts", 1),
            "tokens": root["attrs"].get("tokens", 0),
            "prefill_tokens_computed": computed,
            "prefill_tokens_reused": reused,
        })
    return out


def aggregate_requests(reqs: list[dict]) -> dict:
    """Totals + distribution over request_breakdown() output — the
    shape /debug/slo, the slo CLI, and the kftpu_request_* families
    render (monitoring/report.py)."""
    walls = sorted(r["wall"] for r in reqs)
    wall = sum(walls)
    totals = {p: sum(r[p] for r in reqs) for p in REQUEST_PHASES}
    by_outcome: dict[str, int] = {}
    for r in reqs:
        key = r["outcome"] or "unknown"
        by_outcome[key] = by_outcome.get(key, 0) + 1
    return {
        "count": len(reqs),
        "wall_s": round(wall, 6),
        "by_outcome": by_outcome,
        "phases_s": {p: round(v, 6) for p, v in totals.items()},
        "fractions": {
            p: (round(v / wall, 4) if wall else 0.0)
            for p, v in totals.items()
        },
        "wall": {
            "mean_s": round(wall / len(reqs), 6) if reqs else 0.0,
            "p50_s": round(percentile(walls, 0.50), 6),
            "p99_s": round(percentile(walls, 0.99), 6),
        },
        "prefill_tokens_computed": sum(
            r["prefill_tokens_computed"] for r in reqs),
        "prefill_tokens_reused": sum(
            r["prefill_tokens_reused"] for r in reqs),
    }


def request_shape(spans: list[dict]) -> str:
    """Canonical, golden-pinnable text form of the serving request
    traces (the restart_shape analogue): every `request` root with its
    outcome/attempts and collapsed child-span counts, then every
    replica-kill event with the requeues parent-linked to it — names
    and parentage only, no ids or times, so a structural regression (a
    dropped carrier, a requeue orphaned from its kill) diffs loudly
    while timing noise never does."""
    by_parent: dict[str, list[dict]] = {}
    for s in spans:
        by_parent.setdefault(s.get("parent", ""), []).append(s)

    def kid_counts(span_id: str) -> list[str]:
        counts: dict[str, int] = {}
        for s in by_parent.get(span_id, []):
            counts[s["name"]] = counts.get(s["name"], 0) + 1
        return [f"  {name} x{counts[name]}" for name in sorted(counts)]

    lines: list[str] = []
    for root in sorted((s for s in spans if s["name"] == REQUEST_ROOT),
                       key=lambda s: s["ts"]):
        lines.append(
            f"request outcome={root['attrs'].get('outcome')} "
            f"attempts={root['attrs'].get('attempts', 1)}")
        lines.extend(kid_counts(root["span"]))
    for kill in sorted(
            (s for s in spans if s["name"] == "fleet.replica_kill"),
            key=lambda s: s["ts"]):
        lines.append(
            f"fleet.replica_kill replica={kill['attrs'].get('replica')}")
        lines.extend(kid_counts(kill["span"]))
    return "\n".join(lines) + ("\n" if lines else "")


def scaler_shape(spans: list[dict]) -> str:
    """Canonical, golden-pinnable text form of the autoscaler's decision
    traces (the request_shape analogue for serving/fleet/scaler.py):
    every `scaler.evaluate` event in time order with its decision and
    demand, then the scale/drain/kill/hang events parent-linked to it as
    collapsed `name xN` counts — names and parentage only, no ids or
    times, so a decision that loses its causal link to the burn
    evaluation that triggered it (the attributability contract) diffs
    loudly while timing noise never does."""
    by_parent: dict[str, list[dict]] = {}
    for s in spans:
        by_parent.setdefault(s.get("parent", ""), []).append(s)
    lines: list[str] = []
    for ev in sorted((s for s in spans if s["name"] == "scaler.evaluate"),
                     key=lambda s: s["ts"]):
        lines.append(
            f"scaler.evaluate decision={ev['attrs'].get('decision')} "
            f"demand={ev['attrs'].get('demand')}")
        counts: dict[str, int] = {}
        for s in by_parent.get(ev["span"], []):
            counts[s["name"]] = counts.get(s["name"], 0) + 1
        for name in sorted(counts):
            lines.append(f"  {name} x{counts[name]}")
    return "\n".join(lines) + ("\n" if lines else "")
