"""kftpu-storm — the production-day soak (ROADMAP item 6).

One seeded, tick-driven "day in production" composing every subsystem
the platform has grown: diurnal traffic waves against a FleetScaler-
autoscaled serving fleet (scale-to-zero through the wake-on-arrival
cold-start path), training-job churn on the control plane, and injected
faults — replica kills, a pod hang, a torn checkpoint — with ONE report
(`monitoring.build_slo_report` + `SLOMonitor.evaluate()` over the
calibrated `default_slos()` set) gating goodput ratio, the restart-
overhead budget, p99 TTFT, and zero dropped requests. tests/test_soak.py
runs the day once and holds each of its counts as a case;
`run_prod_day(cfg, frozen=True)` is the falsifiable teeth: a scaler
that stops reacting while the waves continue must fire the SLO
burn-rate alert. docs/autoscaling.md is the guide.

kftpu-chipsched adds the diurnal storm (`run_diurnal_storm`): the same
day re-run on a chip-CONSTRAINED cluster where peak serving demand
cannot fit without preempting batch training through the shared
ChipScheduler ledger — real JAXJob gangs evicted via the gang-restart
path, resumed when the trough frees chips, gated on preemption-to-
resume latency, zero serving SLO violations, and a batch goodput
floor. `run_diurnal_storm(cfg, frozen=True)` (the ledger stops
granting) is its teeth. docs/scheduler.md is the guide.

kftpu-net re-composes the day on REAL pods (`run_prod_day_pods`): a
spawn_pod TCP fleet where the kills are SIGKILLs discovered through the
wire, the hang is a SIGSTOP indicted by heartbeat age, and a mid-peak
network partition heals only after the scaler has replaced the victim —
the fenced claim's late deliveries are then read back and refused
(epoch fencing, docs/serving.md), gated on dropped == 0 EXACT and
zero duplicate tokens.
"""

from kubeflow_tpu.soak.scenario import (
    PodSoakConfig,
    SoakConfig,
    StormConfig,
    calibrated_default_slos,
    run_diurnal_storm,
    run_prod_day,
    run_prod_day_pods,
)

__all__ = [
    "PodSoakConfig",
    "SoakConfig",
    "StormConfig",
    "calibrated_default_slos",
    "run_diurnal_storm",
    "run_prod_day",
    "run_prod_day_pods",
]
