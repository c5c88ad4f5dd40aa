# Top-level developer entry points. The native core has its own Makefile
# (kubeflow_tpu/native/Makefile) for building libkfcore.so and the
# sanitizer self-test binaries.

NATIVE := kubeflow_tpu/native

.PHONY: test lint modelcheck test-analysis test-chaos test-trace test-health test-prof test-cplane test-fleet test-hotpath test-partition test-slo test-decode test-soak test-pods test-sched test-protocheck selftest-sanitizers native

test: lint modelcheck
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow'

# kftpu-check: AST invariant linter (docs/analysis.md). Exits non-zero on
# any finding not pinned in tests/golden/lint_baseline.json; regenerate
# with `KFTPU_UPDATE_LINT_BASELINE=1 python -m kubeflow_tpu.analysis`
# (only to shrink it — never grow it to dodge a new finding).
lint:
	python -m kubeflow_tpu.analysis

# kftpu-protocheck: bounded-exhaustive model checking of the wire /
# paged-KV-handoff / chip-ledger protocol state machines, with minimal
# counterexample schedules on violation (docs/analysis.md "Protocol
# model checking"; KFTPU_MODELCHECK_DEPTH / KFTPU_MODELCHECK_SEED widen
# the sweep). Sub-second at the default budget — a `make test` step.
modelcheck:
	python -m kubeflow_tpu.analysis --modelcheck

# kftpu-check's own suite: checker fixtures, baseline round-trip, and the
# lock-order/race detector unit tests (docs/analysis.md)
test-analysis:
	JAX_PLATFORMS=cpu python -m pytest tests/test_analysis.py -q -m analysis

# recovery drills only (seeded fault injection — docs/chaos.md)
test-chaos:
	JAX_PLATFORMS=cpu python -m pytest tests/test_chaos_drills.py -q -m chaos

# tracing + flight-recorder suite, incl. the gang-restart trace drill
# (docs/observability.md)
test-trace:
	JAX_PLATFORMS=cpu python -m pytest tests/test_tracing.py -q -m trace

# liveness layer: heartbeat leases, hang/straggler detection, and the
# verified-checkpoint fallback drill (docs/health.md)
test-health:
	JAX_PLATFORMS=cpu python -m pytest tests/test_health_drills.py -q -m health

# profiling layer: trace analytics + golden trace-shape drill
# (docs/profiling.md)
test-prof:
	JAX_PLATFORMS=cpu python -m pytest tests/test_profiling.py -q -m prof

# control-plane scale-out suite: sharded/filtered watch drills, keyed-pool
# per-key ordering, status-write group commit
# (docs/architecture.md "Control-plane scaling")
test-cplane:
	JAX_PLATFORMS=cpu python -m pytest tests/test_cplane.py -q -m cplane

# serving-fleet suite: paged-KV prefix reuse, chunked-prefill equivalence,
# router admission/shed + the seeded replica-kill drill
# (docs/serving.md)
test-fleet:
	JAX_PLATFORMS=cpu python -m pytest tests/test_fleet.py -q -m fleet

# training hot-path suite: restart-warm compile cache (warm incarnation
# = zero backend compiles), AsyncLoader edge drills under the lock-order
# detector, analytics splits (docs/perf.md "MFU hunt")
test-hotpath:
	JAX_PLATFORMS=cpu python -m pytest tests/test_hotpath.py -q -m hotpath

# kftpu-partition suite: logical-axis rule derivation, legacy round-trip,
# hybrid-mesh guard, bf16-by-default numerics gate, buffer-donation
# accounting (docs/partitioner.md)
test-partition:
	JAX_PLATFORMS=cpu python -m pytest tests/test_partitioner.py -q -m partition

# kftpu-reqtrace suite: serving request tracing (golden kill→requeue
# trace shape), the bounded TSDB, SLO burn-rate evaluation, /debug/slo
# surface agreement (docs/slo.md)
test-slo:
	JAX_PLATFORMS=cpu python -m pytest tests/test_slo.py -q -m slo

# kftpu-decode suite: decode rows growing paged block chains
# (allocate-on-boundary, COW-safe sharing), block-budgeted admission,
# chain adoption by digest, speculative x chunked composition pinned
# token-identical, the disaggregated prefill/decode tier, and the
# resume-from-KV requeue drill
# (docs/serving.md "Disaggregated prefill/decode")
test-decode:
	JAX_PLATFORMS=cpu python -m pytest tests/test_decode.py -q -m decode

# kftpu-storm suite: the closed autoscaling loop (scale-up cooldown,
# graceful-drain scale-down, loss-free drain-kill resume, scale-to-zero
# + wake-on-arrival, hang detection, frozen-scaler chaos mode), the
# golden scaler decision trace, activator cold-start Retry-After
# calibration, SLO monitoring across scaler activity, the seeded
# production day (healthy and with the scaler frozen) and the
# chip-constrained diurnal storm (healthy and with the ledger frozen)
# (docs/autoscaling.md, docs/scheduler.md)
test-soak:
	JAX_PLATFORMS=cpu python -m pytest tests/test_soak.py -q -m soak

# kftpu-pods suite: cross-process pod-backed replicas — real subprocess
# workers behind the length-prefixed wire protocol over BOTH transports
# (AF_UNIX and kftpu-net's 127.0.0.1 TCP), the digest-checked paged-KV
# handoff codec, SIGKILL mid-decode zero-drop chain resume, SIGSTOP
# heartbeat-age hang indictment + scaler replacement, torn-frame retry
# idempotency, end-to-end deadline propagation, the network failure
# family (severed-connection replay, stale-epoch 410 fencing, the
# partition-heal split-brain drill), and the kill drill under the
# seeded wire- and net-fault plan
# (docs/serving.md "Pod-backed replicas")
test-pods:
	JAX_PLATFORMS=cpu python -m pytest tests/test_pods.py -q -m pods

# kftpu-chipsched suite: the shared chip ledger both workload classes
# claim through — slice-aware placement, priority preemption through
# the gang-restart path (sched.preempt→job.gang_restart span link +
# restart-warm resume), DRF tenant quotas with borrow/reclaim, the
# deny/Retry-After contract, /debug/sched surface agreement (the
# diurnal storm that drives it end to end is in tests/test_soak.py)
# (docs/scheduler.md)
test-sched:
	JAX_PLATFORMS=cpu python -m pytest tests/test_chipsched.py -q -m sched

# kftpu-protocheck suite: exploration-kernel unit tests, HEAD-clean pins,
# the per-mutation counterexample pins, and recorded-trace conformance
# (docs/analysis.md "Protocol model checking")
test-protocheck: modelcheck
	JAX_PLATFORMS=cpu python -m pytest tests/test_protocheck.py -q -m modelcheck

native:
	$(MAKE) -C $(NATIVE)

# Build and run the ASan/UBSan + TSan self-tests of the native core
# (workqueue, expectations, event hub, reconciler, metastore). Nothing
# under native/build/ is tracked: every binary is made from src/*.cc here.
selftest-sanitizers:
	$(MAKE) -C $(NATIVE) check tsan
