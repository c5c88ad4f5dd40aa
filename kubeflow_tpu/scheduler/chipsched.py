"""ChipScheduler — one chip inventory for BOTH workload classes.

ROADMAP item 3's arbiter: until now `GangScheduler` (controller/gang.py)
first-fit a private ledger for training gangs while the serving tier
allocated engines with no chip accounting at all — two tenants of the
same repo, each blind to the other's usage, and the autoscaler's paired
free/demand reads raced both. This module is the single source of truth
they all route through:

  - **slice-aware bin-packing**: the inventory is slices × chips
    (``chips_per_slice``). Gangs place whole-slice (topology-sized,
    slice-multiple gangs) or contiguous-within-a-slice, with a spanning
    fallback so admission remains a pure total-capacity predicate (a
    gang that fits by count always binds — fragmentation changes the
    *placement*, never the *admission*, preserving the pre-ledger
    contract every gang test pins). Serving replicas best-fit into the
    fullest slice that holds them, keeping whole slices free for gangs.
  - **priority classes**: serving > interactive > batch
    (``PRIORITY_SERVING/INTERACTIVE/BATCH``, aligned with the gang
    scheduler's PriorityClass ladder — "system-critical" == serving).
  - **preemption**: a claim that cannot fit may evict strictly-lower-
    priority *gang* claims (lowest priority first, youngest first —
    least sunk work). Feasibility is decided on a scratch copy BEFORE
    any eviction commits, so an infeasible preemption never thrashes a
    batch job through a pointless restart. Each committed eviction
    emits a ``sched.preempt`` span whose context is handed to the
    registered ``evictor`` — the gang scheduler stamps it on the victim
    pods (CARRIER_ANNOTATION + the retryable PREEMPTED exit class), so
    the job's ``job.gang_restart`` parent-links to the preemption and
    restart-overhead attribution + the compile-cache warm resume
    compose unchanged (docs/scheduler.md).
  - **fair-share tenant quotas**: ``set_shares({tenant: weight})`` arms
    weighted max-min entitlements (dominant-resource fairness over the
    single chip resource). A tenant over its entitlement may *borrow*
    idle chips — but a borrower can never preempt anyone (the quota
    analogue of gang.py's "quota-blocked gangs never use preempted
    chips"), and its borrowed claims become reclaim-eligible: an
    under-entitlement claimant may evict borrowed gang claims at equal
    priority, counted separately as quota reclaims.
  - **denial contract**: every refused claim is a ``Deny`` carrying the
    reason (frozen / quota / capacity) and a ``retry_after_s`` hint —
    the activator's Retry-After idiom, scheduler edition — plus a
    traced ``sched.deny`` event so a starved fleet's burn alert has a
    cause to point at.
  - **chaos**: ``freeze()`` (``run_diurnal_storm(cfg, frozen=True)``)
    stops all granting; the serving burn signal keeps demanding and
    the SLO alert fires — tests/test_soak.py pins both sides.

Thread-safety: one ``make_lock``-named mutex guards the ledger
(GuardedState-checked under KFTPU_LOCKCHECK=1). Evictor callbacks are
invoked AFTER the lock is released — the gang scheduler re-enters its
own ``_mu`` there, and the only cross-module order is the acyclic
gang._mu -> chipsched._mu (admission) with no reverse edge.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

from kubeflow_tpu.analysis.lockcheck import GuardedState, make_lock
from kubeflow_tpu.analysis.protocheck.eventlog import log_event

#: The platform priority ladder (ISSUE: serving > interactive > batch).
#: Values align with gang.PRIORITY_CLASSES so a gang claim's PodGroup
#: priority drops in unchanged: "system-critical" gangs rank with
#: serving, "high" with interactive, default batch at 0.
PRIORITY_SERVING = 2000
PRIORITY_INTERACTIVE = 1000
PRIORITY_BATCH = 0

#: Default Retry-After hint on a deny (seconds) — the caller's backoff
#: floor when nothing better (a cold-start EWMA) is known.
DEFAULT_RETRY_AFTER_S = 0.5


@dataclass(frozen=True)
class Grant:
    """A successful claim. ``slices`` is the placement ((slice index,
    chips) pairs); ``placement`` names the strategy that produced it
    (whole_slice / contiguous / spanning / none for 0-chip claims)."""

    key: str
    chips: int
    slices: tuple = ()
    placement: str = "none"
    borrowed: int = 0
    preempted: tuple = ()
    ok = True


@dataclass(frozen=True)
class Deny:
    """A refused claim: reason in {frozen, quota, capacity}, plus the
    Retry-After hint and the free count at decision time."""

    key: str
    chips: int
    reason: str
    retry_after_s: float = DEFAULT_RETRY_AFTER_S
    free: int = 0
    ok = False


@dataclass
class _Claim:
    key: str
    uid: str
    kind: str  # "gang" | "replica"
    tenant: str
    chips: int
    priority: int
    seq: int
    slices: tuple = ()
    borrowed: int = 0
    preemptible: bool = True


def _counter_dict() -> dict:
    return {
        "grants_total": 0,
        "denies_total": 0,
        "preemptions_total": 0,
        "quota_borrows_total": 0,
        "quota_reclaims_total": 0,
        "resumes_total": 0,
        "reclaimed_chips_total": 0,
        "double_count_avoided_chips_total": 0,
    }


class ChipScheduler:
    """The shared ledger (module docstring). Construct once per cluster
    (client.Platform wires one through GangScheduler, the training
    autoscaler, and every FleetScaler); standalone construction with a
    fixed ``capacity`` serves the unit drills."""

    def __init__(self, capacity: int = 0, chips_per_slice: int = 8,
                 capacity_fn=None, tracer_fn=None,
                 retry_after_s: float = DEFAULT_RETRY_AFTER_S):
        """capacity_fn() -> live chip capacity (the cluster's
        capacity_chips, which tests resize after construction); a fixed
        ``capacity`` otherwise. tracer_fn() -> tracer-or-None, read per
        event (races stop_tracing, same single-read idiom as gang.py)."""
        if chips_per_slice < 1:
            raise ValueError("chips_per_slice must be >= 1")
        self._capacity = capacity
        self._capacity_fn = capacity_fn
        self.chips_per_slice = chips_per_slice
        self.retry_after_s = retry_after_s
        self._tracer_fn = tracer_fn or (lambda: None)
        #: evictor(key, uid, chips, carrier, by) — registered by the
        #: gang scheduler; turns a committed preemption into the victim
        #: pods' FAILED(preempted) writes. Called WITHOUT _mu held.
        self.evictor = None
        self.metrics = _counter_dict()
        #: preempt -> resume latency samples, seconds (histogram source)
        self.preempt_to_resume_s: list[float] = []
        #: tenant -> share weight; empty == quotas unenforced
        self.shares: dict[str, float] = {}
        self._mu = make_lock("scheduler.ChipScheduler._mu")
        # claims IS the inventory; preempted_at carries the resume-latency
        # clock across a victim's restart (key survives the podgroup's
        # delete/recreate cycle — same ns/name, new uid).
        self._guarded = GuardedState(
            self._mu, claims={}, preempted_at={}, frozen=False, seq=0)

    # ------------------------------------------------------------ config

    @property
    def capacity_chips(self) -> int:
        return int(self._capacity_fn() if self._capacity_fn else self._capacity)

    def set_shares(self, shares: dict[str, float]) -> None:
        """Arm fair-share quotas. Weighted max-min: tenant i is entitled
        to capacity * w_i / sum(w). Tenants absent from the map are
        entitled to 0 — they run entirely on borrowed (reclaimable)
        chips."""
        if any(w <= 0 for w in shares.values()):
            raise ValueError("share weights must be positive")
        with self._mu:
            self.shares = dict(shares)

    def freeze(self) -> None:
        """Chaos: stop granting (sched_freeze). Held claims keep their
        chips; releases still work — the outage is admission-only."""
        with self._mu:
            self._guarded.frozen = True

    def thaw(self) -> None:
        with self._mu:
            self._guarded.frozen = False

    # ------------------------------------------------------------ claims

    def claim_gang(self, key: str, uid: str, chips: int, priority: int =
                   PRIORITY_BATCH, tenant: str = "default",
                   preempt: bool = False) -> Grant | Deny:
        """Place a whole gang (whole-slice-or-contiguous, spanning
        fallback). A same-key claim while one is held is denied —
        callers release (or grow_gang) first."""
        res, evictions = self._claim("gang", key, uid, chips, priority,
                                     tenant, preempt)
        self._run_evictions(evictions)
        return res

    def claim_replica(self, key: str, chips: int = 1, priority: int =
                      PRIORITY_SERVING, tenant: str = "serving",
                      preempt: bool = True) -> Grant | Deny:
        """Place one serving replica's chips (best-fit into the fullest
        slice that holds them). Preemption-then-grant is the default
        escalation: a serving scale-up that cannot fit evicts the
        lowest-priority/youngest batch gang (module docstring)."""
        res, evictions = self._claim("replica", key, "", chips, priority,
                                     tenant, preempt)
        self._run_evictions(evictions)
        return res

    def grow_gang(self, key: str, uid: str, extra: int) -> bool:
        """Add chips to a held gang claim (the late-member path). Pure
        capacity growth — no preemption, no quota borrow upgrade."""
        if extra <= 0:
            return True
        with self._mu:
            if self._guarded.frozen:
                return False
            c = self._guarded.claims.get(key)
            if c is None or c.uid != uid:
                return False
            placed = self._place_gang(self._slice_free(), extra)
            if placed is None:
                return False
            merged: dict[int, int] = dict(c.slices)
            for idx, n in placed[0]:
                merged[idx] = merged.get(idx, 0) + n
            c.slices = tuple(sorted(merged.items()))
            c.chips += extra
            self.metrics["grants_total"] += 1
            log_event("ledger", "sched", "grow", key=key, chips=c.chips,
                      extra=extra, capacity=self.capacity_chips,
                      free=self._free_locked())
            return True

    def release(self, key: str, uid: str = "") -> int:
        """Return a claim's chips to the pool. ``uid`` guards gang
        releases across delete/recreate races (gang.py's ledger
        contract); empty matches any. Returns chips freed (0 if the
        claim was absent or uid-mismatched)."""
        with self._mu:
            c = self._guarded.claims.get(key)
            if c is None or (uid and c.uid and c.uid != uid):
                return 0
            self._guarded.claims.pop(key)
            self.metrics["reclaimed_chips_total"] += c.chips
            log_event("ledger", "sched", "release", key=key,
                      chips=c.chips, capacity=self.capacity_chips,
                      free=self._free_locked())
            return c.chips

    def audit(self) -> dict:
        """Chip-conservation audit — the drill suites call this after a
        storm. Asserts, under the ledger lock: every claim's slice
        placement sums to exactly its chips, no slice is oversubscribed,
        and the per-slice free chips account for every held chip (so a
        lost or double-counted grant cannot hide). Returns the audited
        figures for the caller's own asserts."""
        with self._mu:
            cap = self.capacity_chips
            claims = self._guarded.claims
            for c in claims.values():
                placed = sum(k for _, k in c.slices)
                assert placed == c.chips, (
                    f"ledger audit: claim {c.key!r} holds {c.chips} "
                    f"chips but its slices sum to {placed}")
            slice_free = self._slice_free()
            assert min(slice_free, default=0) >= 0, (
                f"ledger audit: slice oversubscribed: {slice_free}")
            held = sum(c.chips for c in claims.values())
            assert sum(slice_free) == cap - held, (
                f"ledger audit: chips not conserved: per-slice free "
                f"{slice_free} != capacity {cap} - held {held}")
            return {"capacity": cap, "held": held,
                    "free": cap - held, "claims": len(claims),
                    "slice_free": slice_free}

    # ------------------------------------------------------------- views

    def free_chips(self) -> int:
        with self._mu:
            return self._free_locked()

    def used_chips(self) -> int:
        with self._mu:
            return sum(c.chips for c in self._guarded.claims.values())

    def held(self, key: str) -> bool:
        with self._mu:
            return key in self._guarded.claims

    def tenant_usage(self) -> dict[str, int]:
        with self._mu:
            out: dict[str, int] = {}
            for c in self._guarded.claims.values():
                out[c.tenant] = out.get(c.tenant, 0) + c.chips
            return out

    def entitlements(self) -> dict[str, int]:
        """tenant -> entitled chips under the armed shares (empty when
        quotas are unenforced)."""
        with self._mu:
            return self._entitlements_locked()

    def note_double_count_avoided(self, chips: int) -> None:
        """The race-fix witness: chips a pending gang ALREADY holds in
        the ledger, which the old paired free/demand reads would have
        counted twice (once as demand, once as used). The combined
        snapshot skips them — and counts what it skipped."""
        if chips > 0:
            with self._mu:
                self.metrics["double_count_avoided_chips_total"] += chips

    def snapshot(self) -> dict:
        """One consistent view (report.py / /metrics / /debug/sched)."""
        with self._mu:
            cap = self.capacity_chips
            free = self._slice_free()
            claims = [
                {
                    "key": c.key, "kind": c.kind, "tenant": c.tenant,
                    "chips": c.chips, "priority": c.priority,
                    # JSON-native pairs: /debug/sched consumers must
                    # compare equal to a direct build (surface agreement)
                    "slices": [list(s) for s in c.slices],
                    "borrowed": c.borrowed,
                    "seq": c.seq,
                }
                for c in sorted(self._guarded.claims.values(),
                                key=lambda c: c.seq)
            ]
            usage: dict[str, int] = {}
            borrowed: dict[str, int] = {}
            for c in self._guarded.claims.values():
                usage[c.tenant] = usage.get(c.tenant, 0) + c.chips
                if c.borrowed:
                    borrowed[c.tenant] = borrowed.get(c.tenant, 0) + c.borrowed
            ents = self._entitlements_locked()
            tenants = {
                t: {
                    "share": self.shares.get(t, 0.0),
                    "entitled_chips": ents.get(t, 0),
                    "used_chips": usage.get(t, 0),
                    "borrowed_chips": borrowed.get(t, 0),
                }
                for t in sorted(set(self.shares) | set(usage))
            }
            return {
                "capacity_chips": cap,
                "chips_per_slice": self.chips_per_slice,
                "used_chips": sum(c.chips
                                  for c in self._guarded.claims.values()),
                "free_chips": max(0, sum(free)),
                "slice_free": list(free),
                "frozen": self._guarded.frozen,
                "quota_enforced": bool(self.shares),
                "claims": claims,
                "tenants": tenants,
                "metrics": dict(self.metrics),
                "preempt_to_resume_s": list(self.preempt_to_resume_s),
            }

    # ---------------------------------------------------------- internals

    def _free_locked(self) -> int:
        return self.capacity_chips - sum(
            c.chips for c in self._guarded.claims.values())

    def _slice_free(self, claims=None) -> list[int]:
        """Free chips per slice. The last slice may be partial when
        capacity is not a slice multiple."""
        cap = self.capacity_chips
        cps = self.chips_per_slice
        n = max(1, -(-cap // cps)) if cap > 0 else 1
        free = [max(0, min(cps, cap - i * cps)) for i in range(n)]
        source = self._guarded.claims if claims is None else claims
        for c in source.values():
            for idx, k in c.slices:
                if idx < len(free):
                    free[idx] -= k
        return free

    def _place_gang(self, free: list[int], chips: int):
        """((slice, chips) pairs, strategy) or None. Whole slices for
        slice-multiple gangs, else contiguous within one slice (best
        fit), else span slices in order — admission stays a total-free
        predicate (module docstring)."""
        cps = self.chips_per_slice
        if chips >= cps and chips % cps == 0:
            whole = [i for i, f in enumerate(free) if f == cps]
            need = chips // cps
            if len(whole) >= need:
                return tuple((i, cps) for i in whole[:need]), "whole_slice"
        if chips <= cps:
            fits = [i for i, f in enumerate(free) if f >= chips]
            if fits:
                best = min(fits, key=lambda i: free[i])
                return ((best, chips),), "contiguous"
        if sum(f for f in free if f > 0) >= chips:
            placed, left = [], chips
            for i, f in enumerate(free):
                if left <= 0:
                    break
                take = min(max(0, f), left)
                if take:
                    placed.append((i, take))
                    left -= take
            if left <= 0:
                return tuple(placed), "spanning"
        return None

    def _place_replica(self, free: list[int], chips: int):
        """Best-fit: the FULLEST slice that still holds the replica —
        dense packing keeps whole slices free for gangs."""
        fits = [i for i, f in enumerate(free) if f >= chips]
        if fits:
            best = min(fits, key=lambda i: free[i])
            return ((best, chips),), "contiguous"
        # a replica wider than any single slice's free chips spans
        return self._place_gang(free, chips)

    def _entitlements_locked(self) -> dict[str, int]:
        if not self.shares:
            return {}
        total = sum(self.shares.values())
        cap = self.capacity_chips
        return {t: int(cap * w / total) for t, w in self.shares.items()}

    def _tenant_used_locked(self, tenant: str, claims) -> int:
        return sum(c.chips for c in claims.values() if c.tenant == tenant)

    def _claim(self, kind, key, uid, chips, priority, tenant, preempt):
        """The one admission path. Returns (Grant|Deny, evictions) where
        evictions are executed by the caller AFTER _mu is released."""
        tracer = self._tracer_fn()
        with self._mu:
            if self._guarded.frozen:
                return self._deny(tracer, key, chips, tenant, "frozen"), ()
            claims = self._guarded.claims
            if key in claims:
                # double-claim: the ledger is the single source — a
                # caller that lost track must release first
                return self._deny(tracer, key, chips, tenant,
                                  "capacity"), ()
            # quota: entitlement under the armed shares; over-entitlement
            # chips are a borrow, and borrowers never preempt
            borrowed = 0
            ents = self._entitlements_locked()
            if ents:
                ent = ents.get(tenant, 0)
                used_t = self._tenant_used_locked(tenant, claims)
                borrowed = max(0, min(chips, used_t + chips - ent))
            place = (self._place_gang if kind == "gang"
                     else self._place_replica)
            placed = place(self._slice_free(), chips) if chips > 0 else ((), "none")
            evict_plan: list[_Claim] = []
            reclaims = 0
            if placed is None and preempt and borrowed == 0:
                # feasibility on a SCRATCH copy first: an infeasible
                # preemption must not thrash victims through restarts
                scratch = dict(claims)
                for v in self._victims_locked(priority, scratch):
                    scratch.pop(v.key)
                    evict_plan.append(v)
                    if v.borrowed:
                        reclaims += 1
                    placed = place(self._slice_free(scratch), chips)
                    if placed is not None:
                        break
                if placed is None:
                    evict_plan, reclaims = [], 0
            if placed is None:
                # a borrower's only escalation would be preemption, and
                # borrowers never preempt: that refusal is a QUOTA deny
                reason = "quota" if borrowed else "capacity"
                return self._deny(tracer, key, chips, tenant, reason), ()
            evictions = []
            for v in evict_plan:
                claims.pop(v.key, None)
                self._guarded.preempted_at[v.key] = time.monotonic()
                self.metrics["preemptions_total"] += 1
                self.metrics["reclaimed_chips_total"] += v.chips
                carrier = ""
                if tracer is not None:
                    sp = tracer.event(
                        "sched.preempt", parent=None, victim=v.key,
                        chips=v.chips, by=key, tenant=v.tenant,
                        victim_priority=v.priority, priority=priority,
                        reclaim=bool(v.borrowed))
                    ctx = sp.context
                    carrier = ctx.to_header() if ctx is not None else ""
                evictions.append((v.key, v.uid, v.chips, carrier, key))
            self.metrics["quota_reclaims_total"] += reclaims
            self._guarded.seq += 1
            claims[key] = _Claim(
                key=key, uid=uid, kind=kind, tenant=tenant, chips=chips,
                priority=priority, seq=self._guarded.seq,
                slices=placed[0], borrowed=borrowed)
            self.metrics["grants_total"] += 1
            if borrowed:
                self.metrics["quota_borrows_total"] += 1
            t0 = self._guarded.preempted_at.pop(key, None)
            if t0 is not None and kind == "gang":
                self.preempt_to_resume_s.append(time.monotonic() - t0)
                self.metrics["resumes_total"] += 1
            log_event("ledger", "sched", "grant", key=key,
                      chips=chips, borrowed=borrowed,
                      capacity=self.capacity_chips,
                      free=self._free_locked(),
                      evicted=[v.key for v in evict_plan])
            return Grant(key=key, chips=chips, slices=placed[0],
                         placement=placed[1], borrowed=borrowed,
                         preempted=tuple(v.key for v in evict_plan)), \
                tuple(evictions)

    def _victims_locked(self, priority: int, claims: dict):
        """Preemption candidates in eviction order: gang claims strictly
        below the claimant's priority, plus borrowed gang claims at-or-
        below it (quota reclaim). Lowest priority first, youngest first
        within a level — least sunk work lost (gang.py's rule)."""
        out = [
            c for c in claims.values()
            if c.kind == "gang" and c.preemptible
            and (c.priority < priority
                 or (c.borrowed > 0 and c.priority <= priority))
        ]
        out.sort(key=lambda c: c.seq, reverse=True)
        out.sort(key=lambda c: c.priority)
        return out

    def _deny(self, tracer, key, chips, tenant, reason) -> Deny:
        self.metrics["denies_total"] += 1
        free = self._free_locked()
        if tracer is not None:
            tracer.event("sched.deny", parent=None, key=key, chips=chips,
                         tenant=tenant, reason=reason, free=free,
                         retry_after_s=self.retry_after_s)
        return Deny(key=key, chips=chips, reason=reason,
                    retry_after_s=self.retry_after_s, free=max(0, free))

    def _run_evictions(self, evictions) -> None:
        # outside _mu: the evictor re-enters the gang scheduler's lock
        for key, uid, chips, carrier, by in evictions:
            if self.evictor is not None:
                self.evictor(key, uid, chips, carrier, by=by)
