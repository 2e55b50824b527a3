"""Continuous-batching sweep service over slot-recycling fleet engines.

The serving analogue of :class:`repro.serve.batching.ContinuousBatcher`, one
level up: instead of token sequences in decode slots, the unit of work is a
whole cluster configuration (a :class:`~repro.core.scu.engine.FleetConfig`)
and the step is one scheduling round of every
:class:`~repro.core.scu.engine.SlotFleet` -- the batched array program over
every occupied slot.  Finished jobs free their lanes and queued jobs are
admitted at the next round, so the fleets stay warm across a stream of
heterogeneous sweep jobs instead of draining to idle between fixed batches.

Fault domains
-------------
The service runs ``n_domains`` slot fleets (default 1) as independent
**fault domains** -- the serving analogue of the voltage islands / cluster
groups that :mod:`repro.core.scu.faults` models with its domain-scoped
events (correlated droop, SCU blackout, domain-wide bank blackout).  A
fault that takes out one domain takes out every slot in it at once, so
recovery must be *topological*: re-route the work somewhere else and stop
feeding the sick domain, instead of retrying into the blast radius.  Every
queue, admission rule and health record is per domain; a one-domain
service is simply the ``n_domains == 1`` case.

Time axis and latency
---------------------
All latency accounting is in **scheduler rounds** (calls to :meth:`step`),
the machine-independent clock shared with :mod:`repro.serve.arrivals`.  A
job's latency spans submit to finish inclusive; its queue wait is the
submit-to-admission span.  Wall-clock enters only in the benchmark layer,
as same-run throughput ratios.

Backpressure (documented choice: **reject**)
--------------------------------------------
``queue_limit`` bounds the sum of the per-domain queues; :meth:`submit` on
a full service raises :class:`QueueFull` deterministically -- the caller
decides whether to retry, drop, or throttle (``try_submit`` is the
non-raising variant).  Rejecting keeps the service loop non-blocking and
the behaviour identical on every machine, which blocking-with-timeout
would not.  Retry requeues bypass the bound: a retried job already owns
its place in the system.

Router
------
New jobs are placed onto a domain at submit time by a pluggable policy
(``placement``):

``least-loaded``
    the admissible domain with the smallest load (queued + in-flight
    jobs), ties broken by higher health score then lower domain id.
``round-robin``
    cycles through the admissible domains in index order.

Admissible means *healthy* domains when any exist, else *probation*
domains, else every domain (all quarantined -- jobs queue and wait out the
cooldown; a job already queued on a domain that is quarantined later also
waits, by design: placement never reshuffles).  Rerouted retries join the
tail of their new domain's queue.

Admission rules (per domain)
----------------------------
``admission="continuous"`` (default) admits into any free slot;
``"drain"`` -- the fixed-batch baseline -- admits into domain ``d`` only
once its fleet is empty.  ``admission_order="fifo"`` (default) takes the
domain's queue in arrival order; ``"priority"`` picks its best job:
highest effective priority first, ties broken by earlier submission then
lower job id.  ``aging_rounds=k`` bumps a waiting job's effective priority
by one every ``k`` queued rounds, so low-priority work cannot starve.
``preempt=True`` (requires priority mode and a checkpoint-capable job)
lets a queued job with strictly higher effective priority suspend the
lowest-priority running member of its domain to a checkpoint and take its
lane; the victim re-enters the queue with its checkpoint and resumes later
(``faults="carry"`` -- preemption continues the same attempt, losing zero
cycles).

Correctness
-----------
Admission timing is invisible to co-resident jobs (see
:class:`~repro.core.scu.engine.SlotFleet`): every job's ``ClusterStats`` is
bit-exact against a sequential ``Cluster.run()`` of the same config, no
matter when it was admitted or what shared a step with it.  A job that
hits its ``max_cycles`` cap (or trips a watchdog) fails alone -- same
message ``Cluster.run`` would raise, carried on ``SweepJob.error`` -- and
its lanes are recycled.

Recovery (opt-in via :class:`RetryPolicy`)
------------------------------------------
Clusters are single-use, so a failed attempt cannot be re-run in place;
retryable jobs are submitted with a ``factory(attempt) -> FleetConfig``
callable that rebuilds a fresh config per attempt (attempt numbers start
at 1).  On failure the service logs the attempt in ``SweepJob.fault_log``
(with ``"domain"`` blame) and re-queues the job after an exponential
backoff in scheduler rounds (``backoff_rounds * backoff_factor **
(attempts - 1)``); after ``degrade_after`` failed attempts it switches to
``fallback_factory`` when provided (graceful degradation, e.g. the ``scu``
policy falling back to ``sw`` spin barriers -- marked on
``SweepJob.degraded``).  With ``RetryPolicy(reroute=True)`` the retry is
resubmitted to a *different healthy* domain when one exists (counted in
:attr:`FleetService.reroutes`), decided when the backoff expires against
the health state of that round; otherwise it retries in place.  A job that
exhausts ``max_attempts`` (or has no way to rebuild a config) goes
**terminal**: ``state == "failed"``, ``error`` set, counted in
``finished`` -- so :meth:`run_until_drained` terminates instead of
spinning on permanently-failed work.

Checkpointing and live migration (opt-in via :class:`CheckpointPolicy`)
-----------------------------------------------------------------------
With ``checkpoint=CheckpointPolicy(interval_rounds=k)`` the service
snapshots every running member each ``k`` rounds of its attempt, at the
round boundary (a full-step boundary -- the only place the engine's
recovery contract allows).  The checkpoint is deterministic and bit-exact
(:mod:`repro.core.scu.checkpoint`); a member running generator-backed
programs is silently non-checkpointable and keeps the restart-only
behaviour -- never a wrong resume.  A failed attempt that has a checkpoint
retries by **resuming** from it -- on whatever domain the reroute logic
picks, which makes it a live migration (counted in
:attr:`FleetService.migrations`) -- with the attempt-scoped
:class:`~repro.core.scu.faults.FaultPlan` stripped (the transient-fault
model: the sick domain's fault schedule does not follow the job), so its
wasted cycles shrink from the whole attempt to at most one checkpoint
interval plus the detection lag.  If the resumed attempt fails again the
checkpoint is considered poisoned (it captured already-corrupted state,
e.g. a core whose wake was already lost) and dropped -- the next retry
rebuilds from scratch.  :meth:`suspend_all` checkpoints and evicts every
running member at once (service restart): the service object -- queues,
backoff list and checkpoints -- is the serialized in-flight state, and
subsequent :meth:`step` calls resume the whole sweep bit-exactly.

Fault injection is tied to domains through the optional ``inject`` hook:
``inject(domain, config) -> config`` runs at admission for every fresh
attempt, letting a chaos harness (``benchmarks/fault_domains.py``) arm
:class:`~repro.core.scu.faults.FaultPlan`\\ s on the configs a particular
domain executes -- which is exactly why rerouting escapes them.
Checkpoint-resumed admissions bypass it (a restore continues an old
attempt).

Health + circuit breaker
------------------------
Each domain carries a :class:`DomainHealth` record: a rolling window of
attempt outcomes plus running totals of watchdog trips, terminal failures
and wasted cycles.  An optional :class:`BreakerPolicy` drives a
deterministic, round-counted state machine per domain::

      healthy --(>= probation_after failures in window)--> probation
    probation --(any failure)--> quarantined        [cooldown_rounds]
    probation --(probe_successes consecutive successes)--> healthy
  quarantined --(cooldown elapsed)--> probation     [probe admissions]

``probation`` is probe mode: at most one job in flight, so a still-sick
domain burns one probe per window instead of a full fleet of jobs.
``quarantined`` admits nothing until the cooldown expires.  All
transitions happen at round boundaries from round-counted state -- no
wall-clock anywhere -- so a run is bit-reproducible.

Watchdog escalation
-------------------
The chain is slot -> domain -> router: a cluster-level watchdog first
force-releases parked waiters (slot-level recovery, invisible up here);
a hard trip surfaces as the member's ``DeadlockError`` whose
``"watchdog tripped"`` message carries the :class:`WaitForGraph` dump.
The service records the trip against the domain's health (``fault_log``
entries carry ``"domain"`` and ``"watchdog"``), and the breaker escalates
the domain to quarantine.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Tuple

from repro.core.scu.checkpoint import NotCheckpointable
from repro.core.scu.engine import ClusterStats, FleetConfig, SlotFleet
from repro.core.scu.trace import TraceProgram

__all__ = [
    "SweepJob",
    "QueueFull",
    "RetryPolicy",
    "CheckpointPolicy",
    "BreakerPolicy",
    "DomainHealth",
    "FleetService",
]

HEALTHY, PROBATION, QUARANTINED = "healthy", "probation", "quarantined"
HEALTH_WINDOW = 16  # attempt outcomes kept per DomainHealth


def _fresh_traces(config: FleetConfig) -> FleetConfig:
    """Clone any single-use :class:`TraceProgram`s in a config.

    Trace programs are consumed on first call (mirroring ``FaultPlan``), but
    a retry ``factory(attempt)`` commonly rebuilds only the cluster and
    reuses the lowered tables -- lowering is the expensive part.  Cloning at
    admission-config construction keeps that pattern valid: every attempt
    gets fresh cursors over the same immutable row tables.
    """
    if not any(isinstance(p, TraceProgram) for p in config.programs):
        return config
    return dataclasses.replace(
        config,
        programs=[
            p.clone() if isinstance(p, TraceProgram) else p
            for p in config.programs
        ],
    )


class QueueFull(RuntimeError):
    """Raised by :meth:`FleetService.submit` when the bounded queue is full."""


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Failure-recovery knobs for :class:`FleetService`.

    ``max_attempts`` caps total attempts per job (1 = no retry);
    ``backoff_rounds`` / ``backoff_factor`` shape the exponential backoff
    delay (in scheduler rounds) before attempt ``k+1``:
    ``backoff_rounds * backoff_factor ** (k - 1)``.  ``degrade_after``
    (optional) switches the job to its ``fallback_factory`` once that many
    attempts have failed -- graceful degradation to a more robust (slower)
    configuration instead of repeating the failing one forever.
    ``reroute`` asks for the retry to land on a *different healthy fault
    domain* when one exists (falling back to in-place retry otherwise, and
    always with a single domain).
    """

    max_attempts: int = 3
    backoff_rounds: int = 1
    backoff_factor: int = 2
    degrade_after: Optional[int] = None
    reroute: bool = False

    def __post_init__(self):
        if self.max_attempts < 1:
            raise ValueError(f"max_attempts must be >= 1, got {self.max_attempts}")
        if self.backoff_rounds < 0:
            raise ValueError(f"backoff_rounds must be >= 0, got {self.backoff_rounds}")
        if self.backoff_factor < 1:
            raise ValueError(f"backoff_factor must be >= 1, got {self.backoff_factor}")
        if self.degrade_after is not None and self.degrade_after < 1:
            raise ValueError(f"degrade_after must be >= 1, got {self.degrade_after}")


@dataclasses.dataclass(frozen=True)
class CheckpointPolicy:
    """Periodic-checkpoint knob for :class:`FleetService`.

    Every running member is snapshotted each ``interval_rounds`` rounds of
    its current attempt (at the round boundary).  Smaller intervals bound
    the worst-case recovery loss tighter (a failed attempt resumes from
    its last checkpoint, so at most one interval of progress is redone) at
    the cost of more frequent captures."""

    interval_rounds: int = 8

    def __post_init__(self):
        if self.interval_rounds < 1:
            raise ValueError(
                f"interval_rounds must be >= 1, got {self.interval_rounds}"
            )


@dataclasses.dataclass(frozen=True)
class BreakerPolicy:
    """Deterministic circuit-breaker knobs for :class:`FleetService`.

    ``probation_after`` window failures drop a healthy domain to
    probation; any failure on probation quarantines it for
    ``cooldown_rounds`` scheduler rounds, after which it re-enters
    probation (probe mode: one job in flight); ``probe_successes``
    consecutive successes restore it to healthy."""

    probation_after: int = 2
    cooldown_rounds: int = 8
    probe_successes: int = 2

    def __post_init__(self):
        if self.probation_after < 1:
            raise ValueError(
                f"probation_after must be >= 1, got {self.probation_after}"
            )
        if self.cooldown_rounds < 1:
            raise ValueError(
                f"cooldown_rounds must be >= 1, got {self.cooldown_rounds}"
            )
        if self.probe_successes < 1:
            raise ValueError(
                f"probe_successes must be >= 1, got {self.probe_successes}"
            )


class DomainHealth:
    """Rolling health record for one fault domain.

    ``outcomes`` is a bounded window of recent attempt results (True =
    success); the running totals survive window eviction and feed the
    service-level metrics.  ``score`` is the window success fraction (1.0
    while empty -- a fresh domain is presumed healthy)."""

    def __init__(self, window: int = HEALTH_WINDOW):
        if window < 1:
            raise ValueError(f"health window must be >= 1, got {window}")
        self.window = window
        self.outcomes: Deque[bool] = deque(maxlen=window)
        self.watchdog_trips = 0
        self.terminal_failures = 0
        self.wasted_cycles = 0
        self.completed = 0
        self.failed_attempts = 0

    def record_success(self) -> None:
        self.outcomes.append(True)
        self.completed += 1

    def record_failure(self, wasted_cycles: int, watchdog: bool) -> None:
        self.outcomes.append(False)
        self.failed_attempts += 1
        self.wasted_cycles += wasted_cycles
        if watchdog:
            self.watchdog_trips += 1

    @property
    def score(self) -> float:
        if not self.outcomes:
            return 1.0
        return sum(self.outcomes) / len(self.outcomes)

    @property
    def window_failures(self) -> int:
        return len(self.outcomes) - sum(self.outcomes)


@dataclasses.dataclass
class SweepJob:
    """One sweep job's lifecycle record (filled in by the service).

    ``stats`` is a materialized snapshot -- safe to read after the job's
    slot has been recycled.  ``error`` is ``None`` on success, otherwise
    the timeout/deadlock message the sequential engine would have raised
    (terminal -- intermediate failures of retried attempts live in
    ``fault_log``).  ``state`` walks ``queued -> running`` and ends in
    ``done`` or ``failed``, with ``backoff -> queued -> running`` loops in
    between for retried attempts.  ``domain`` is the fault-domain (fleet)
    index the job is queued on or last ran on, set at submission.
    """

    job_id: int
    config: FleetConfig
    submitted_round: int
    admitted_round: Optional[int] = None
    finished_round: Optional[int] = None
    slot: Optional[int] = None
    domain: Optional[int] = None
    stats: Optional[ClusterStats] = None
    error: Optional[str] = None
    state: str = "queued"
    attempts: int = 0
    degraded: bool = False
    wasted_cycles: int = 0  # simulated cycles burnt by failed attempts
    fault_log: List[Dict] = dataclasses.field(default_factory=list)
    factory: Optional[Callable[[int], FleetConfig]] = dataclasses.field(
        default=None, repr=False
    )
    fallback_factory: Optional[Callable[[int], FleetConfig]] = dataclasses.field(
        default=None, repr=False
    )
    # -- checkpoint / priority state (see the module docstring) ------------
    priority: int = 0
    checkpoint: Optional[object] = dataclasses.field(default=None, repr=False)
    checkpoint_round: Optional[int] = None
    checkpoint_disabled: bool = False  # member is not checkpointable
    restore_pending: bool = False  # next admission restores the checkpoint
    resume_faults: object = "carry"  # forwarded to SlotFleet.restore
    resumed_attempt: bool = False  # current attempt began as a failure-resume
    preemptions: int = 0  # times this job was suspended by a higher priority
    attempt_admitted_round: Optional[int] = None  # checkpoint cadence anchor

    @property
    def done(self) -> bool:
        return self.finished_round is not None

    @property
    def failed(self) -> bool:
        return self.error is not None

    @property
    def queue_rounds(self) -> Optional[int]:
        """Rounds spent waiting for a slot (0 = admitted immediately)."""
        if self.admitted_round is None:
            return None
        return self.admitted_round - self.submitted_round

    @property
    def latency_rounds(self) -> Optional[int]:
        """Submit-to-finish span, inclusive of the finishing round."""
        if self.finished_round is None:
            return None
        return self.finished_round - self.submitted_round + 1


class FleetService:
    """Bounded-queue sweep service over ``n_domains`` warm
    :class:`SlotFleet`\\ s behind one health-aware router.

    Parameters
    ----------
    n_slots, slot_cores, banking_factor:
        Per-domain fleet geometry, passed through to :class:`SlotFleet`
        (jobs up to ``slot_cores`` cores fit; narrower jobs leave their
        slot's tail lanes idle, which the idle-lane accounting charges
        honestly).
    queue_limit:
        Bound over the sum of the per-domain queues; a full service
        **rejects** (:class:`QueueFull`).
    n_domains:
        Number of fault domains (independent fleets, uniform geometry).
    placement:
        ``"least-loaded"`` (default) or ``"round-robin"``; see the module
        docstring's Router section.
    admission:
        ``"continuous"`` (default) -- finished jobs free lanes mid-flight
        and queued jobs take them at the next round.  ``"drain"`` -- the
        fixed-batch baseline: a domain only admits once *every* one of its
        slots has drained, exactly the utilization loss continuous
        batching removes.  Both modes run the identical engine, so
        measured deltas are scheduling policy, not implementation.
    admission_order:
        ``"fifo"`` (default) or ``"priority"``; see the module docstring's
        admission rules.
    aging_rounds:
        Optional starvation guard for priority mode: +1 effective priority
        per ``aging_rounds`` rounds spent queued.
    preempt:
        Priority mode only: let a strictly-higher-priority queued job
        suspend the lowest-priority running member of its domain to a
        checkpoint and take its lane.
    retry:
        Optional :class:`RetryPolicy`; ``None`` (default) keeps the
        fail-fast behaviour (first failure is terminal).
    breaker:
        Optional :class:`BreakerPolicy`; ``None`` disables quarantine
        (every domain stays ``healthy`` forever, health is still scored).
    checkpoint:
        Optional :class:`CheckpointPolicy`; enables periodic snapshots,
        resume-from-checkpoint retries and live migration.
    inject:
        Optional ``inject(domain, config) -> config`` hook applied at
        admission to every fresh attempt (chaos harness entry point).
    """

    ADMISSION_MODES = ("continuous", "drain")
    ADMISSION_ORDERS = ("fifo", "priority")
    PLACEMENTS = ("least-loaded", "round-robin")

    def __init__(
        self,
        n_slots: int,
        slot_cores: int,
        banking_factor: int = 2,
        queue_limit: int = 64,
        *,
        n_domains: int = 1,
        placement: str = "least-loaded",
        admission: str = "continuous",
        admission_order: str = "fifo",
        aging_rounds: Optional[int] = None,
        preempt: bool = False,
        retry: Optional[RetryPolicy] = None,
        breaker: Optional[BreakerPolicy] = None,
        checkpoint: Optional[CheckpointPolicy] = None,
        inject: Optional[Callable[[int, FleetConfig], FleetConfig]] = None,
    ):
        if n_domains < 1:
            raise ValueError(f"n_domains must be >= 1, got {n_domains}")
        if placement not in self.PLACEMENTS:
            raise ValueError(
                f"placement must be one of {self.PLACEMENTS}, got {placement!r}"
            )
        if admission not in self.ADMISSION_MODES:
            raise ValueError(
                f"admission must be one of {self.ADMISSION_MODES}, "
                f"got {admission!r}"
            )
        if admission_order not in self.ADMISSION_ORDERS:
            raise ValueError(
                f"admission_order must be one of {self.ADMISSION_ORDERS}, "
                f"got {admission_order!r}"
            )
        if preempt and admission_order != "priority":
            raise ValueError("preempt=True requires admission_order='priority'")
        if aging_rounds is not None and aging_rounds < 1:
            raise ValueError(f"aging_rounds must be >= 1, got {aging_rounds}")
        if queue_limit < 1:
            raise ValueError(f"queue_limit must be >= 1, got {queue_limit}")
        self.n_domains = n_domains
        self.fleets = [
            SlotFleet(n_slots, slot_cores, banking_factor)
            for _ in range(n_domains)
        ]
        self.queues: List[Deque[SweepJob]] = [deque() for _ in range(n_domains)]
        self.health = [DomainHealth() for _ in range(n_domains)]
        self.states = [HEALTHY] * n_domains
        self.queue_limit = queue_limit
        self.placement = placement
        self.admission = admission
        self.admission_order = admission_order
        self.aging_rounds = aging_rounds
        self.preempt = preempt
        self.retry = retry
        self.breaker = breaker
        self.checkpoint = checkpoint
        self.inject = inject
        self.round = 0  # completed step() calls == current round index
        self.finished: List[SweepJob] = []
        self.reroutes = 0
        self.quarantines = 0
        self.migrations = 0  # checkpoint-resumed reroutes to a new domain
        self.preemptions = 0  # member suspensions forced by priority
        self._cooldown_until = [0] * n_domains
        self._probe_streak = [0] * n_domains
        self._by_slot: List[Dict[int, SweepJob]] = [
            {} for _ in range(n_domains)
        ]
        # (eligible_round, job) pairs waiting out a retry backoff; re-queued
        # at the round they become eligible (bypassing queue_limit: a retry
        # never competes with fresh submissions for queue space, it already
        # owns its place in the system)
        self._backoff: List[Tuple[int, SweepJob]] = []
        self._rr = 0
        self._next_id = 0
        # lane-occupancy accounting (idle = not running a live job's core;
        # a narrow job's tail lanes count idle -- slot-width waste is real)
        self.lane_rounds = 0
        self.busy_lane_rounds = 0

    # ------------------------------------------------------------------ api
    def submit(
        self,
        config: Optional[FleetConfig] = None,
        *,
        factory: Optional[Callable[[int], FleetConfig]] = None,
        fallback_factory: Optional[Callable[[int], FleetConfig]] = None,
        priority: int = 0,
    ) -> SweepJob:
        """Enqueue a job onto a routed domain; raises :class:`QueueFull`
        when the queue bound is hit and ``ValueError`` on a config no
        fleet could ever admit (so the queues only ever hold admissible
        jobs).

        Pass exactly one of ``config`` (single-shot, non-rebuildable) or
        ``factory`` (``factory(attempt)`` builds a fresh config per
        attempt; attempt numbers start at 1).  ``fallback_factory`` is the
        degraded rebuild used after ``RetryPolicy.degrade_after`` failed
        attempts.  ``priority`` (higher = sooner) only matters under
        ``admission_order="priority"``."""
        if (config is None) == (factory is None):
            raise ValueError("submit: pass exactly one of config or factory")
        if config is None:
            config = _fresh_traces(factory(1))
        self.fleets[0].validate(config)
        if self.pending >= self.queue_limit:
            raise QueueFull(
                f"pool queue full ({self.queue_limit} jobs waiting); "
                "retry after a step() or raise queue_limit"
            )
        job = SweepJob(
            self._next_id, config, submitted_round=self.round,
            factory=factory, fallback_factory=fallback_factory,
            priority=priority,
        )
        self._next_id += 1
        self._enqueue(job, self._place())
        return job

    def try_submit(self, config: FleetConfig) -> Optional[SweepJob]:
        """Non-raising :meth:`submit`: returns ``None`` instead of raising
        :class:`QueueFull` (invalid configs still raise ``ValueError``)."""
        try:
            return self.submit(config)
        except QueueFull:
            return None

    def step(self) -> List[SweepJob]:
        """One service round: expire quarantine cooldowns, re-queue
        backoff-expired retries (rerouting them if asked), admit per
        domain, advance every occupied fleet one scheduling round, collect
        completions and update domain health/breaker state.  Returns the
        jobs that went terminal this round (stats materialized, failures
        marked); retried attempts are not returned -- they surface when
        they finally succeed or exhaust."""
        if self.checkpoint is not None:
            self._checkpoint_pass()
        self._expire_cooldowns()
        self._requeue_backoff()
        for d in range(self.n_domains):
            self._admit(d)
        done: List[SweepJob] = []
        busy_lanes = 0
        for d, fleet in enumerate(self.fleets):
            finished_cores = 0
            if fleet.occupied:
                for m in fleet.advance():
                    finished_cores += m.cluster.n_cores
                    job = self._collect(d, m)
                    if job is not None:
                        done.append(job)
            # occupancy snapshot of the round just executed (post-completion:
            # a lane freed this round was still busy during it, whether the
            # job went terminal or back to the retry queue)
            busy_lanes += sum(
                j.config.cluster.n_cores for j in self._by_slot[d].values()
            ) + finished_cores
        self.lane_rounds += sum(
            f.n_slots * f.slot_cores for f in self.fleets
        )
        self.busy_lane_rounds += busy_lanes
        self.round += 1
        return done

    def run_until_drained(self, max_rounds: int = 10_000_000) -> List[SweepJob]:
        """Step until every queue, the backoff list and every fleet are
        empty; returns all jobs finished along the way (terminally-failed
        jobs included -- they drain instead of spinning the loop).
        Quarantined domains drain too (their cooldowns are round-counted,
        so progress is guaranteed).  ``max_rounds`` guards against a
        caller submitting faster than the fleets can drain (raises
        RuntimeError)."""
        out: List[SweepJob] = []
        rounds = 0
        while (
            self.pending or self._backoff
            or any(f.occupied for f in self.fleets)
        ):
            out.extend(self.step())
            rounds += 1
            if rounds > max_rounds:
                raise RuntimeError(
                    f"run_until_drained: not drained after {max_rounds} rounds"
                )
        return out

    # ---------------------------------------------------------------- router
    def _admissible(self, exclude: Optional[int] = None) -> List[int]:
        """Domains the router may place onto, best tier first: healthy,
        else probation, else everything (all quarantined)."""
        for tier in (HEALTHY, PROBATION):
            ds = [
                d for d in range(self.n_domains)
                if self.states[d] == tier and d != exclude
            ]
            if ds:
                return ds
        return [d for d in range(self.n_domains) if d != exclude] or [exclude]

    def _place(self, exclude: Optional[int] = None) -> int:
        """Pick a target domain by the placement policy."""
        candidates = self._admissible(exclude)
        if self.placement == "round-robin":
            d = candidates[self._rr % len(candidates)]
            self._rr += 1
            return d
        # least-loaded: fewest queued+in-flight jobs, ties to the higher
        # health score, then the lower domain id -- fully deterministic
        return min(
            candidates,
            key=lambda d: (
                len(self.queues[d]) + len(self._by_slot[d]),
                -self.health[d].score,
                d,
            ),
        )

    def _enqueue(self, job: SweepJob, domain: int) -> None:
        job.domain = domain
        job.state = "queued"
        self.queues[domain].append(job)

    # --------------------------------------------------------------- recovery
    def _requeue_backoff(self) -> None:
        still: List[Tuple[int, SweepJob]] = []
        for eligible, job in self._backoff:
            if eligible > self.round:
                still.append((eligible, job))
                continue
            target = job.domain
            r = self.retry
            if r is not None and r.reroute:
                healthy_elsewhere = [
                    d for d in range(self.n_domains)
                    if self.states[d] == HEALTHY and d != job.domain
                ]
                if healthy_elsewhere:
                    target = self._place(exclude=job.domain)
                    if target != job.domain:
                        self.reroutes += 1
                        if job.restore_pending and job.checkpoint is not None:
                            # checkpoint rides along: live migration
                            self.migrations += 1
            self._enqueue(job, target)
        self._backoff = still

    def _maybe_retry(self, job: SweepJob) -> bool:
        """Schedule another attempt for a failed job if policy allows;
        returns False when the failure must go terminal.  The reroute
        decision is deferred to requeue time.  Prefers resuming from the
        job's last checkpoint (faults stripped -- a live migration when
        the reroute picks a new domain); a checkpoint that already backed
        one failed resume is poisoned and dropped."""
        r = self.retry
        if r is None or job.attempts >= r.max_attempts:
            return False
        if job.resumed_attempt:
            job.checkpoint = None
            job.checkpoint_round = None
        if job.checkpoint is not None:
            job.restore_pending = True
            job.resume_faults = None  # transient-fault model: strip the plan
        else:
            job.restore_pending = False
            cfg = self._next_config(job)
            if cfg is None:
                return False
            try:
                self.fleets[0].validate(cfg)
            except ValueError:
                return False  # a factory built an inadmissible config
            job.config = cfg
        job.slot = None
        job.state = "backoff"
        delay = r.backoff_rounds * (r.backoff_factor ** (job.attempts - 1))
        self._backoff.append((self.round + 1 + delay, job))
        return True

    def _next_config(self, job: SweepJob) -> Optional[FleetConfig]:
        """Build the config for the job's next attempt (clusters are
        single-use), or ``None`` when the job cannot be rebuilt."""
        nxt = job.attempts + 1
        r = self.retry
        if (
            r.degrade_after is not None
            and job.attempts >= r.degrade_after
            and job.fallback_factory is not None
        ):
            job.degraded = True
            return _fresh_traces(job.fallback_factory(nxt))
        if job.factory is not None:
            return _fresh_traces(job.factory(nxt))
        return None

    def _rebuild_config(self, job: SweepJob) -> Optional[FleetConfig]:
        """Restart rebuild for a suspended, non-checkpointable job."""
        factory = job.factory
        if job.degraded and job.fallback_factory is not None:
            factory = job.fallback_factory
        if factory is None:
            return None
        return _fresh_traces(factory(job.attempts + 1))

    # ----------------------------------------------------------- checkpoints
    def _checkpoint_pass(self) -> None:
        """Periodic snapshots at the round boundary (before this round's
        admissions and fleet advance -- a full-step boundary)."""
        iv = self.checkpoint.interval_rounds
        for d, fleet in enumerate(self.fleets):
            for slot, job in sorted(self._by_slot[d].items()):
                if job.checkpoint_disabled:
                    continue
                age = self.round - job.attempt_admitted_round
                if age <= 0 or age % iv != 0:
                    continue
                m = fleet.members[slot]
                if m.cluster.cycle >= m.max_cycles:
                    continue  # burned to its cap: timeout imminent, state junk
                try:
                    job.checkpoint = fleet.snapshot(slot)
                except NotCheckpointable:
                    job.checkpoint_disabled = True  # restart-only from here on
                else:
                    job.checkpoint_round = self.round

    def suspend_all(self) -> List[SweepJob]:
        """Checkpoint and evict every running member (service restart).

        After this call no member is in flight; each suspended job sits in
        its own domain's queue with its checkpoint and resumes
        (``faults="carry"`` -- the same attempt continues bit-exactly) on
        subsequent :meth:`step` calls.  Non-checkpointable members fall
        back to a restart requeue via their factory, or go terminal when
        they cannot be rebuilt -- never a wrong resume.  Returns the
        suspended jobs."""
        out: List[SweepJob] = []
        for d, fleet in enumerate(self.fleets):
            for slot in sorted(self._by_slot[d]):
                job = self._by_slot[d][slot]
                try:
                    job.checkpoint = fleet.suspend(slot)
                except NotCheckpointable:
                    job.checkpoint_disabled = True
                    m = fleet.members[slot]
                    job.wasted_cycles += m.cluster.cycle
                    self.health[d].wasted_cycles += m.cluster.cycle
                    m.done = True
                    fleet.free(slot)
                    job.restore_pending = False
                    cfg = self._rebuild_config(job)
                    if cfg is None:
                        job.error = (
                            "suspended: generator-backed program is not "
                            "checkpointable and the job has no factory to "
                            "rebuild from"
                        )
                        job.state = "failed"
                        job.slot = None
                        job.finished_round = self.round
                        self.health[d].terminal_failures += 1
                        self.finished.append(job)
                        continue
                    job.config = cfg
                else:
                    job.checkpoint_round = self.round
                    job.restore_pending = True
                    job.resume_faults = "carry"
                job.slot = None
                self._enqueue(job, d)
                out.append(job)
            self._by_slot[d].clear()
        return out

    # ------------------------------------------------------------- admission
    def _admit(self, d: int) -> None:
        """Admit from domain ``d``'s queue into its fleet under the
        breaker state and the admission rules."""
        if self.states[d] == QUARANTINED:
            return
        fleet, queue = self.fleets[d], self.queues[d]
        if self.admission == "drain" and fleet.occupied:
            return  # baseline: wait for the whole fleet to empty
        while queue:
            if self.states[d] == PROBATION and self._by_slot[d]:
                return  # probe mode: one job in flight
            idx = self._best_queued(d) if self.admission_order == "priority" else 0
            job = queue[idx]
            if not fleet.free_slots and (
                not self.preempt
                or self._preempt_victim(d, self._effective_priority(job)) is None
            ):
                return
            # a preemption victim joined the queue tail; the candidate's
            # index is unchanged
            del queue[idx]
            self._start(d, job)

    def _start(self, d: int, job: SweepJob) -> None:
        """Bind a queued job to a slot of domain ``d``: fresh admit, or
        checkpoint restore."""
        fleet = self.fleets[d]
        if job.restore_pending and job.checkpoint is not None:
            # live migration / restart / preemption resume: the checkpoint
            # IS the job state; the inject hook (fresh-attempt chaos) does
            # not apply
            slot = fleet.restore(job.checkpoint, faults=job.resume_faults)
            job.restore_pending = False
            # a failure-resume (stripped faults) marks the attempt so a
            # second failure poisons the checkpoint; a preemption resume
            # ("carry") continues the attempt unchanged
            if job.resume_faults is None:
                job.resumed_attempt = True
        else:
            if self.inject is not None:
                job.config = self.inject(d, job.config)
            slot = fleet.admit(job.config)
            job.resumed_attempt = False
        job.slot = slot
        job.state = "running"
        job.admitted_round = self.round
        job.attempt_admitted_round = self.round
        self._by_slot[d][slot] = job

    def _effective_priority(self, job: SweepJob) -> int:
        eff = job.priority
        if self.aging_rounds is not None:
            eff += (self.round - job.submitted_round) // self.aging_rounds
        return eff

    def _best_queued(self, d: int) -> int:
        """Index in domain ``d``'s queue of the next job under priority
        order: highest effective priority, then earliest submission, then
        lowest id."""
        queue = self.queues[d]
        return min(
            range(len(queue)),
            key=lambda i: (
                -self._effective_priority(queue[i]),
                queue[i].submitted_round,
                queue[i].job_id,
            ),
        )

    def _preempt_victim(self, d: int, eff: int) -> Optional[SweepJob]:
        """Suspend domain ``d``'s lowest-effective-priority running member
        strictly below ``eff`` (ties to the youngest submission then
        highest id -- the inverse of admission order), skipping
        non-checkpointable members.  Returns the victim, or ``None``."""
        victims = sorted(
            (
                j for j in self._by_slot[d].values()
                if not j.checkpoint_disabled
                and self._effective_priority(j) < eff
            ),
            key=lambda j: (
                self._effective_priority(j),
                -j.submitted_round,
                -j.job_id,
            ),
        )
        for victim in victims:
            try:
                ckpt = self.fleets[d].suspend(victim.slot)
            except NotCheckpointable:
                victim.checkpoint_disabled = True
                continue
            victim.checkpoint = ckpt
            victim.checkpoint_round = self.round
            victim.restore_pending = True
            victim.resume_faults = "carry"  # same attempt, zero lost cycles
            self._by_slot[d].pop(victim.slot)
            victim.slot = None
            victim.preemptions += 1
            self.preemptions += 1
            self._enqueue(victim, d)
            return victim
        return None

    # ------------------------------------------------------------ completion
    def _collect(self, d: int, m) -> Optional[SweepJob]:
        """Fold one finished member of domain ``d`` into job and domain
        state; returns the job if it went terminal."""
        job = self._by_slot[d].pop(m.index)
        job.attempts += 1
        self.fleets[d].free(m.index)
        if m.error is not None:
            watchdog = m.error.startswith("watchdog tripped")
            fail_cycle = m.cluster.cycle
            job.fault_log.append({
                "attempt": job.attempts,
                "round": self.round,
                "cycles": fail_cycle,
                "degraded": job.degraded,
                "domain": d,
                "watchdog": watchdog,
                "error": m.error.splitlines()[0],
            })
            retried = self._maybe_retry(job)
            # a checkpoint-resume redoes only the checkpoint -> failure
            # tail; a restart redoes the whole attempt
            resume_from = (
                job.checkpoint.cycle
                if retried and job.restore_pending else 0
            )
            waste = fail_cycle - resume_from
            job.wasted_cycles += waste
            self.health[d].record_failure(waste, watchdog)
            self._breaker_failure(d)
            if retried:
                return None
            job.error = m.error
            job.state = "failed"
            self.health[d].terminal_failures += 1
        else:
            job.state = "done"
            self.health[d].record_success()
            self._breaker_success(d)
        job.finished_round = self.round
        job.stats = m.cluster.stats
        self.finished.append(job)
        return job

    # --------------------------------------------------------------- breaker
    def _breaker_failure(self, d: int) -> None:
        b = self.breaker
        if b is None:
            return
        state = self.states[d]
        if state == PROBATION:
            self.states[d] = QUARANTINED
            self._cooldown_until[d] = self.round + 1 + b.cooldown_rounds
            self._probe_streak[d] = 0
            self.quarantines += 1
        elif (
            state == HEALTHY
            and self.health[d].window_failures >= b.probation_after
        ):
            self.states[d] = PROBATION
            self._probe_streak[d] = 0

    def _breaker_success(self, d: int) -> None:
        if self.breaker is None or self.states[d] != PROBATION:
            return
        self._probe_streak[d] += 1
        if self._probe_streak[d] >= self.breaker.probe_successes:
            self.states[d] = HEALTHY
            self._probe_streak[d] = 0

    def _expire_cooldowns(self) -> None:
        for d in range(self.n_domains):
            if (
                self.states[d] == QUARANTINED
                and self.round >= self._cooldown_until[d]
            ):
                self.states[d] = PROBATION
                self._probe_streak[d] = 0

    # --------------------------------------------------------------- metrics
    @property
    def pending(self) -> int:
        return sum(len(q) for q in self.queues)

    @property
    def active(self) -> int:
        return sum(len(s) for s in self._by_slot)

    @property
    def idle_lane_fraction(self) -> float:
        """Fraction of (lane, round) cells spent idle so far (0.0 before
        the first round).  The drain baseline's straggler tails and slot
        fragmentation both land here."""
        if self.lane_rounds == 0:
            return 0.0
        return 1.0 - self.busy_lane_rounds / self.lane_rounds

    @property
    def watchdog_trips(self) -> int:
        return sum(h.watchdog_trips for h in self.health)

    @property
    def wasted_cycles(self) -> int:
        return sum(h.wasted_cycles for h in self.health)

    def domain_report(self) -> List[Dict]:
        """Deterministic per-domain health snapshot (benchmark surface)."""
        return [
            {
                "domain": d,
                "state": self.states[d],
                "score": self.health[d].score,
                "completed": self.health[d].completed,
                "failed_attempts": self.health[d].failed_attempts,
                "terminal_failures": self.health[d].terminal_failures,
                "watchdog_trips": self.health[d].watchdog_trips,
                "wasted_cycles": self.health[d].wasted_cycles,
            }
            for d in range(self.n_domains)
        ]
