"""Deadline/budget-constrained (DBC) adaptive scheduling — paper §3.

The schedule advisor periodically re-plans against live grid state:

1. *discovery*   — authorized, believed-up resources; under a Grid
   Information Service this is a TTL-cached, heartbeat-stale snapshot
   (``ResourceView.last_seen``), not ground truth;
2. *trading*     — price quotes / sealed bids from the trade server;
3. *rate model*  — jobs/second each resource sustains: roofline-seeded
   estimate refined by an EMA of measured completions (the paper's
   "historical information, including job consumption rate");
4. *selection*   — a pluggable ``Strategy`` resolved from the registry
   in ``repro.core.strategies`` by ``UserRequirements.strategy``.  The
   three classic Nimrod/G policies live there (byte-identical to the
   historical if/elif dispatch):

   * ``cost``          minimize G$ subject to the deadline: cheapest
                       resources first, just enough aggregate rate;
   * ``time``          minimize completion time subject to the budget:
                       add resources cheapest-per-job first while the
                       rate-weighted projected spend fits the budget;
   * ``conservative``  like ``cost`` but guarantees every unfinished job
                       a budget share before committing a dispatch;

   alongside the economy-aware zoo (``auction``, ``reputation``,
   ``adaptive``, ``scavenger``) — see the package docstrings.

As the deadline tightens the cost strategy buys more (and more expensive)
resources — exactly the paper's Figure 3 behaviour.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Set, Tuple

try:
    import numpy as np
except ImportError:          # pragma: no cover - numpy is a CI dep
    np = None

from repro.core.economy import Bid, BudgetLedger, TradeServer, UserRequirements
from repro.core.resources import ResourceDirectory, ResourceSpec
from repro.core.strategies import Strategy, StrategyContext, create
from repro.core.strategies import cost_per_job  # noqa: F401  (re-export)

HOUR = 3600.0


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    interval: float = 120.0          # seconds between advisor wakeups
    safety: float = 1.15             # aggregate-rate margin over the minimum
    straggler_factor: float = 2.5    # duplicate when elapsed > f * estimate
    max_attempts: int = 5
    rate_ema: float = 0.5            # weight of new measurement
    min_resources: int = 1
    # record every k-th tick into ExperimentReport.timeline (1 = every
    # tick, the historical behavior).  A 10k-job horizon-length run at
    # stride 1 holds O(ticks) tuples per broker; large-scale sweeps set
    # this to keep reports bounded without touching scheduling behavior
    timeline_stride: int = 1


@dataclasses.dataclass
class ResourceView:
    """Scheduler-local model of one resource.

    ``avail_slots`` is the capacity this broker can actually use: total
    slots minus slots occupied by *other* users' jobs.  The single-user
    engine never shrinks it (it owns the whole queue); under a shared
    grid the marketplace engines refresh it every tick so rate and cost
    projections reflect free capacity, not exclusive ownership."""
    spec: ResourceSpec
    est_job_seconds: float           # current duration estimate
    measured_rate: Optional[float] = None    # jobs/s EMA (full resource)
    completions: int = 0
    failures: int = 0
    suspected: bool = False
    avail_slots: Optional[int] = None        # None = all of spec.slots
    # when the liveness/membership half of this view was last fetched
    # from the information service (None = omniscient directory path);
    # everything the advisor believes about this resource is as-of here
    last_seen: Optional[float] = None

    def _avail_fraction(self) -> float:
        if self.avail_slots is None or self.spec.slots <= 0:
            return 1.0
        return max(0, min(self.avail_slots, self.spec.slots)) / self.spec.slots

    def rate(self) -> float:
        full = (self.measured_rate if self.measured_rate is not None
                else self.spec.slots / max(self.est_job_seconds, 1e-9))
        return full * self._avail_fraction()

    def observe_completion(self, duration: float, ema: float) -> None:
        r = self.spec.slots / max(duration, 1e-9)
        self.measured_rate = (r if self.measured_rate is None
                              else (1 - ema) * self.measured_rate + ema * r)
        self.est_job_seconds = self.spec.slots / max(self.rate(), 1e-12)
        self.completions += 1
        self.suspected = False


def views_from_gis(snapshot, est_seconds_base: float
                   ) -> Dict[str, "ResourceView"]:
    """Build the scheduler's resource views from a GIS snapshot — the
    discovery-first path a broker on the wire grid uses (it holds no
    directory, only what the information service answered).  Suspected
    entries carry their flag through, so the advisor deprioritizes them
    exactly as it does on the in-process grid."""
    views: Dict[str, ResourceView] = {}
    for name, e in sorted(snapshot.entries.items()):
        views[name] = ResourceView(
            spec=e.spec,
            est_job_seconds=est_seconds_base / max(e.spec.perf_factor,
                                                   1e-6),
            suspected=e.suspected,
            last_seen=snapshot.taken_at)
    return views


@dataclasses.dataclass
class AllocationDecision:
    allocate: List[str]
    release: List[str]
    projected_rate: float
    needed_rate: float
    projected_cost_per_job: float
    feasible_time: bool
    feasible_budget: bool


class ScheduleAdvisor:
    """The pluggable scheduling policy (the paper exposes exactly this
    seam: "a user could build an alternative scheduler by using these
    APIs").  Policy lives in a ``Strategy`` resolved from the registry;
    the advisor owns what every policy shares — live-view filtering,
    the needed-rate computation, the canonical ranking, the
    ``min_resources`` floor and the decision bookkeeping."""

    def __init__(self, cfg: SchedulerConfig, requirements: UserRequirements,
                 strategy: Optional[Strategy] = None):
        self.cfg = cfg
        self.req = requirements
        # an unregistered strategy string fails HERE, at broker build
        # time — not as a silent fall-through to the cost policy
        self.strategy = (strategy if strategy is not None
                         else create(requirements.strategy))
        self._secondary = None
        self._bank = None
        self._history = None
        self._gis_client = None
        self._trace = None
        self._track = ""
        # last canonical ranking, keyed on exactly the inputs the sort
        # consumes — prices move piecewise (peak windows, slot churn),
        # so consecutive re-plans usually share one ordering
        self._rank_cpj: Optional[Dict[str, float]] = None
        self._rank_held: Optional[Set[str]] = None
        self._rank_list: Optional[List[str]] = None
        # (live, rates, cpj) from the last decide, valid while the
        # caller's views-epoch and the exact views/prices dict objects
        # are unchanged (the board hands out one shared prices dict per
        # clean stretch, so identity is a real stamp, not an accident)
        self._lv_epoch: Optional[int] = None
        self._lv_views = None
        self._lv_prices = None
        self._lv = None

    def bind_telemetry(self, tracer, track: str) -> None:
        """Attach a ``repro.core.telemetry.Tracer``: whenever the
        allocation actually changes, ``decide`` counts it
        (``sched.replans``) and emits a ``sched``/``replan`` instant.
        Purely observational — the decision is computed identically
        with or without it."""
        self._trace = tracer
        self._track = track
        self._m_replans = tracer.metrics.counter("sched.replans")

    def bind_market(self, *, secondary=None, bank=None, history=None,
                    gis_client=None) -> None:
        """Attach the marketplace's economy hooks (resale book, grid
        bank, clearing history, GIS client) so strategies can consult
        them.  The single-user engine never calls this — every strategy
        must work with the hooks at None."""
        self._secondary = secondary
        self._bank = bank
        self._history = history
        self._gis_client = gis_client

    def retarget(self, requirements: UserRequirements) -> None:
        """Swap the user's requirements mid-run — the paper's steering
        interaction (deadline/budget can change at any time).  The next
        ``decide`` re-plans against the new deadline; nothing else is
        cached off the old object."""
        self.req = requirements

    # -- selection strategies ------------------------------------------------

    def decide(self, t: float, views: Dict[str, ResourceView],
               prices: Dict[str, float], remaining_jobs: int,
               ledger: BudgetLedger, current: Set[str],
               contracted: Optional[Set[str]] = None,
               views_epoch: Optional[int] = None
               ) -> AllocationDecision:
        """Re-plan the allocation.  ``prices`` must already be
        *effective* prices (a negotiated contract's locked price where
        one is active, the spot quote otherwise) — the advisor ranks
        contracts and spot offers in one ordering.  ``contracted``
        resources win cost ties: capacity already paid for by a
        negotiated contract should be drawn down first."""
        # One pass over the views computes everything the ranking and
        # the feasibility sums below re-derive per-name in the scalar
        # path: the free-capacity rate and the cost-per-job, each the
        # exact expression ``ResourceView.rate``/``cost_per_job`` uses
        # (a 1.0 avail fraction multiplies out bit-exactly).
        if (views_epoch is not None and views_epoch == self._lv_epoch
                and views is self._lv_views and prices is self._lv_prices):
            live, rates, cpj = self._lv
            return self._decide_tail(t, views, prices, remaining_jobs,
                                     ledger, current, contracted,
                                     live, rates, cpj)
        live: Dict[str, ResourceView] = {}
        rates: Dict[str, float] = {}
        cpj: Dict[str, float] = {}
        for n, v in views.items():
            if v.suspected:
                continue
            live[n] = v
            spec = v.spec
            slots = spec.slots
            est = v.est_job_seconds
            full = v.measured_rate
            if full is None:
                full = slots / max(est, 1e-9)
            av = v.avail_slots
            if av is None or slots <= 0:
                rates[n] = full
            else:
                if av > slots:
                    av = slots
                elif av < 0:
                    av = 0
                rates[n] = full * (av / slots)
            cpj[n] = prices[n] * spec.chips * est / HOUR
        if views_epoch is not None:
            self._lv_epoch = views_epoch
            self._lv_views = views
            self._lv_prices = prices
            self._lv = (live, rates, cpj)
        return self._decide_tail(t, views, prices, remaining_jobs, ledger,
                                 current, contracted, live, rates, cpj)

    def _decide_tail(self, t: float, views: Dict[str, ResourceView],
                     prices: Dict[str, float], remaining_jobs: int,
                     ledger: BudgetLedger, current: Set[str],
                     contracted: Optional[Set[str]],
                     live: Dict[str, ResourceView],
                     rates: Dict[str, float],
                     cpj: Dict[str, float]) -> AllocationDecision:
        """Everything after the per-view map build: ranking, strategy
        selection, the floor and the decision bookkeeping."""
        time_left = max(self.req.deadline - t, 1e-6)
        needed = self.cfg.safety * remaining_jobs / time_left

        held = contracted or set()
        if (self._rank_list is not None
                and (cpj is self._rank_cpj or cpj == self._rank_cpj)
                and held == self._rank_held):
            ranked = self._rank_list
        else:
            if np is not None and len(live) > 1:
                # one lexsort over (cpj, not-held, name) — the same
                # lexicographic key tuple, evaluated as three flat arrays
                names = list(live)
                order = np.lexsort((
                    np.array(names),
                    np.fromiter((n not in held for n in names),
                                dtype=bool, count=len(names)),
                    np.fromiter((cpj[n] for n in names),
                                dtype=np.float64, count=len(names))))
                ranked = [names[i] for i in order]
            else:
                ranked = sorted(
                    live, key=lambda n: (cpj[n], n not in held, n))
            # the cpj dict is rebuilt fresh every call and never mutated
            # after select(), so holding a reference is a valid stamp
            self._rank_cpj = cpj
            self._rank_held = set(held)
            self._rank_list = ranked
        if not ranked:   # transient: everything down/suspected — hold state
            return AllocationDecision(
                allocate=[], release=[], projected_rate=0.0,
                needed_rate=needed, projected_cost_per_job=math.inf,
                feasible_time=False, feasible_budget=False)

        # current/held/ranked pass by reference: every registered
        # strategy treats the context as read-only (select() builds its
        # own result set), and ``ranked`` may be the advisor's cached
        # ranking — a strategy that mutated it would corrupt the cache
        ctx = StrategyContext(
            t=t, req=self.req, cfg=self.cfg, views=live, prices=prices,
            remaining_jobs=remaining_jobs, ledger=ledger,
            needed_rate=needed, current=current, held=held,
            ranked=ranked, secondary=self._secondary,
            bank=self._bank, history=self._history,
            gis_client=self._gis_client, rates=rates, cpj=cpj)
        chosen = self.strategy.select(ctx)

        need = self.cfg.min_resources - len(chosen)
        if need > 0:
            # prefer resources with free capacity when topping up — the
            # stable zero-rate-last partition of ``ranked``, walked only
            # until the floor is met
            fallback: List[str] = []
            for n in ranked:
                if rates[n] > 0 and n not in chosen:
                    fallback.append(n)
                    if len(fallback) == need:
                        break
            if len(fallback) < need:
                for n in ranked:
                    if rates[n] <= 0 and n not in chosen:
                        fallback.append(n)
                        if len(fallback) == need:
                            break
            chosen |= set(fallback)

        rate = 0.0
        wsum = 0.0
        for n in chosen:
            r = rates[n]
            rate += r
            wsum += r * cpj[n]
        wcost = (wsum / rate) if rate > 0 else math.inf
        decision = AllocationDecision(
            allocate=sorted(chosen - current),
            release=sorted(current - chosen),
            projected_rate=rate,
            needed_rate=needed,
            projected_cost_per_job=wcost,
            feasible_time=rate + 1e-12 >= remaining_jobs / time_left,
            feasible_budget=(wcost * remaining_jobs <= ledger.remaining + 1e-9),
        )
        if self._trace is not None and (decision.allocate
                                        or decision.release):
            self._m_replans.inc()
            self._trace.instant(
                t, self._track, "sched", "replan",
                allocate=",".join(decision.allocate),
                release=",".join(decision.release),
                projected_rate=rate, needed_rate=needed,
                cost_per_job=(wcost if math.isfinite(wcost) else -1.0),
                remaining=remaining_jobs)
        return decision

    # -- per-dispatch budget guard -------------------------------------------

    def may_commit(self, est_cost: float, remaining_jobs: int,
                   ledger: BudgetLedger) -> bool:
        return self.strategy.may_commit(est_cost, remaining_jobs, ledger)


# ---------------------------------------------------------------------------
# contract mode (paper §3, "second method"): negotiate before running
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ContractQuote:
    feasible: bool
    est_completion: float            # absolute virtual time
    est_cost: float
    n_resources: int
    reserved: Tuple[int, ...] = ()   # reservation ids if accepted


def negotiate_contract(t: float, req: UserRequirements, n_jobs: int,
                       trade: TradeServer, views: Dict[str, ResourceView],
                       accept: bool = False,
                       accept_at: Optional[float] = None) -> ContractQuote:
    """Solicit bids, pick the cheapest feasible set, optionally lock it in
    with advance reservations.  The user can then proceed or renegotiate
    with a different deadline/budget (exactly the paper's protocol).

    ``accept_at`` is when the user actually signs (defaults to ``t``,
    i.e. on the spot).  A user who deliberates past a sealed bid's
    validity loses its price: the reservation locks at the live quote
    instead — an expired bid is re-quoted, never silently honored."""
    bids = trade.solicit_bids(
        t, req.user, lambda spec: views[spec.name].est_job_seconds
        if spec.name in views else 3600.0)
    time_left = max(req.deadline - t, 1e-6)
    needed = n_jobs / time_left

    chosen: List[Bid] = []
    acc = 0.0
    by_cpj = sorted(
        bids, key=lambda b: b.chip_hour_price * trade.directory.spec(
            b.resource).chips / max(b.est_rate, 1e-9))
    for b in by_cpj:
        if acc >= needed:
            break
        chosen.append(b)
        acc += b.est_rate / HOUR
    feasible_time = acc >= needed
    if acc <= 0:
        return ContractQuote(False, math.inf, math.inf, 0)
    completion = t + n_jobs / acc
    cost = 0.0
    for b in chosen:
        share = (b.est_rate / HOUR) / acc * n_jobs
        spec = trade.directory.spec(b.resource)
        # amortized per-job cost: the whole resource bills
        # chip_hour_price * chips per hour and sustains est_rate
        # jobs/hour, so one job costs price * chips / est_rate.
        # (est_rate already counts every slot — multiplying by
        # spec.slots again overstated the quote by the slot count and
        # made feasible contracts look budget-infeasible.)  This is the
        # resource-level price of the farm's chip-hours; note the
        # engine's per-dispatch settlement bills each concurrent job
        # the full chip complement, so on a slots>1 queue the two
        # conventions differ — everywhere both run today (gusto-style
        # testbeds) slots == 1 and they agree exactly.
        cost += share * b.chip_hour_price * spec.chips / max(b.est_rate, 1e-9)
    feasible = feasible_time and cost <= req.budget
    rids: Tuple[int, ...] = ()
    if feasible and accept:
        at = t if accept_at is None else accept_at
        # resale-backed bids (resale_rid != 0) price the quote but are
        # not reservable here: locking one in means buying the listing
        # on the secondary market, never reserving fresh capacity at
        # the all-in rate (that would pay the seller's premium to the
        # owner — or crash on a queue the listing already fills)
        rids = tuple(
            trade.reserve(
                b.resource, req.user, at, req.deadline, at,
                locked_price=(b.chip_hour_price
                              if at <= b.valid_until else None)
            ).reservation_id for b in chosen if not b.resale_rid)
    return ContractQuote(feasible, completion, cost, len(chosen), rids)
