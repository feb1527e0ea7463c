"""Multi-user grid marketplace (paper §3 + §7 GRACE).

Nimrod/G's premise is *distributed ownership*: many users, each with an
independent deadline/budget broker, competing for the same scattered
resources, with prices mediating demand.  ``Marketplace`` realizes that
experiment: N concurrent ``NimrodG`` engines — each with its own
``UserRequirements``, ``BudgetLedger`` and ``ScheduleAdvisor`` — run
against ONE shared ``ResourceDirectory``/``TradeServer`` on a single
``Simulator`` clock.  Trading runs through one ``TradeServer`` per
administrative domain (federated behind ``TradeFederation``), an
``AuctionHouse`` clears negotiated contracts between brokers and owners,
and every settlement is mirrored into the ``GridBank`` as the owning
domain's revenue.

What the shared grid changes versus the single-user engine:

* slot accounting is contention-safe — a broker's dispatch can lose the
  race for the last free slot (``SLOT_LOST``) and requeues without
  burning an attempt or suspecting the resource;
* owners quote demand-responsive prices (utilization-indexed multiplier,
  the GRACE supply-and-demand knob), so a crowded grid gets expensive
  and cost-minimizing brokers back off to off-peak/cheap machines;
* each broker reads *free* capacity (slots not held by rivals), not the
  resource's full rate;
* discovery runs through the hierarchical ``GridInformationService``:
  brokers plan against TTL-cached, heartbeat-stale snapshots, and with
  ``run(churn=True)`` whole sites leave and rejoin mid-run (in-flight
  jobs fail over, contracts are voided with breach rebates through the
  bank, the trade federation's membership tracks the GIS).

Everything unfolds in virtual time from seeded RNG streams: the entire
market run is exactly reproducible per seed.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core.accounting import GridBank
from repro.core.auctions import AuctionBroker, AuctionHouse
from repro.core.dispatcher import Dispatcher, SimulatedExecutor
from repro.core.economy import (PriceSchedule, TradeFederation, TradeServer,
                                UserRequirements)
from repro.core.gis import GridInformationService
from repro.core.jobs import JobSpec
from repro.core.parametric import NimrodG
from repro.core.persistence import left_sum
from repro.core.resources import (ResourceDirectory, ResourceSpec,
                                  gusto_like_testbed)
from repro.core.scheduler import SchedulerConfig
from repro.core.secondary import ClearingHistory, SecondaryMarket
from repro.core.simulator import ChurnProcess, FailureProcess, Simulator
from repro.core.strategies import strategy_class

HOUR = 3600.0


@dataclasses.dataclass(frozen=True)
class MarketUser:
    """One participant: their broker's knobs (paper's deadline + budget)."""
    name: str
    deadline: float                  # absolute virtual time
    budget: float                    # G$
    strategy: str = "cost"           # any name in repro.core.strategies
    n_jobs: int = 50
    est_seconds: float = 1800.0      # per-job runtime on perf_factor=1


@dataclasses.dataclass
class UserOutcome:
    """Per-user market result (the broker's report, condensed)."""
    user: str
    strategy: str
    n_jobs: int
    n_done: int
    completion_time: float
    spent: float
    budget: float
    met_deadline: bool
    within_budget: bool
    requeues: int
    slot_races_lost: int
    peak_allocation: int
    stall_reason: Optional[str]
    contracts_won: int = 0
    resource_losses: int = 0         # dispatches burned on dead resources

    def row(self) -> str:
        return (f"{self.user:12s} {self.strategy:12s} "
                f"{self.n_done:4d}/{self.n_jobs:<4d} "
                f"t={self.completion_time / HOUR:7.2f}h "
                f"spent={self.spent:9.2f}/{self.budget:<9.0f} "
                f"met={str(self.met_deadline):5s} "
                f"races_lost={self.slot_races_lost:3d} "
                f"requeues={self.requeues:3d} "
                f"burned={self.resource_losses:3d} "
                f"contracts={self.contracts_won:3d}")


@dataclasses.dataclass
class MarketReport:
    seed: int
    n_users: int
    n_resources: int
    outcomes: List[UserOutcome]
    total_jobs: int
    total_done: int
    total_spent: float
    slot_races_lost: int
    deadline_met_frac: float
    price_trace: List[Tuple[float, float]]   # (t, mean grid quote)
    contracts_struck: int = 0
    owner_revenue: Dict[str, float] = dataclasses.field(default_factory=dict)
    # information-layer / churn telemetry
    resource_losses: int = 0                 # dispatches burned on corpses
    evictions: int = 0                       # in-flight jobs failed over
    refunds: float = 0.0                     # G$ of contract-breach rebates
    churn_trace: List[Tuple[float, str, str]] = dataclasses.field(
        default_factory=list)                # (t, leave|join, site)
    gis_refreshes: int = 0                   # broker snapshot fetches
    # secondary-market telemetry (all zero when the market is off)
    resale_enabled: bool = False
    resales: int = 0                         # listings filled
    resale_volume: float = 0.0               # G$ of lumps seller-ward
    wasted_spend: float = 0.0                # G$ of idle/commitment fees

    def summary(self) -> str:
        lines = [f"marketplace seed={self.seed}: {self.n_users} users on "
                 f"{self.n_resources} resources — "
                 f"{self.total_done}/{self.total_jobs} jobs, "
                 f"{self.deadline_met_frac:.0%} deadlines met, "
                 f"spend={self.total_spent:.1f}G$, "
                 f"slot races lost={self.slot_races_lost}, "
                 f"contracts={self.contracts_struck}"]
        lines += ["  " + o.row() for o in self.outcomes]
        if self.owner_revenue:
            lines.append("  owner revenue: " + ", ".join(
                f"{o}={v:.1f}" for o, v in sorted(self.owner_revenue.items())))
        if self.churn_trace or self.resource_losses:
            lines.append(
                f"  churn: {len(self.churn_trace)} membership events, "
                f"{self.evictions} in-flight evictions, "
                f"{self.resource_losses} dispatches burned on stale views, "
                f"refunds={self.refunds:.1f}G$")
        if self.resale_enabled or self.wasted_spend:
            lines.append(
                f"  secondary: resale={'on' if self.resale_enabled else 'off'}"
                f", {self.resales} fills, volume={self.resale_volume:.1f}G$, "
                f"wasted-contract spend={self.wasted_spend:.1f}G$")
        return "\n".join(lines)

    def stable_repr(self) -> str:
        """Byte-stable serialization (repr floats are exact) for
        determinism checks: two same-seed runs must match exactly."""
        parts = [f"seed={self.seed};users={self.n_users};"
                 f"res={self.n_resources}"]
        for o in self.outcomes:
            parts.append(
                f"{o.user}|{o.strategy}|{o.n_done}/{o.n_jobs}"
                f"|t={o.completion_time!r}|spent={o.spent!r}"
                f"|met={o.met_deadline}|races={o.slot_races_lost}"
                f"|rq={o.requeues}|rl={o.resource_losses}"
                f"|peak={o.peak_allocation}"
                f"|stall={o.stall_reason}|contracts={o.contracts_won}")
        parts.append("revenue=" + ",".join(
            f"{o}:{v!r}" for o, v in sorted(self.owner_revenue.items())))
        parts.append(f"churn={self.churn_trace!r};ev={self.evictions}"
                     f";refunds={self.refunds!r}")
        if self.resale_enabled or self.resales or self.wasted_spend:
            # only emitted when the secondary market ran: default-market
            # serializations stay byte-identical to the pre-PR-5 ones
            parts.append(f"secondary={self.resale_enabled}"
                         f";fills={self.resales}"
                         f";vol={self.resale_volume!r}"
                         f";wasted={self.wasted_spend!r}")
        parts.append("trace=" + ",".join(
            f"({t!r},{p!r})" for t, p in self.price_trace))
        return "\n".join(parts)


class Marketplace:
    """N brokers, one grid, one clock.

    Each user gets their own dispatcher/executor (the paper's per-broker
    architecture) but all of them mutate the same directory status — the
    shared truth the slot race is fought over.
    """

    def __init__(self, specs: Optional[Sequence[ResourceSpec]] = None,
                 *, n_machines: int = 20, seed: int = 0,
                 demand_elasticity: float = 0.5,
                 spot_amplitude: float = 0.0,
                 dispatch_latency: float = 1.0,
                 noise_sigma: float = 0.1,
                 max_reservations_per_user: Optional[int] = None,
                 auction_round: float = HOUR,
                 auction_window: float = 2 * HOUR,
                 idle_discount: float = 0.25,
                 gis_ttl: float = 600.0,
                 heartbeat_interval: float = 300.0,
                 gis_suspect_after: int = 2,
                 churn_mean_uptime_h: float = 8.0,
                 churn_mean_downtime_h: float = 2.0,
                 churn_min_sites: int = 1,
                 churn_rebate: float = 0.25,
                 release_fee: float = 0.0,
                 resale: bool = False,
                 ask_fraction: float = 0.5,
                 discovery_gain: float = 0.0,
                 discovery_band: float = 0.5,
                 wire: str = "direct",
                 tracer=None):
        self.seed = seed
        # optional telemetry.Tracer: when set, every subsystem below is
        # bound to it (spans, instants, registry metrics); when None —
        # the default — no instrumentation site in the market fires
        self.tracer = tracer
        self._snap_tick = 0
        self.sim = Simulator()
        self.directory = ResourceDirectory()
        for spec in (specs if specs is not None
                     else gusto_like_testbed(n_machines, seed=seed)):
            self.directory.register(spec)
        self.schedules: Dict[str, PriceSchedule] = {
            name: PriceSchedule(self.directory.spec(name),
                                demand_elasticity=demand_elasticity,
                                spot_amplitude=spot_amplitude,
                                discovery_gain=discovery_gain,
                                discovery_band=discovery_band)
            for name in self.directory.all_names()}
        # the producer side of the economy: every settlement lands in
        # the bank as the owning domain's revenue
        self.bank = GridBank()
        if tracer is not None:
            self.bank.bind_telemetry(tracer)
        # one trade server per administrative domain, federated — the
        # cross-domain price board brokers arbitrage over.  Kwargs kept
        # so a site rejoining after churn gets an identical fresh server.
        self._server_kw = dict(
            max_reservations_per_user=max_reservations_per_user,
            bank=self.bank)
        self.trade = TradeFederation.from_directory(
            self.directory, self.schedules, **self._server_kw)
        # wire="loopback" re-plumbs every cross-domain call through the
        # protocol codec (repro.core.transport) — same objects, same
        # clock, byte-identical reports; the differential the real
        # multi-process deployment is certified against
        if wire not in ("direct", "loopback"):
            raise ValueError(f"wire must be 'direct' or 'loopback', "
                             f"got {wire!r}")
        self.wire = wire
        if wire == "loopback":
            from repro.core.transport import wrap_federation_loopback
            self.trade = wrap_federation_loopback(self.trade)
        # realized-trade price log: clearing rounds and resale fills
        # append here; schedules with discovery_gain > 0 learn from the
        # clearing rounds (fills are user-to-user and don't nudge)
        self.history = ClearingHistory()
        self.auction_house = AuctionHouse(
            self.trade, round_interval=auction_round,
            window=auction_window, idle_discount=idle_discount,
            history=self.history)
        if tracer is not None:
            self.auction_house.bind_telemetry(tracer)
        # secondary capacity market: with release_fee > 0 idle windows
        # handed back cost their holder the commitment fee; with resale
        # they can be listed and transferred to rival brokers instead
        self.secondary: Optional[SecondaryMarket] = None
        if resale or release_fee > 0.0:
            self.secondary = SecondaryMarket(
                self.trade, self.bank, release_fee=release_fee,
                resale=resale, ask_fraction=ask_fraction,
                history=self.history)
            if tracer is not None:
                self.secondary.bind_telemetry(tracer)
            if resale:
                for server in self.trade.servers.values():
                    server.secondary = self.secondary
        # the information layer: brokers discover through this, never by
        # reading the directory — so what they know is heartbeat-stale
        # and TTL-cached, and membership can churn under them
        self.gis_ttl = gis_ttl
        self.gis = GridInformationService(
            self.directory, heartbeat_interval=heartbeat_interval,
            suspect_after=gis_suspect_after,
            price_fn=lambda name, t: self.trade.forward_quote(name, t))
        if tracer is not None:
            self.gis.bind_telemetry(tracer)
        for name in self.directory.all_names():
            self.gis.register(self.directory.spec(name), 0.0)
        for site, server in self.trade.servers.items():
            self.gis.register_trade_server(site, server)
        self.churn_mean_uptime_h = churn_mean_uptime_h
        self.churn_mean_downtime_h = churn_mean_downtime_h
        self.churn_min_sites = churn_min_sites
        self.churn_rebate = churn_rebate
        self.churn: Optional[ChurnProcess] = None
        self.churn_trace: List[Tuple[float, str, str]] = []
        self.evictions = 0
        self.refunds = 0.0
        self.dispatch_latency = dispatch_latency
        self.noise_sigma = noise_sigma
        self.users: List[MarketUser] = []
        self.engines: List[NimrodG] = []
        self.price_trace: List[Tuple[float, float]] = []
        self._gis_handle = None
        self._auction_handle = None

    # ------------------------------------------------------------------
    def add_user(self, user: MarketUser,
                 sched_cfg: Optional[SchedulerConfig] = None) -> NimrodG:
        if any(u.name == user.name for u in self.users):
            raise ValueError(f"user {user.name!r} already in market")
        executor = SimulatedExecutor(
            self.sim, self.directory,
            seed=f"{self.seed}:{user.name}",
            noise_sigma=self.noise_sigma,
            dispatch_latency=self.dispatch_latency)
        dispatcher = Dispatcher(executor, self.directory)
        jobs = [JobSpec(job_id=f"{user.name}:j{i:05d}", experiment=user.name,
                        point={"i": i}, steps=(),
                        est_seconds_base=user.est_seconds)
                for i in range(user.n_jobs)]
        req = UserRequirements(deadline=user.deadline, budget=user.budget,
                               strategy=user.strategy, user=user.name)
        # strategies that negotiate (double auction + contracts) bring
        # their own bidder; the registry decides, not a string compare
        scls = strategy_class(user.strategy)
        broker = (scls.make_auction_broker(self.auction_house, user.name,
                                           secondary=self.secondary,
                                           bank=self.bank)
                  if scls.wants_auction_broker else None)
        engine = NimrodG(user.name, jobs, req, self.directory, self.trade,
                         dispatcher, sim=self.sim,
                         sched_cfg=sched_cfg or SchedulerConfig(),
                         seed=self.seed, stop_sim_when_done=False,
                         auction=broker, bank=self.bank,
                         secondary=(self.secondary
                                    if self.secondary is not None
                                    and self.secondary.resale else None),
                         gis=self.gis, gis_ttl=self.gis_ttl,
                         history=self.history, tracer=self.tracer)
        if self.secondary is not None:
            self.secondary.register_user(user.name, engine.ledger)
        self.users.append(user)
        self.engines.append(engine)
        return engine

    def _engine_for(self, user: str) -> Optional[NimrodG]:
        for u, e in zip(self.users, self.engines):
            if u.name == user:
                return e
        return None

    # ------------------------------------------------------------------
    # membership churn: whole sites leave and rejoin mid-run
    # ------------------------------------------------------------------
    def _site_leaves(self, site: str, rejoin_at: float) -> bool:
        if site not in self.trade.servers:
            return False             # already gone (shouldn't happen)
        if len(self.trade.servers) - 1 < self.churn_min_sites:
            return False             # veto: never empty the grid
        t = self.sim.now
        # 1. the machines vanish: down + departed, ETA published, and
        #    the GIS registration is withdrawn (brokers' cached views
        #    keep advertising them until their TTL lapses)
        names = self.directory.site_resources(site)
        for name in names:
            st = self.directory.status(name)
            st.departed = True
            st.set_up(False)
            st.next_transition = rejoin_at
            self.gis.deregister(name, t)
        # 2. in-flight work fails over NOW — requeued without burning
        #    an attempt, commitments refunded by each engine's handler
        evicted_before = self.evictions
        for name in names:
            for engine in self.engines:
                self.evictions += engine.dispatcher.executor.interrupt(name)
        if self.tracer is not None and self.evictions > evicted_before:
            self.tracer.instant(t, f"site:{site}", "churn", "eviction",
                                site=site,
                                jobs=self.evictions - evicted_before)
        # 3. live contracts on the dying domain are voided; the owner
        #    pays each holder a breach rebate through the bank (the
        #    consumer's ledger is credited the same amount: the books
        #    still reconcile to the cent)
        for user, c, remaining in self.auction_house.remove_site(site, t):
            holders: Dict[int, str] = {}
            if self.secondary is not None:
                # a listing over a voided reservation dies with it, fee-
                # free and at void time (never rediscovered post-expiry
                # as "unsold" — the breach rebate settles this loss);
                # and a window that was RESOLD belongs to its buyer now,
                # so the rebate for that slice must follow it
                for rid in c.reservation_ids:
                    self.secondary.drop(rid, t)
                    buyer = self.secondary.buyer_of(rid)
                    if buyer is not None and buyer != user:
                        holders[rid] = buyer
            if not holders:
                self._pay_rebate(user, site, c.resource, t,
                                 self.churn_rebate * remaining)
                continue
            # per-window split: each reservation carries an equal share
            # of the contract's remaining value (max_commitment is
            # price x chips x slots x left — one slot each)
            per_rid = remaining / max(len(c.reservation_ids), 1)
            for rid in c.reservation_ids:
                self._pay_rebate(holders.get(rid, user), site, c.resource,
                                 t, self.churn_rebate * per_rid)
        # 4. the domain's trade server leaves the federation (it stays
        #    behind as a read-only price board for stale views)
        self.trade.remove_server(site)
        self.gis.deregister_trade_server(site)
        self.churn_trace.append((t, "leave", site))
        if self.tracer is not None:
            self.tracer.instant(t, f"site:{site}", "churn", "site_leave",
                                site=site, rejoin_at=rejoin_at,
                                resources=len(names))
        return True

    def _pay_rebate(self, user: str, site: str, resource: str, t: float,
                    amt: float) -> None:
        """Breach rebate for one voided window, credited to whoever
        holds it (the contract's broker, or the buyer of a resold
        reservation) — ledger and bank move together, so the books
        still reconcile to the cent."""
        engine = self._engine_for(user)
        if amt > 0.0 and engine is not None:
            engine.ledger.settle(0.0, -amt)
            self.bank.record(t=t, user=user, owner=site,
                             resource=resource, amount=-amt,
                             kind="refund")
            self.refunds += amt

    def drain_site(self, site: str) -> bool:
        """Steering: force ``site`` out of the grid NOW and keep it out
        (rejoin ETA published as ``inf`` — unlike churn, nothing
        schedules a return).  Same departure semantics as a churn leave:
        in-flight jobs fail over, live contracts are voided with breach
        rebates, the domain's trade server leaves the federation.
        Returns False when the drain was vetoed (the site is already
        gone, or removing it would empty the grid below
        ``churn_min_sites``).  The ``ExperimentMonitor`` records a
        ``steer`` instant around this call."""
        return self._site_leaves(site, rejoin_at=math.inf)

    def _site_joins(self, site: str) -> None:
        t = self.sim.now
        # fresh trade server — the old book died with the old site
        names = self.directory.site_resources(site)
        server = TradeServer(self.directory,
                             {n: self.schedules[n] for n in names},
                             site=site, **self._server_kw)
        if self.secondary is not None and self.secondary.resale:
            server.secondary = self.secondary
        self.trade.add_server(site, server)
        # hand the auction house whatever the federation now fronts the
        # site with (in wire mode add_server wrapped it in a proxy)
        self.auction_house.add_site(site, self.trade.servers[site])
        self.gis.register_trade_server(site, self.trade.servers[site])
        for name in names:
            st = self.directory.status(name)
            st.departed = False
            st.set_up(True)
            st.next_transition = math.inf
            self.gis.register(self.directory.spec(name), t)
        self.churn_trace.append((t, "join", site))
        if self.tracer is not None:
            self.tracer.instant(t, f"site:{site}", "churn", "site_join",
                                site=site, resources=len(names))

    # ------------------------------------------------------------------
    def mean_quote(self, t: float) -> float:
        names = self.directory.all_names()
        if not names:
            return 0.0
        return left_sum(self.trade.quote(n, t) for n in names) / len(names)

    def _watch(self, sample_interval: float, horizon: float) -> None:
        t = self.sim.now
        self.price_trace.append((t, self.mean_quote(t)))
        if self.tracer is not None:
            # the price signal samples every tick; the full registry
            # snapshot (a few dozen counter events each) every 4th —
            # metrics move slowly against the watch cadence and the
            # run-end snapshot always lands the final values
            self.tracer.counter(t, "market", "price.mean_quote",
                                self.price_trace[-1][1])
            if self._snap_tick % 4 == 0:
                self.tracer.snapshot_counters(t)
            self._snap_tick += 1
        if self.secondary is not None:
            # housekeeping on the sim clock: expire unsold listings
            # (charging their commitment fees) and drop dangling ones
            self.secondary.sweep(t)
        if all(e.finished for e in self.engines):
            # nobody is trading anymore: the heartbeat pump and clearing
            # rounds leave the heap with the brokers, then the clock stops
            for handle in (self._gis_handle, self._auction_handle):
                if handle is not None:
                    handle.cancel()
            self.sim.stop()
            return
        if t + sample_interval <= horizon:
            self.sim.after(sample_interval,
                           lambda: self._watch(sample_interval, horizon))

    def run(self, *, failures: bool = False, churn: bool = False,
            horizon: Optional[float] = None,
            sample_interval: float = 600.0) -> MarketReport:
        if not self.engines:
            raise ValueError("no users in the market — add_user() first")
        if horizon is None:
            horizon = max(u.deadline for u in self.users) * 1.5 + 8 * HOUR
        self._gis_handle = self.gis.start(self.sim, until=horizon)
        wall0 = time.perf_counter() if self.tracer is not None else 0.0
        if failures:
            fp = FailureProcess(self.sim, self.directory, seed=self.seed,
                                tracer=self.tracer)
            for name in self.directory.all_names():
                fp.install(name)
        if churn:
            self.churn = ChurnProcess(
                self.sim, self.directory, seed=self.seed,
                mean_uptime_hours=self.churn_mean_uptime_h,
                mean_downtime_hours=self.churn_mean_downtime_h,
                on_leave=self._site_leaves, on_join=self._site_joins)
            for site in self.directory.sites():
                self.churn.install(site)
        if any(e.auction is not None for e in self.engines):
            self._auction_handle = self.auction_house.start(self.sim)
        for engine in self.engines:
            self.sim.after(0.0, engine.tick)
        self.sim.after(0.0, lambda: self._watch(sample_interval, horizon))
        self.sim.run(until=horizon)
        for engine in self.engines:
            if not engine.finished:
                engine.finish(stall="horizon_reached")
        if self.secondary is not None:
            # close the resale book: whatever never sold pays its fee
            # now, and the reports re-read the ledgers so late fees and
            # lump refunds show up in each user's final spend
            self.secondary.finalize(self.sim.now)
            for engine in self.engines:
                engine.report.total_cost = engine.ledger.settled
                engine.report.within_budget = (
                    engine.ledger.settled <= engine.req.budget + 1e-6)
        if self.tracer is not None:
            m = self.tracer.metrics
            m.gauge("market.sim_events").set(float(self.sim.events))
            # final registry snapshot BEFORE the wall-derived gauge is
            # registered: everything in the event stream (and hence the
            # JSONL export) stays deterministic; throughput lands only
            # in the registry, i.e. the Chrome export's otherData
            self.tracer.snapshot_counters(self.sim.now)
            wall = max(time.perf_counter() - wall0, 1e-9)
            m.gauge("market.events_per_sec", unit="ev/s").set(
                self.sim.events / wall)
        return self._report()

    # ------------------------------------------------------------------
    def _report(self) -> MarketReport:
        outcomes = []
        for user, engine in zip(self.users, self.engines):
            rep = engine.report
            outcomes.append(UserOutcome(
                user=user.name, strategy=user.strategy,
                n_jobs=rep.n_jobs, n_done=rep.n_done,
                completion_time=rep.completion_time,
                spent=rep.total_cost, budget=user.budget,
                met_deadline=rep.met_deadline,
                within_budget=rep.within_budget,
                requeues=rep.requeues,
                slot_races_lost=rep.slot_races_lost,
                peak_allocation=rep.peak_allocation,
                stall_reason=rep.stall_reason,
                contracts_won=rep.contracts_won,
                resource_losses=rep.resource_losses))
        total_jobs = sum(o.n_jobs for o in outcomes)
        total_done = sum(o.n_done for o in outcomes)
        met = sum(1 for o in outcomes if o.met_deadline)
        return MarketReport(
            seed=self.seed, n_users=len(outcomes),
            n_resources=len(self.directory.all_names()),
            outcomes=outcomes, total_jobs=total_jobs, total_done=total_done,
            total_spent=left_sum(o.spent for o in outcomes),
            slot_races_lost=sum(o.slot_races_lost for o in outcomes),
            deadline_met_frac=met / max(len(outcomes), 1),
            price_trace=list(self.price_trace),
            contracts_struck=len(self.auction_house.contracts),
            owner_revenue={o: self.bank.owner_revenue(o)
                           for o in self.bank.owners()},
            resource_losses=sum(o.resource_losses for o in outcomes),
            evictions=self.evictions,
            refunds=self.refunds,
            churn_trace=list(self.churn_trace),
            gis_refreshes=sum(e.gis_client.refreshes for e in self.engines
                              if e.gis_client is not None),
            resale_enabled=(self.secondary is not None
                            and self.secondary.resale),
            resales=(len(self.secondary.fills)
                     if self.secondary is not None else 0),
            resale_volume=(self.secondary.resale_volume
                           if self.secondary is not None else 0.0),
            wasted_spend=(self.secondary.wasted_spend
                          if self.secondary is not None else 0.0))


# ---------------------------------------------------------------------------
def standard_market(n_users: int, *, n_machines: int = 20, seed: int = 0,
                    deadline_h: float = 12.0, budget: float = 5_000.0,
                    n_jobs: int = 40, est_seconds: float = 1800.0,
                    strategies: Sequence[str] = ("cost", "time",
                                                 "conservative"),
                    demand_elasticity: float = 0.5,
                    dispatch_latency: float = 1.0,
                    sched_cfg: Optional[SchedulerConfig] = None,
                    **market_kw) -> Marketplace:
    """Canonical N-user market: strategies round-robin over the mix,
    deadlines/budgets slightly staggered so brokers are heterogeneous but
    everything stays deterministic in (n_users, seed).  Extra keywords
    (``gis_ttl=``, ``churn_mean_uptime_h=``, ...) pass through to
    ``Marketplace``; ``sched_cfg`` (e.g. ``timeline_stride`` for big
    sweeps) is applied to every broker."""
    market = Marketplace(n_machines=n_machines, seed=seed,
                         demand_elasticity=demand_elasticity,
                         dispatch_latency=dispatch_latency,
                         **market_kw)
    for i in range(n_users):
        market.add_user(MarketUser(
            name=f"user{i:02d}",
            deadline=(deadline_h + 2.0 * (i % 3)) * HOUR,
            budget=budget * (1.0 + 0.25 * (i % 4)),
            strategy=strategies[i % len(strategies)],
            n_jobs=n_jobs,
            est_seconds=est_seconds), sched_cfg=sched_cfg)
    return market


def mixed_auction_market(n_users: int, **kw) -> Marketplace:
    """``standard_market`` with auction brokers in the mix: every other
    user negotiates (double auction / contracts), the rest buy at the
    posted price — the head-to-head the GRACE papers call for."""
    kw.setdefault("strategies", ("auction", "cost", "auction", "time",
                                 "auction", "conservative"))
    return standard_market(n_users, **kw)
