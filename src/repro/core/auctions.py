"""GRACE auction house: negotiated resource trading (paper §7).

Nimrod/G's economy is not just posted prices.  The GRACE follow-up
papers (cs/0111048, cs/0203019) spell out the negotiation protocols a
computational market needs beyond take-it-or-leave-it quotes:

* a **double auction** — brokers submit sealed bids for slot capacity,
  owners submit asks for their idle queues, and periodic clearing rounds
  on the virtual clock cross them at a uniform price, producing
  price-locked ``Contract``s for slot-hours;
* a **contract-net / tender** path — a broker issues a call for
  tenders, every domain's owners counter-offer (price valid for a
  window), and the broker accepts or lets the offer lapse
  (``NegotiationTimeout`` forces a re-solicit, never a stale price).

Trading happens *across per-site trade servers*: each administrative
domain runs its own book, all rounds share one clock, and brokers
arbitrage price differences between domains by steering their bids at
whichever site currently clears cheapest.  Struck contracts are locked
in as advance reservations on the owning domain's trade server, so the
whole settlement path (``TradeServer.effective_price`` →
``NimrodG._handle_done``) automatically charges the negotiated price,
not the spot quote.

Everything is deterministic in virtual time: books iterate in sorted
order, ties break lexically, and no wall clock or RNG is consulted.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional, Tuple

try:
    import numpy as np
except ImportError:              # pragma: no cover - numpy is a CI dep
    np = None

from repro.core.economy import (AdmissionError, TradeFederation, TradeServer)
from repro.core.persistence import left_sum
from repro.core.resources import ResourceDirectory
from repro.core.simulator import Simulator

HOUR = 3600.0


class NegotiationTimeout(Exception):
    """A counter-offer was accepted after its validity window closed."""


@dataclasses.dataclass(frozen=True)
class AuctionBid:
    """A broker's sealed bid into one site's double auction: up to
    ``slots`` queue slots for the next contract window, at no more than
    ``chip_hour_price`` G$ per chip-hour."""
    user: str
    chip_hour_price: float          # limit price (max the broker pays)
    slots: int
    valid_until: float

    def valid_at(self, t: float) -> bool:
        return t <= self.valid_until + 1e-9


@dataclasses.dataclass(frozen=True)
class Ask:
    """An owner's offer into the book: ``slots`` uncommitted slots on
    ``resource`` for the window, at no less than ``chip_hour_price``."""
    resource: str
    site: str
    chip_hour_price: float          # reserve price (min the owner takes)
    slots: int


@dataclasses.dataclass(frozen=True)
class CounterOffer:
    """An owner's reply to a call for tenders (contract-net leg)."""
    resource: str
    site: str
    chip_hour_price: float
    slots: int
    start: float
    end: float
    valid_until: float


@dataclasses.dataclass
class Contract:
    """A struck trade: ``user`` holds ``slots`` on ``resource`` over
    [start, end) at the locked ``chip_hour_price``.  Settlement is
    usage-based (pay for chip time actually held), the lock is carried
    by the advance reservations created at signing."""
    contract_id: int
    user: str
    resource: str
    site: str
    chip_hour_price: float
    slots: int
    start: float
    end: float
    via: str                        # "auction" | "tender"
    reservation_ids: Tuple[int, ...] = ()
    voided_at: Optional[float] = None   # owner broke it (site departed)

    def active_at(self, t: float) -> bool:
        return self.start <= t < self.end and self.voided_at is None

    def max_commitment(self, directory: ResourceDirectory,
                       t: Optional[float] = None) -> float:
        """Worst-case G$ this contract can still cost if every remaining
        slot-hour is consumed — the number budget guards must respect."""
        left = self.end - (self.start if t is None else max(self.start, t))
        if left <= 0:
            return 0.0
        chips = directory.spec(self.resource).chips
        return self.chip_hour_price * chips * self.slots * left / HOUR


@dataclasses.dataclass(frozen=True)
class ClearingRound:
    """Audit record of one site's clearing: what crossed and at what
    uniform price."""
    t: float
    site: str
    clearing_price: float
    matched_slots: int
    n_bids: int
    n_asks: int


class DoubleAuctionBook:
    """One administrative domain's order book.

    Brokers replace (not stack) their standing bid between rounds; asks
    are generated fresh at each clearing from the domain's live state —
    an owner offers exactly the slots not yet promised to anyone over
    the coming window, at a reserve price that discounts the posted
    quote in proportion to idleness (an empty queue earns nothing, so
    its owner sells below the posted price rather than not at all)."""

    def __init__(self, server: TradeServer, *, idle_discount: float = 0.25):
        self.server = server
        self.idle_discount = idle_discount
        self.bids: Dict[str, AuctionBid] = {}

    def submit(self, bid: AuctionBid) -> None:
        self.bids[bid.user] = bid

    def make_asks(self, t: float, window: float) -> List[Ask]:
        asks = []
        for name in self.server.resources():
            # liveness through the server (resource_up), not the
            # directory: across a process boundary only the owning
            # domain knows whether its machine is really up
            if not self.server.resource_up(name):
                continue
            slots = self.server.reservable_slots(name, t, t + window)
            if slots <= 0:
                continue
            # forward capacity is priced off the posted schedule (the
            # spot demand premium is transient), then discounted in
            # proportion to idleness: an empty queue earns nothing, so
            # its owner would rather sell below list than not at all
            util = self.server.utilization(name)
            price = self.server.forward_quote(name, t) * (
                1.0 - self.idle_discount * (1.0 - util))
            asks.append(Ask(resource=name, site=self.server.site or "",
                            chip_hour_price=price, slots=slots))
        return asks

    def clear(self, t: float, window: float
              ) -> Tuple[List[Tuple[str, str, int]], float,
                         ClearingRound]:
        """Uniform-price double auction (k = 1/2).

        Sort bids descending and asks ascending by limit price and
        match the longest unit prefix where demand still out-prices
        supply.  All matched units trade at one clearing price — the
        midpoint of the marginal matched pair, which by construction
        lies within every matched bid's and ask's limits.

        The crossing runs on flat price/cumulative-quantity arrays
        (``clear_book_arrays``); ``clear_book_reference`` is the
        retained unit-expansion clearer, byte-equivalent by the
        differential tests and used when numpy is absent.

        Returns ([(user, resource, slots)], clearing_price, audit).
        """
        live_bids = [b for b in self.bids.values()
                     if b.valid_at(t) and b.slots > 0]
        asks = self.make_asks(t, window)
        self.bids.clear()            # bids are per-round: re-bid or drop out

        clearer = clear_book_arrays if np is not None else \
            clear_book_reference
        trades, price, k, nb, na = clearer(live_bids, asks)
        audit = ClearingRound(t=t, site=self.server.site or "",
                              clearing_price=price, matched_slots=k,
                              n_bids=nb, n_asks=na)
        return trades, price, audit


def clear_book_reference(bids: List[AuctionBid], asks: List[Ask]
                         ) -> Tuple[List[Tuple[str, str, int]], float,
                                    int, int, int]:
    """The scalar reference clearer: expand every order into single-slot
    units and walk the prefix.  O(units) — kept as the behavioral oracle
    for the array clearer (and the no-numpy fallback).

    Returns (trades, clearing_price, matched_units, bid_units, ask_units).
    """
    live_bids = sorted(bids, key=lambda b: (-b.chip_hour_price, b.user))
    bid_units: List[AuctionBid] = []
    for b in live_bids:
        bid_units.extend([b] * b.slots)
    ask_units: List[Ask] = []
    for a in sorted(asks, key=lambda a: (a.chip_hour_price, a.resource)):
        ask_units.extend([a] * a.slots)

    k = 0
    while (k < len(bid_units) and k < len(ask_units)
           and bid_units[k].chip_hour_price
           >= ask_units[k].chip_hour_price - 1e-12):
        k += 1
    if k == 0:
        return [], 0.0, 0, len(bid_units), len(ask_units)
    price = 0.5 * (bid_units[k - 1].chip_hour_price
                   + ask_units[k - 1].chip_hour_price)
    matched: Dict[Tuple[str, str], int] = {}
    for i in range(k):
        key = (bid_units[i].user, ask_units[i].resource)
        matched[key] = matched.get(key, 0) + 1
    trades = sorted((u, r, n) for (u, r), n in matched.items())
    return trades, price, k, len(bid_units), len(ask_units)


def clear_book_arrays(bids: List[AuctionBid], asks: List[Ask]
                      ) -> Tuple[List[Tuple[str, str, int]], float,
                                 int, int, int]:
    """Array-program clearer: argsort + cumulative-quantity crossing.

    No unit expansion — orders stay one row each.  Bids argsort by the
    same ``(-price, user)`` key the scalar clearer uses (numpy string
    comparison is the same code-point lexicographic order as Python's,
    and ``lexsort`` is stable, so exact-tie books order identically);
    asks by ``(price, resource)``.  The crossing point is found on the
    cumulative-quantity breakpoints: within a segment between two
    breakpoints the (bid, ask) pair is constant, and bid prices
    non-increasing against ask prices non-decreasing makes the match
    condition a prefix property — the first failing segment ends it.
    Matched units are re-aggregated per (user, resource) by a
    two-pointer walk over the same breakpoints, so the trade list is
    element-for-element the reference clearer's.  All returned scalars
    are Python ints/floats (nothing numpy leaks into contracts or
    journals); the midpoint price is computed in CPython float
    arithmetic on the two marginal limits, bit-identical to the scalar
    path.
    """
    nb_units = sum(b.slots for b in bids)
    na_units = sum(a.slots for a in asks)
    if nb_units == 0 or na_units == 0:
        return [], 0.0, 0, nb_units, na_units

    nb, na = len(bids), len(asks)
    pb = np.fromiter((b.chip_hour_price for b in bids),
                     dtype=np.float64, count=nb)
    ob = np.lexsort((np.array([b.user for b in bids]), -pb))
    pb = pb[ob]
    cb = np.cumsum(np.fromiter((bids[i].slots for i in ob),
                               dtype=np.int64, count=nb))
    users = [bids[i].user for i in ob]

    pa = np.fromiter((a.chip_hour_price for a in asks),
                     dtype=np.float64, count=na)
    oa = np.lexsort((np.array([a.resource for a in asks]), pa))
    pa = pa[oa]
    ca = np.cumsum(np.fromiter((asks[i].slots for i in oa),
                               dtype=np.int64, count=na))
    resources = [asks[i].resource for i in oa]

    lim = int(min(cb[-1], ca[-1]))
    # segment starts: 0 plus every cumulative-quantity breakpoint below
    # the unit limit; each segment maps to one constant (bid, ask) pair
    bounds = np.union1d(cb, ca)
    starts = np.concatenate(
        (np.zeros(1, dtype=np.int64), bounds[bounds < lim]))
    bi = np.searchsorted(cb, starts, side="right")
    ai = np.searchsorted(ca, starts, side="right")
    ok = pb[bi] >= pa[ai] - 1e-12
    k = lim if bool(ok.all()) else int(starts[int(np.argmin(ok))])
    if k == 0:
        return [], 0.0, 0, nb_units, na_units

    bj = int(np.searchsorted(cb, k - 1, side="right"))
    aj = int(np.searchsorted(ca, k - 1, side="right"))
    price = 0.5 * (float(pb[bj]) + float(pa[aj]))

    cbl = cb.tolist()
    cal = ca.tolist()
    matched: Dict[Tuple[str, str], int] = {}
    pos, bj, aj = 0, 0, 0
    while pos < k:
        while cbl[bj] <= pos:        # skip exhausted (or 0-slot) rows
            bj += 1
        while cal[aj] <= pos:
            aj += 1
        end = min(cbl[bj], cal[aj], k)
        key = (users[bj], resources[aj])
        matched[key] = matched.get(key, 0) + (end - pos)
        pos = end
    trades = sorted((u, r, n) for (u, r), n in matched.items())
    return trades, price, k, nb_units, na_units


class AuctionHouse:
    """Federates one ``DoubleAuctionBook`` per site and runs the
    negotiation protocols on the shared virtual clock.

    Double-auction leg: ``start(sim)`` schedules a clearing round every
    ``round_interval`` seconds; each round clears every site's book
    (sites in sorted order) and converts matches into ``Contract``s
    backed by price-locked reservations on the owning trade server.

    Contract-net leg: ``call_for_tenders`` collects counter-offers from
    every domain (price-sorted — the arbitrage view), ``accept`` strikes
    a contract while the offer is still valid and raises
    ``NegotiationTimeout`` after it lapses.
    """

    def __init__(self, federation: TradeFederation, *,
                 round_interval: float = HOUR,
                 window: float = 2 * HOUR,
                 idle_discount: float = 0.25,
                 tender_discount: float = 0.15,
                 tender_validity: float = 0.5 * HOUR,
                 history=None):
        self.federation = federation
        self.round_interval = round_interval
        self.window = window
        self.idle_discount = idle_discount
        self.tender_discount = tender_discount
        self.tender_validity = tender_validity
        # per-resource ClearingHistory (see repro.core.secondary): every
        # clearing round's matched resources append their uniform price,
        # and owners' PriceSchedules get the observation — the discovery
        # loop that lets posted prices track what capacity clears at
        self.history = history
        self.books: Dict[str, DoubleAuctionBook] = {
            site: DoubleAuctionBook(server, idle_discount=idle_discount)
            for site, server in federation.servers.items()}
        self.contracts: List[Contract] = []       # full audit trail
        self._live: Dict[str, List[Contract]] = {}  # per-user, pruned
        self.rounds: List[ClearingRound] = []
        self._next_cid = 1
        self._subscribers: Dict[str, Callable[[Contract], None]] = {}
        self._sim: Optional[Simulator] = None
        self.tracer = None              # set by bind_telemetry

    def bind_telemetry(self, tracer) -> None:
        """Attach a ``repro.core.telemetry.Tracer``: clearing rounds,
        struck contracts and price-discovery nudges emit ``auction``
        instants on the owning site's track, and the registry gains
        derived gauges over the audit trails."""
        self.tracer = tracer
        m = tracer.metrics
        m.gauge("auction.rounds", fn=lambda: float(len(self.rounds)))
        m.gauge("auction.contracts",
                fn=lambda: float(len(self.contracts)))

    # -- wiring --------------------------------------------------------
    def register(self, user: str,
                 on_contract: Callable[[Contract], None]) -> None:
        self._subscribers[user] = on_contract

    def start(self, sim: Simulator):
        """Begin periodic clearing rounds on the simulator clock.
        Returns the recurring-timer handle (cancel it to end trading)."""
        self._sim = sim
        return sim.every(self.round_interval, self._run_round,
                         start_delay=self.round_interval)

    def _run_round(self) -> None:
        assert self._sim is not None
        self.clear_all(self._sim.now)

    # -- double auction ------------------------------------------------
    def submit_bid(self, site: str, bid: AuctionBid) -> None:
        self.books[site].submit(bid)

    def clear_all(self, t: float) -> List[Contract]:
        struck: List[Contract] = []
        for site in sorted(self.books):
            server = self.books[site].server
            trades, price, audit = self.books[site].clear(t, self.window)
            self.rounds.append(audit)
            if self.tracer is not None:
                self.tracer.instant(
                    t, f"site:{site}", "auction", "clearing_round",
                    price=audit.clearing_price,
                    matched=audit.matched_slots, bids=audit.n_bids,
                    asks=audit.n_asks)
            # record the round and feed the owners' discovery loop
            # BEFORE striking: the posted quote logged is the one the
            # round actually cleared against, not an already-nudged one
            for resource in sorted({r for _, r, _ in trades}):
                # a remote (wire-proxy) server keeps its schedules on
                # the domain side; the discovery nudge then happens
                # there and this broker-side hook is a no-op
                sched = getattr(server, "schedules", {}).get(resource)
                if self.history is not None:
                    posted = server.forward_quote(resource, t)
                    self.history.append(t, resource, price, posted,
                                        "auction")
                if sched is not None:
                    base_before = sched.base_price
                    sched.observe_clearing(t, price)
                    if (self.tracer is not None
                            and sched.base_price != base_before):
                        self.tracer.instant(
                            t, f"site:{site}", "auction",
                            "discovery_nudge", resource=resource,
                            base_from=base_before,
                            base_to=sched.base_price, clearing=price)
            for user, resource, slots in trades:
                c = self._strike(user, resource, site, price, slots,
                                 t, t + self.window, via="auction")
                if c is not None:
                    struck.append(c)
        return struck

    # -- contract-net / tender -----------------------------------------
    def call_for_tenders(self, t: float, user: str, *,
                         window: Optional[float] = None
                         ) -> List[CounterOffer]:
        """Broker solicits; every domain's owners counter-offer.  The
        tender discount beats the idle-auction discount only modestly —
        a direct negotiation skips the auction's price discovery, so
        owners concede less."""
        window = self.window if window is None else window
        offers: List[CounterOffer] = []
        for site in sorted(self.books):
            server = self.books[site].server
            for spec in server.directory.discover(user, site=site):
                name = spec.name
                slots = server.reservable_slots(name, t, t + window)
                if slots <= 0:
                    continue
                util = server.utilization(name)
                price = server.quote(name, t, user) * (
                    1.0 - self.tender_discount * (1.0 - util))
                offers.append(CounterOffer(
                    resource=name, site=site, chip_hour_price=price,
                    slots=slots, start=t, end=t + window,
                    valid_until=t + self.tender_validity))
        return sorted(offers, key=lambda o: (o.chip_hour_price, o.resource))

    def accept(self, offer: CounterOffer, user: str, t: float,
               slots: Optional[int] = None) -> Contract:
        """Accept a counter-offer inside its validity window.  Late
        acceptance is a protocol violation: the owner's price has moved
        on, the broker must re-solicit."""
        if t > offer.valid_until + 1e-9:
            raise NegotiationTimeout(
                f"offer on {offer.resource} expired at "
                f"{offer.valid_until:.0f}s, acceptance attempted at "
                f"{t:.0f}s — re-solicit tenders")
        want = offer.slots if slots is None else min(slots, offer.slots)
        c = self._strike(user, offer.resource, offer.site,
                         offer.chip_hour_price, want, offer.start,
                         offer.end, via="tender")
        if c is None:
            raise AdmissionError(
                f"{offer.resource}: capacity gone before acceptance")
        return c

    def decline(self, offer: CounterOffer) -> None:
        """Contract-net completeness: declining is free and stateless."""

    # -- common --------------------------------------------------------
    def _strike(self, user: str, resource: str, site: str, price: float,
                slots: int, start: float, end: float, *, via: str
                ) -> Optional[Contract]:
        # asks are user-agnostic, so authorization is enforced at
        # signing: a restricted resource never contracts to a stranger
        spec = self.federation.directory.spec(resource)
        if spec.authorized_users and user not in spec.authorized_users:
            return None
        server = self.federation.servers.get(site)
        if server is None:
            return None         # domain departed mid-negotiation
        rids = []
        for _ in range(slots):
            try:
                r = server.reserve(resource, user, start, end, start,
                                   locked_price=price)
            except AdmissionError:
                break               # capacity raced away mid-signing
            rids.append(r.reservation_id)
        if not rids:
            return None
        c = Contract(contract_id=self._next_cid, user=user,
                     resource=resource, site=site, chip_hour_price=price,
                     slots=len(rids), start=start, end=end, via=via,
                     reservation_ids=tuple(rids))
        self._next_cid += 1
        self.contracts.append(c)
        self._live.setdefault(user, []).append(c)
        if self.tracer is not None:
            self.tracer.instant(start, f"site:{site}", "auction",
                                "contract", cid=c.contract_id, user=user,
                                resource=resource, price=price,
                                slots=c.slots, via=via)
        sub = self._subscribers.get(user)
        if sub is not None:
            sub(c)
        return c

    # -- membership churn ----------------------------------------------
    def add_site(self, site: str, server: TradeServer) -> None:
        """A (re)joined domain opens a fresh order book."""
        self.books[site] = DoubleAuctionBook(server,
                                             idle_discount=self.idle_discount)

    def remove_site(self, site: str, t: float
                    ) -> List[Tuple[str, Contract, float]]:
        """The domain left: close its book and VOID every live contract
        on it — the owner can no longer deliver the promised slot-hours.
        Backing reservations are cancelled and each voided contract's
        still-undelivered value is returned as ``(user, contract,
        remaining_value)`` so the driver can route breach refunds
        through the bank.  Iterates users sorted — deterministic."""
        self.books.pop(site, None)
        voided: List[Tuple[str, Contract, float]] = []
        for user in sorted(self._live):
            keep = []
            for c in self._live[user]:
                if c.site == site and c.end > t and c.voided_at is None:
                    remaining = c.max_commitment(self.federation.directory, t)
                    for rid in c.reservation_ids:
                        self.federation.cancel(rid)
                    c.voided_at = t
                    voided.append((user, c, remaining))
                else:
                    keep.append(c)
            self._live[user] = keep
        return voided

    def contracts_for(self, user: str) -> List[Contract]:
        return [c for c in self.contracts if c.user == user]

    def outstanding_commitment(self, user: str, t: float) -> float:
        """Worst-case G$ of the user's not-yet-elapsed contracted
        slot-hours — what budget guards must subtract from headroom.
        Scans a per-user live index pruned on access (``contracts``
        keeps the full history for audits), so broker ticks stay O(live)
        however long the market has been trading."""
        live = self._live.get(user)
        if not live:
            return 0.0
        if any(c.end <= t for c in live):
            live = [c for c in live if c.end > t]
            self._live[user] = live
        return left_sum(c.max_commitment(self.federation.directory, t)
                        for c in live)


class AuctionBroker:
    """The bidding policy one engine runs when its user chose
    ``strategy="auction"``.

    Each scheduling tick it (re)places a sealed bid at the site that is
    currently cheapest *per job* for it (cross-domain arbitrage), priced
    just under the best posted quote — the broker only wants the auction
    to beat the price board, never to outbid it.  Bid size is capped so
    that worst-case contracted commitments can never exceed the
    remaining budget.
    """

    def __init__(self, house: AuctionHouse, user: str, *,
                 bid_discount: float = 1.0,
                 commit_fraction: float = 0.8,
                 secondary=None,
                 site_penalty: Optional[Callable[[str, float],
                                                 float]] = None):
        self.house = house
        self.user = user
        self.bid_discount = bid_discount
        self.commit_fraction = commit_fraction
        # optional risk markup per (site, t): reputation-aware bidders
        # inflate a flaky domain's effective cost-per-job when steering
        # the bid and shade the limit price accordingly (None = the
        # historical behavior, exactly)
        self.site_penalty = site_penalty
        # secondary market (repro.core.secondary): idle contracted
        # windows are listed for resale (or released for the commitment
        # fee) instead of silently cancelled
        self.secondary = secondary
        self.contracts: List[Contract] = []      # full history (audit)
        self._live: List[Contract] = []          # pruned on access
        house.register(user, self._on_contract)

    def _on_contract(self, c: Contract) -> None:
        self.contracts.append(c)
        self._live.append(c)

    def withdraw(self, t: float = 0.0) -> None:
        """Leave the market (the experiment is over): pull all standing
        bids so no further contract can be struck, and cancel the
        reservations behind contracts that have not yet elapsed — a
        finished broker must not keep blocking capacity rivals could
        trade for."""
        for book in self.house.books.values():
            book.bids.pop(self.user, None)
        for c in self._live:
            # a contract voided by a departing site already had its
            # reservations cancelled — after the site rejoins its old
            # ids are retired, never ours to cancel again
            if c.end > t and c.voided_at is None:
                for rid in c.reservation_ids:
                    if self.secondary is not None:
                        # resell the unexpired window (or pay the
                        # commitment fee) rather than tear it up free
                        self.secondary.shed(rid, self.user, t)
                    else:
                        self.house.federation.cancel(rid)
        self._live = []

    def shed_idle(self, t: float, keep) -> List[int]:
        """Hand off contracted windows the re-plan left idle: any live
        contract on a resource outside ``keep`` (the advisor's current
        allocation) that has survived at least one full clearing round
        unused goes to the secondary market — listed for resale, or
        released for the fee when resale is off.  Returns the shed
        reservation ids.  The grace round keeps a contract struck this
        tick from bouncing straight back onto the book."""
        if self.secondary is None:
            return []
        shed: List[int] = []
        kept: List[Contract] = []
        for c in self._live:
            idle = (c.end > t and c.voided_at is None
                    and c.resource not in keep
                    and c.start + self.house.round_interval <= t)
            if not idle:
                kept.append(c)
                continue
            for rid in c.reservation_ids:
                if self.secondary.shed(rid, self.user, t) != "gone":
                    shed.append(rid)
        self._live = kept
        return shed

    def active_contracts(self, t: float) -> List[Contract]:
        """Contracts covering ``t``, scanning only the not-yet-elapsed
        list (dropped on access — every-tick calls stay O(live))."""
        if any(c.end <= t for c in self._live):
            self._live = [c for c in self._live if c.end > t]
        return [c for c in self._live if c.active_at(t)]

    def contracted_resources(self, t: float) -> List[str]:
        return sorted({c.resource for c in self.active_contracts(t)})

    # ------------------------------------------------------------------
    def step(self, t: float, est_job_seconds: Dict[str, float],
             remaining_jobs: int, ledger) -> Optional[AuctionBid]:
        """Place (or refresh) this round's sealed bid.  Returns the bid
        for observability, or None when there is nothing to bid for."""
        if remaining_jobs <= 0:
            return None
        fed = self.house.federation
        directory = fed.directory

        # arbitrage: score each site by its cheapest forward
        # cost-per-job — the posted price the broker would otherwise pay
        # for window capacity there
        best_site, best_cpj, site_floor = "", math.inf, math.inf
        best_markup = 0.0
        for site, server in fed.servers.items():
            markup = (max(0.0, self.site_penalty(site, t))
                      if self.site_penalty is not None else 0.0)
            for name in server.resources():
                if name not in est_job_seconds:
                    continue
                if not directory.status(name).up:
                    continue
                q = server.forward_quote(name, t, self.user)
                cpj = q * directory.spec(name).chips \
                    * est_job_seconds[name] / HOUR * (1.0 + markup)
                if cpj < best_cpj - 1e-12 or (abs(cpj - best_cpj) <= 1e-12
                                              and site < best_site):
                    best_site, best_cpj = site, cpj
                    site_floor = q
                    best_markup = markup
        if not best_site or not math.isfinite(best_cpj):
            return None

        # bid the spot-equivalent value (truthful for a uniform-price
        # auction): the clearing midpoint, not the limit, sets the
        # actual price, so wins always come in at-or-under spot — shaded
        # down by the site's risk markup (capacity on a domain likely to
        # void its contracts is worth less than its posted quote)
        price = self.bid_discount * site_floor / (1.0 + best_markup)
        if price <= 0.0:
            return None

        # demand: enough slots to retire the backlog within the window
        server = fed.servers[best_site]
        ests = [est_job_seconds[n] for n in server.resources()
                if n in est_job_seconds]
        est = min(ests) if ests else HOUR
        wanted = max(1, math.ceil(remaining_jobs * est / self.house.window))

        # budget cap: worst-case cost of everything contracted so far
        # plus this bid must fit inside the remaining budget
        max_chips = max((directory.spec(n).chips
                         for n in server.resources()), default=1)
        unit_cost = price * max_chips * self.house.window / HOUR
        already = self.house.outstanding_commitment(self.user, t)
        headroom = ledger.remaining * self.commit_fraction - already
        affordable = int(headroom / unit_cost) if unit_cost > 0 else 0
        slots = min(wanted, affordable)
        if slots <= 0:
            return None
        bid = AuctionBid(user=self.user, chip_hour_price=price, slots=slots,
                         valid_until=t + self.house.round_interval + 1.0)
        self.house.submit_bid(best_site, bid)
        return bid
