"""Transport layer for the sharded grid: loopback and per-domain
OS processes, both speaking ``repro.core.protocol``.

Two implementations of one contract (``request(msg) -> reply``):

* :class:`LoopbackTransport` — in-process, delivered synchronously on
  the sim clock.  Every message still round-trips through the full
  ``encode -> stable_dumps -> parse`` codec, so the loopback proves the
  wire encoding is lossless while default-knob runs stay byte-identical
  to the direct-call goldens (canonical JSON floats are exact).

* :class:`DomainProcess` — one OS process per administrative domain
  (trade server + its resource slice + its GIS branch), spoken to over
  a pipe carrying the same canonical bytes.  The domain journals every
  state-mutating message; SIGKILL it mid-run, restart it on the same
  journal, and the book (and every booked settlement) is rebuilt
  exactly — reservation awards and settlements are keyed, so replays
  and retries are idempotent.

Broker-side, :class:`RemoteTradeServer` and :class:`WireFederation`
present the exact ``TradeServer``/``TradeFederation`` surface, so the
scheduler (``negotiate_contract``), the auction house and the GIS
client run unchanged whether their counterparty is an object, a
loopback endpoint, or another process.
"""
from __future__ import annotations

import dataclasses
import math
import multiprocessing
import os
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro.core import protocol as P
from repro.core.accounting import GridBank
from repro.core.economy import (AdmissionError, Bid, PriceSchedule,
                                Reservation, TradeServer)
from repro.core.gis import GISEntry, GridInformationService
from repro.core.persistence import Journal, replay
from repro.core.resources import ResourceDirectory, ResourceSpec

HOUR = 3600.0


class TransportError(ConnectionError):
    """The counterparty is gone (crashed domain, closed pipe)."""


def _spec_to_wire(spec: ResourceSpec) -> P.WireSpec:
    return P.WireSpec(**dataclasses.asdict(spec))


def _spec_from_wire(w: P.WireSpec) -> ResourceSpec:
    return ResourceSpec(**dataclasses.asdict(w))


def _res_to_wire(r: Reservation) -> P.WireReservation:
    return P.WireReservation(resource=r.resource, user=r.user,
                             start=r.start, end=r.end,
                             locked_price=r.locked_price,
                             reservation_id=r.reservation_id)


def _res_from_wire(w: P.WireReservation) -> Reservation:
    return Reservation(resource=w.resource, user=w.user, start=w.start,
                       end=w.end, locked_price=w.locked_price,
                       reservation_id=w.reservation_id)


# ---------------------------------------------------------------------------
# domain endpoint: the server side of the protocol
# ---------------------------------------------------------------------------

class DomainEndpoint:
    """One administrative domain's protocol handler.

    Wraps a real ``TradeServer`` (and optionally that domain's GIS
    branch): every wire message lowers to the same method call the
    in-process grid makes, so domain behavior is identical under every
    transport.  With a ``journal_path``, every state-mutating message
    (reserve / cancel / transfer / restride / settle) is journaled
    after it applies; constructing an endpoint on an existing journal
    replays it — the crash/recovery story."""

    def __init__(self, server: TradeServer,
                 gis: Optional[GridInformationService] = None,
                 journal_path: Optional[str] = None):
        self.server = server
        self.gis = gis
        self.requests = 0
        # exactly-once keys: awarded reservations by request_id and a
        # domain-local revenue book keyed by settlement_id
        self._awards: Dict[str, Reservation] = {}
        self.bank = GridBank()
        self._revenue_rows: List[Tuple[str, str, str, float, str, float]] \
            = []
        self.journal: Optional[Journal] = None
        if journal_path is not None:
            self._replay(journal_path)
            self.journal = Journal(journal_path)

    # -- crash/recovery -------------------------------------------------
    def _replay(self, path: str) -> None:
        """Rebuild the reservation book and the settlement ledger from
        the journal — admission checks are NOT re-run (the journal
        records what was admitted), and rid counters resume exactly."""
        server = self.server
        for ev in replay(path):
            kind = ev.get("kind")
            if kind == "reserve":
                r = Reservation(resource=ev["resource"], user=ev["user"],
                                start=ev["start"], end=ev["end"],
                                locked_price=ev["locked_price"],
                                reservation_id=ev["rid"])
                server.reservations.append(r)
                server._next_rid = ev["next_rid"]
                server.book_version += 1
                self._awards[ev["request_id"]] = r
            elif kind == "cancel":
                server.cancel(ev["rid"])
            elif kind == "transfer":
                r = server.find_reservation(ev["rid"])
                if r is not None:
                    r.user = ev["buyer"]
                    server.book_version += 1
            elif kind == "restride":
                server._next_rid = ev["next_rid"]
                server._rid_step = ev["rid_step"]
            elif kind == "settle":
                if self.bank.record_once(
                        ev["settlement_id"], t=ev["t"], user=ev["user"],
                        owner=ev["owner"], resource=ev["resource"],
                        amount=ev["amount"], kind=ev["entry_kind"]):
                    self._revenue_rows.append(
                        (ev["settlement_id"], ev["user"], ev["resource"],
                         ev["amount"], ev["entry_kind"], ev["t"]))

    def _log(self, kind: str, **fields: Any) -> None:
        if self.journal is not None:
            self.journal.append(kind, **fields)

    def close(self) -> None:
        if self.journal is not None:
            self.journal.close()

    # -- dispatch --------------------------------------------------------
    def handle(self, msg: P.Message) -> P.Message:
        self.requests += 1
        try:
            return self._dispatch(msg)
        except AdmissionError as e:
            return P.ErrorReply(error=str(e), admission=True)
        except P.ProtocolError:
            raise
        except Exception as e:                    # surface, don't kill
            return P.ErrorReply(error=f"{type(e).__name__}: {e}")

    def _dispatch(self, msg: P.Message) -> P.Message:
        s = self.server
        if isinstance(msg, P.QuoteRequest):
            price = (s.forward_quote(msg.resource, msg.t, msg.user)
                     if msg.forward else s.quote(msg.resource, msg.t,
                                                 msg.user))
            return P.PriceReply(price=price, book_version=s.book_version)
        if isinstance(msg, P.SolicitRequest):
            est = msg.est_seconds
            bids = s.solicit_bids(
                msg.t, msg.user,
                lambda spec: est.get(spec.name, msg.default_est))
            return P.BidsReply(
                bids=tuple(P.WireBid(**dataclasses.asdict(b))
                           for b in bids),
                book_version=s.book_version)
        if isinstance(msg, P.ReserveRequest):
            prior = self._awards.get(msg.request_id)
            if prior is not None:       # replayed/retried award
                return P.ReserveReply(ok=True,
                                      reservation=_res_to_wire(prior),
                                      book_version=s.book_version)
            r = s.reserve(msg.resource, msg.user, msg.start, msg.end,
                          msg.t, locked_price=msg.locked_price)
            self._awards[msg.request_id] = r
            self._log("reserve", request_id=msg.request_id,
                      rid=r.reservation_id, resource=r.resource,
                      user=r.user, start=r.start, end=r.end,
                      locked_price=r.locked_price, next_rid=s._next_rid)
            return P.ReserveReply(ok=True, reservation=_res_to_wire(r),
                                  book_version=s.book_version)
        if isinstance(msg, P.CancelRequest):
            ok = s.cancel(msg.reservation_id)
            if ok:
                self._log("cancel", rid=msg.reservation_id)
            return P.OkReply(ok=ok, book_version=s.book_version)
        if isinstance(msg, P.TransferRequest):
            r = s.transfer(msg.reservation_id, msg.buyer, msg.t)
            if r is None:
                return P.TransferReply(ok=False, error="gone",
                                       book_version=s.book_version)
            self._log("transfer", rid=msg.reservation_id, buyer=msg.buyer)
            return P.TransferReply(ok=True, reservation=_res_to_wire(r),
                                   book_version=s.book_version)
        if isinstance(msg, P.FindRequest):
            r = s.find_reservation(msg.reservation_id)
            return P.ReserveReply(
                ok=r is not None,
                reservation=None if r is None else _res_to_wire(r),
                book_version=s.book_version)
        if isinstance(msg, P.BookRequest):
            return self._book(msg)
        if isinstance(msg, P.StatusRequest):
            st = s.directory.status(msg.resource)
            return P.StatusReply(up=st.up, running=st.running,
                                 queued=st.queued, version=st.version)
        if isinstance(msg, P.SyncRequest):
            return P.SyncReply(
                site=s.site or "",
                specs=tuple(_spec_to_wire(s.directory.spec(n))
                            for n in s.resources()),
                bid_validity=s.bid_validity,
                book_version=s.book_version,
                membership_version=s.membership_version,
                next_rid=s._next_rid,
                rid_step=s._rid_step)
        if isinstance(msg, P.RestrideRequest):
            s._next_rid = msg.next_rid
            s._rid_step = msg.rid_step
            self._log("restride", next_rid=msg.next_rid,
                      rid_step=msg.rid_step)
            return P.OkReply(ok=True, book_version=s.book_version)
        if isinstance(msg, P.SettleRequest):
            fresh = self.bank.record_once(
                msg.settlement_id, t=msg.t, user=msg.user,
                owner=msg.owner, resource=msg.resource,
                amount=msg.amount, kind=msg.kind)
            if fresh:
                self._revenue_rows.append(
                    (msg.settlement_id, msg.user, msg.resource,
                     msg.amount, msg.kind, msg.t))
                self._log("settle", settlement_id=msg.settlement_id,
                          t=msg.t, user=msg.user, owner=msg.owner,
                          resource=msg.resource, amount=msg.amount,
                          entry_kind=msg.kind)
            return P.SettleReply(ok=True, duplicate=not fresh)
        if isinstance(msg, P.RevenueRequest):
            return P.RevenueReply(entries=tuple(self._revenue_rows))
        if self.gis is not None:
            reply = self._gis(msg)
            if reply is not None:
                return reply
        return P.ErrorReply(
            error=f"unhandled message {msg.wire_kind!r} at domain "
                  f"{s.site!r}")

    def _book(self, msg: P.BookRequest) -> P.Message:
        s = self.server
        op = msg.op
        if op == "reserved_price":
            p = s.reserved_price(msg.resource, msg.user, msg.t)
            return P.BookReply(price=p, book_version=s.book_version)
        if op == "reserved_price_list":
            ps = s.reserved_price_list(msg.resource, msg.user, msg.t)
            return P.BookReply(prices=tuple(ps),
                               book_version=s.book_version)
        if op == "reserved_slots":
            n = s.reserved_slots(msg.resource, msg.user, msg.t)
            return P.BookReply(slots=n, book_version=s.book_version)
        if op == "effective_price":
            return P.BookReply(price=s.effective_price(msg.resource,
                                                       msg.user, msg.t),
                               book_version=s.book_version)
        if op == "honored_price":
            return P.BookReply(
                price=s.honored_price(msg.resource, msg.user,
                                      msg.sealed_price, msg.sealed_at,
                                      msg.t),
                book_version=s.book_version)
        if op == "reservable_slots":
            return P.BookReply(slots=s.reservable_slots(msg.resource,
                                                        msg.start,
                                                        msg.end),
                               book_version=s.book_version)
        if op == "utilization":
            return P.BookReply(price=s.utilization(msg.resource),
                               book_version=s.book_version)
        if op == "resource_up":
            return P.BookReply(slots=int(s.resource_up(msg.resource)),
                               book_version=s.book_version)
        if op == "version":
            return P.BookReply(book_version=s.book_version)
        return P.ErrorReply(error=f"unknown book op {op!r}")

    def _gis(self, msg: P.Message) -> Optional[P.Message]:
        g = self.gis
        if isinstance(msg, P.GISRegister):
            g.register(_spec_from_wire(msg.spec), msg.t)
            return P.OkReply(ok=True)
        if isinstance(msg, P.GISDeregister):
            g.deregister(msg.name, msg.t)
            return P.OkReply(ok=True)
        if isinstance(msg, P.GISHeartbeat):
            g.heartbeat(msg.name, msg.t)
            return P.OkReply(ok=True)
        if isinstance(msg, P.GISPump):
            g.pump_heartbeats(msg.t)
            return P.OkReply(ok=True)
        if isinstance(msg, P.GISQuery):
            entries = g.query(
                msg.t, user=msg.user, level=msg.level, within=msg.within,
                min_chips=msg.min_chips, max_price=msg.max_price,
                include_suspected=msg.include_suspected)
            return P.GISQueryReply(
                entries=tuple(P.WireGISEntry(**e.to_wire())
                              for e in entries),
                version=g.version)
        return None


# ---------------------------------------------------------------------------
# transports
# ---------------------------------------------------------------------------

class LoopbackTransport:
    """Synchronous in-process delivery on the sim clock.

    Every message (and reply) still crosses the full canonical-JSON
    codec, so a loopback run certifies the protocol encoding while
    behaving — byte-for-byte — like the direct-call grid."""

    def __init__(self, endpoint: DomainEndpoint, codec: bool = True):
        self.endpoint = endpoint
        self.codec = codec
        self.messages = 0
        self.bytes_out = 0
        self.bytes_in = 0

    def request(self, msg: P.Message) -> P.Message:
        self.messages += 1
        if self.codec:
            wire = P.dumps(msg)
            self.bytes_out += len(wire)
            reply = self.endpoint.handle(P.loads(wire))
            back = P.dumps(reply)
            self.bytes_in += len(back)
            return P.loads(back)
        return self.endpoint.handle(msg)

    def close(self) -> None:
        self.endpoint.close()


@dataclasses.dataclass(frozen=True)
class DomainConfig:
    """Everything a domain process needs to build its world: picklable,
    and sufficient to REBUILD it identically after a crash (plus the
    journal, which carries the state the config cannot)."""
    site: str
    specs: Tuple[ResourceSpec, ...]
    journal_path: Optional[str] = None
    demand_elasticity: float = 0.0
    spot_amplitude: float = 0.0
    max_reservations_per_user: Optional[int] = None
    bid_validity: float = HOUR
    heartbeat_interval: float = 300.0
    gis_suspect_after: int = 2
    run_gis: bool = True


def build_domain(cfg: DomainConfig) -> DomainEndpoint:
    """Construct one administrative domain from its config: directory
    slice, price schedules, trade server, GIS branch — the same objects
    the in-process marketplace builds, owned by one process."""
    directory = ResourceDirectory()
    for spec in cfg.specs:
        directory.register(spec)
    schedules = {spec.name: PriceSchedule(
        spec, demand_elasticity=cfg.demand_elasticity,
        spot_amplitude=cfg.spot_amplitude) for spec in cfg.specs}
    server = TradeServer(
        directory, schedules, site=cfg.site,
        max_reservations_per_user=cfg.max_reservations_per_user,
        bid_validity=cfg.bid_validity)
    gis = None
    if cfg.run_gis:
        gis = GridInformationService(
            directory, heartbeat_interval=cfg.heartbeat_interval,
            suspect_after=cfg.gis_suspect_after,
            price_fn=lambda name, t: server.forward_quote(name, t))
        for spec in cfg.specs:
            gis.register(spec, 0.0)
    return DomainEndpoint(server, gis=gis,
                          journal_path=cfg.journal_path)


def _domain_serve(conn, cfg: DomainConfig) -> None:
    """Domain process main loop: canonical bytes in, canonical bytes
    out, until shutdown or the pipe dies."""
    endpoint = build_domain(cfg)
    try:
        while True:
            try:
                data = conn.recv_bytes()
            except (EOFError, OSError):
                break
            try:
                msg = P.loads(data.decode("utf-8"))
            except P.ProtocolError as e:
                conn.send_bytes(P.dumps(P.ErrorReply(
                    error=f"protocol: {e}")).encode("utf-8"))
                continue
            if isinstance(msg, P.ShutdownRequest):
                conn.send_bytes(P.dumps(P.OkReply(ok=True))
                                .encode("utf-8"))
                break
            reply = endpoint.handle(msg)
            conn.send_bytes(P.dumps(reply).encode("utf-8"))
    finally:
        endpoint.close()
        conn.close()


class DomainProcess:
    """One administrative domain as its own OS process.

    ``request`` sends canonical bytes down a pipe and blocks for the
    reply.  ``kill`` is a real SIGKILL (the crash test's hammer);
    ``restart`` spawns a fresh process on the SAME journal, which
    replays it — reservations, rid counters and booked settlements come
    back exactly."""

    def __init__(self, cfg: DomainConfig,
                 ctx: Optional[multiprocessing.context.BaseContext] = None):
        self.cfg = cfg
        # forked children must stay JAX-free, and so must the parent up
        # to the fork: on a TPU host a parent that has started a JAX
        # backend holds the chip, and a child that touches it fails or
        # hangs.  core/ imports no JAX, which keeps domain processes safe
        self._ctx = ctx or multiprocessing.get_context("fork")
        self._proc: Optional[multiprocessing.Process] = None
        self._conn = None
        self.restarts = -1
        self.start()

    @property
    def site(self) -> str:
        return self.cfg.site

    def start(self) -> None:
        if self._proc is not None and self._proc.is_alive():
            raise RuntimeError(f"domain {self.site!r} already running")
        parent, child = self._ctx.Pipe()
        self._proc = self._ctx.Process(
            target=_domain_serve, args=(child, self.cfg), daemon=True)
        self._proc.start()
        child.close()
        self._conn = parent
        self.restarts += 1

    def request(self, msg: P.Message) -> P.Message:
        if self._conn is None:
            raise TransportError(f"domain {self.site!r} is not running")
        try:
            self._conn.send_bytes(P.dumps(msg).encode("utf-8"))
            data = self._conn.recv_bytes()
        except (EOFError, OSError, BrokenPipeError) as e:
            raise TransportError(
                f"domain {self.site!r} died mid-request: {e}")
        return P.loads(data.decode("utf-8"))

    def alive(self) -> bool:
        return self._proc is not None and self._proc.is_alive()

    def kill(self) -> None:
        """SIGKILL — no goodbye, no flush beyond what fsync already
        guaranteed.  This is the crash the journal exists for."""
        if self._proc is not None:
            self._proc.kill()
            self._proc.join(timeout=10.0)
        if self._conn is not None:
            self._conn.close()
            self._conn = None

    def restart(self) -> None:
        self.kill()
        self._proc = None
        self.start()

    def stop(self) -> None:
        """Orderly shutdown (flush + close), falling back to kill."""
        if self._conn is not None and self.alive():
            try:
                self.request(P.ShutdownRequest(reason="stop"))
            except TransportError:
                pass
        self.kill()

    def close(self) -> None:
        self.stop()


# ---------------------------------------------------------------------------
# broker-side proxies: the TradeServer surface over a transport
# ---------------------------------------------------------------------------

class RemoteTradeServer:
    """The ``TradeServer`` public surface, spoken over a transport.

    Brokers, the auction house and the secondary market call the same
    methods with the same types; each lowers to one protocol message.
    The broker's ``directory`` is a spec mirror fetched at sync time
    (shared across proxies, so the federation sees one namespace)."""

    def __init__(self, transport,
                 directory: Optional[ResourceDirectory] = None):
        self._transport = transport
        sync = self._req(P.SyncRequest())
        self.site: Optional[str] = sync.site or None
        self.bid_validity = sync.bid_validity
        self.book_version = sync.book_version
        self.membership_version = sync.membership_version
        self._next_rid = sync.next_rid
        self._rid_step = sync.rid_step
        self.directory = directory if directory is not None \
            else ResourceDirectory()
        for w in sync.specs:
            spec = _spec_from_wire(w)
            if spec.name not in self.directory:
                self.directory.register(spec)
        # loopback endpoints share the process: schedules stay readable
        # (the auction house's discovery nudge); across a real process
        # boundary they live domain-side and this mapping is empty
        ep = getattr(transport, "endpoint", None)
        self.schedules = ep.server.schedules if ep is not None else {}
        self._secondary = None

    # the resale book is read domain-side (TradeServer.solicit_bids
    # merges its listings into tenders): attaching it to a loopback
    # proxy must attach it to the real server behind the endpoint
    @property
    def secondary(self):
        ep = getattr(self._transport, "endpoint", None)
        return ep.server.secondary if ep is not None else self._secondary

    @secondary.setter
    def secondary(self, value) -> None:
        ep = getattr(self._transport, "endpoint", None)
        if ep is not None:
            ep.server.secondary = value
        self._secondary = value

    # -- plumbing --------------------------------------------------------
    def _req(self, msg: P.Message) -> P.Message:
        reply = self._transport.request(msg)
        if isinstance(reply, P.ErrorReply):
            if reply.admission:
                raise AdmissionError(reply.error)
            raise TransportError(reply.error)
        bv = getattr(reply, "book_version", None)
        if bv is not None:
            self.book_version = bv
        return reply

    # -- TradeServer surface ----------------------------------------------
    def resources(self) -> List[str]:
        return [n for n in self.directory.all_names()
                if self.site is None
                or self.directory.spec(n).site == self.site]

    def resource_up(self, resource: str) -> bool:
        r = self._req(P.BookRequest(op="resource_up", resource=resource,
                                    user="", t=0.0))
        return bool(r.slots)

    def price_version(self, resource: str) -> int:
        # always a wire read: broker quote caches key on this, and only
        # the domain knows whether a rival moved the book since
        self._req(P.BookRequest(op="version", resource=resource,
                                user="", t=0.0))
        return self.book_version

    def utilization(self, resource: str) -> float:
        return self._req(P.BookRequest(op="utilization",
                                       resource=resource, user="",
                                       t=0.0)).price

    def quote(self, resource: str, t: float, user: str = "") -> float:
        return self._req(P.QuoteRequest(resource=resource, t=t,
                                        user=user)).price

    def forward_quote(self, resource: str, t: float,
                      user: str = "") -> float:
        return self._req(P.QuoteRequest(resource=resource, t=t, user=user,
                                        forward=True)).price

    def solicit_bids(self, t: float, user: str,
                     est_job_seconds: Callable[[ResourceSpec], float]
                     ) -> List[Bid]:
        # the callable can't cross the wire: evaluate it against the
        # spec mirror and ship per-resource estimates
        est = {n: est_job_seconds(self.directory.spec(n))
               for n in self.resources()}
        reply = self._req(P.SolicitRequest(t=t, user=user,
                                           est_seconds=est))
        return [Bid(**dataclasses.asdict(w)) for w in reply.bids]

    def reservable_slots(self, resource: str, start: float, end: float
                         ) -> int:
        return self._req(P.BookRequest(op="reservable_slots",
                                       resource=resource, user="", t=0.0,
                                       start=start, end=end)).slots

    def reserve(self, resource: str, user: str, start: float, end: float,
                t: float, locked_price: Optional[float] = None
                ) -> Reservation:
        self._reqseq = getattr(self, "_reqseq", 0) + 1
        reply = self._req(P.ReserveRequest(
            request_id=f"{user}:{self.site}:{self._reqseq}",
            resource=resource, user=user, start=start, end=end, t=t,
            locked_price=locked_price))
        r = _res_from_wire(reply.reservation)
        # mirror the rid stream (the federation's restride arithmetic
        # reads it, exactly as it reads a local server's counter)
        self._next_rid = r.reservation_id + self._rid_step
        return r

    def cancel(self, reservation_id: int) -> bool:
        return self._req(P.CancelRequest(
            reservation_id=reservation_id)).ok

    def transfer(self, reservation_id: int, buyer: str, t: float
                 ) -> Optional[Reservation]:
        reply = self._req(P.TransferRequest(reservation_id=reservation_id,
                                            buyer=buyer, t=t))
        return _res_from_wire(reply.reservation) if reply.ok else None

    def find_reservation(self, reservation_id: int
                         ) -> Optional[Reservation]:
        reply = self._req(P.FindRequest(reservation_id=reservation_id))
        return _res_from_wire(reply.reservation) if reply.ok else None

    def reserved_price(self, resource: str, user: str, t: float
                       ) -> Optional[float]:
        return self._req(P.BookRequest(op="reserved_price",
                                       resource=resource, user=user,
                                       t=t)).price

    def reserved_slots(self, resource: str, user: str, t: float) -> int:
        return self._req(P.BookRequest(op="reserved_slots",
                                       resource=resource, user=user,
                                       t=t)).slots

    def reserved_price_list(self, resource: str, user: str, t: float
                            ) -> List[float]:
        return list(self._req(P.BookRequest(op="reserved_price_list",
                                            resource=resource, user=user,
                                            t=t)).prices)

    def effective_price(self, resource: str, user: str, t: float) -> float:
        return self._req(P.BookRequest(op="effective_price",
                                       resource=resource, user=user,
                                       t=t)).price

    def honored_price(self, resource: str, user: str, sealed_price: float,
                      sealed_at: float, t: float) -> float:
        return self._req(P.BookRequest(op="honored_price",
                                       resource=resource, user=user, t=t,
                                       sealed_price=sealed_price,
                                       sealed_at=sealed_at)).price

    def settle(self, settlement_id: str, *, t: float, user: str,
               resource: str, amount: float,
               kind: str = "settle") -> P.SettleReply:
        """GridBank settlement pushed to the owning domain's ledger —
        idempotent under ``settlement_id``."""
        return self._transport.request(P.SettleRequest(
            settlement_id=settlement_id, t=t, user=user,
            owner=self.site or "", resource=resource, amount=amount,
            kind=kind))

    def revenue_rows(self) -> List[Tuple]:
        """The domain's booked settlement rows — the producer side of
        the exact reconciliation audit."""
        return [tuple(r) for r in
                self._req(P.RevenueRequest(owner=self.site or "")).entries]

    def restride(self, next_rid: int, rid_step: int) -> None:
        self._req(P.RestrideRequest(next_rid=next_rid, rid_step=rid_step))
        self._next_rid = next_rid
        self._rid_step = rid_step

    @property
    def reservations(self) -> List[Reservation]:
        raise NotImplementedError(
            "a remote book is not enumerable; use find_reservation "
            "(the secondary market's locate path) or reserved_* reads")


class WireFederation:
    """``TradeFederation``'s public surface over remote servers.

    The broker-facing contract — sorted ``servers``, merged price-sorted
    ``solicit_bids``, routed ``reserve``/``cancel``/price reads,
    federation-unique rid striding, membership churn with departed
    read-only boards — is re-implemented over proxies, so scheduler and
    auction code cannot tell the difference."""

    # batched quote boards read schedules/status objects directly;
    # a wire federation quotes through messages instead
    supports_board = False

    def __init__(self, servers: Dict[str, RemoteTradeServer],
                 directory: Optional[ResourceDirectory] = None,
                 restride: bool = True):
        if not servers:
            raise ValueError("federation needs at least one trade server")
        self.servers: Dict[str, RemoteTradeServer] = dict(sorted(
            servers.items()))
        self.directory = directory if directory is not None \
            else next(iter(self.servers.values())).directory
        self.bid_validity = max(s.bid_validity
                                for s in self.servers.values())
        self._departed: Dict[str, RemoteTradeServer] = {}
        self._rid_floor = 1
        self.membership_version = 0
        self._board = None
        # restride=False: the domains were already strided (a wrapped
        # in-process federation) — re-striding would move the counters
        # forward and the wire grid would issue different ids than the
        # direct one
        if restride:
            self._restride()

    def _restride(self) -> None:
        # identical arithmetic to TradeFederation._restride, pushed to
        # each domain as an explicit protocol message (and journaled
        # there, so a crashed domain resumes its residue class exactly)
        n = len(self.servers)
        if n == 0:
            return
        start = max([self._rid_floor]
                    + [s._next_rid for s in self.servers.values()]
                    + [s._next_rid for s in self._departed.values()])
        self._rid_floor = start
        for i, server in enumerate(self.servers.values()):
            server.restride(start + (i + 1 - start) % n, n)

    # -- membership churn ----------------------------------------------
    def remove_server(self, site: str) -> RemoteTradeServer:
        server = self.servers.pop(site)
        self._departed[site] = server
        self.membership_version += 1
        if self.servers:
            self.bid_validity = max(s.bid_validity
                                    for s in self.servers.values())
        return server

    def add_server(self, site: str, server) -> None:
        """A domain (re)joined.  Accepts a ready proxy, or a plain
        ``TradeServer`` which is wrapped in a loopback endpoint — the
        marketplace's churn rejoin path stays a one-liner."""
        if site in self.servers:
            raise ValueError(f"trade server for {site!r} already federated")
        if not isinstance(server, RemoteTradeServer):
            server = RemoteTradeServer(
                LoopbackTransport(DomainEndpoint(server)),
                directory=self.directory)
        old = self._departed.pop(site, None)
        if old is not None:
            self._rid_floor = max(self._rid_floor, old._next_rid)
        self.servers[site] = server
        self.servers = dict(sorted(self.servers.items()))
        self.bid_validity = max(s.bid_validity
                                for s in self.servers.values())
        self.membership_version += 1
        self._restride()

    # -- routing ---------------------------------------------------------
    def sites(self) -> List[str]:
        return list(self.servers)

    def departed_sites(self) -> List[str]:
        return sorted(self._departed)

    def server_for(self, resource: str) -> RemoteTradeServer:
        site = self.directory.spec(resource).site
        if site in self.servers:
            return self.servers[site]
        return self._departed[site]

    # -- single-server interface (delegated) ------------------------------
    def price_version(self, resource: str) -> int:
        return self.server_for(resource).price_version(resource)

    def utilization(self, resource: str) -> float:
        return self.server_for(resource).utilization(resource)

    def quote(self, resource: str, t: float, user: str = "") -> float:
        return self.server_for(resource).quote(resource, t, user)

    def forward_quote(self, resource: str, t: float,
                      user: str = "") -> float:
        return self.server_for(resource).forward_quote(resource, t, user)

    def solicit_bids(self, t: float, user: str,
                     est_job_seconds: Callable[[ResourceSpec], float]
                     ) -> List[Bid]:
        bids: List[Bid] = []
        for server in self.servers.values():
            bids.extend(server.solicit_bids(t, user, est_job_seconds))
        return sorted(bids, key=lambda b: (b.chip_hour_price, b.resource))

    def reserve(self, resource: str, user: str, start: float, end: float,
                t: float, locked_price: Optional[float] = None
                ) -> Reservation:
        site = self.directory.spec(resource).site
        if site not in self.servers:
            raise AdmissionError(
                f"{resource}: domain {site!r} has left the grid — "
                f"no reservations until it rejoins")
        return self.servers[site].reserve(
            resource, user, start, end, t, locked_price=locked_price)

    def cancel(self, reservation_id: int) -> bool:
        return any(s.cancel(reservation_id)
                   for s in list(self.servers.values())
                   + list(self._departed.values()))

    def find_reservation(self, reservation_id: int
                         ) -> Optional[Reservation]:
        for s in list(self.servers.values()) \
                + list(self._departed.values()):
            r = s.find_reservation(reservation_id)
            if r is not None:
                return r
        return None

    def reserved_price(self, resource: str, user: str, t: float
                       ) -> Optional[float]:
        return self.server_for(resource).reserved_price(resource, user, t)

    def reserved_slots(self, resource: str, user: str, t: float) -> int:
        return self.server_for(resource).reserved_slots(resource, user, t)

    def reserved_price_list(self, resource: str, user: str, t: float
                            ) -> List[float]:
        return self.server_for(resource).reserved_price_list(
            resource, user, t)

    def effective_price(self, resource: str, user: str, t: float) -> float:
        return self.server_for(resource).effective_price(resource, user, t)

    def honored_price(self, resource: str, user: str, sealed_price: float,
                      sealed_at: float, t: float) -> float:
        return self.server_for(resource).honored_price(
            resource, user, sealed_price, sealed_at, t)


class RemoteGIS:
    """Broker-side GIS over domain transports: each administrative
    domain answers for its own branch; queries merge the branches into
    the one global view ``GISClient`` expects.  Spec objects come from
    the shared mirror, so entries are real ``GISEntry`` values and the
    client's snapshot machinery runs unchanged."""

    def __init__(self, transports: Dict[str, Any],
                 directory: ResourceDirectory):
        self.transports = dict(sorted(transports.items()))
        self.directory = directory
        self.version = 0
        self.queries = 0

    def query(self, t: float, *, user: str = "", level: str = "global",
              within: Optional[str] = None, min_chips: int = 0,
              max_price: float = math.inf,
              include_suspected: bool = False) -> List[GISEntry]:
        self.queries += 1
        entries: List[GISEntry] = []
        for site, tr in self.transports.items():
            if level != "global" and within is not None \
                    and not str(within).startswith(site):
                continue
            try:
                reply = tr.request(P.GISQuery(
                    t=t, user=user, level=level, within=within,
                    min_chips=min_chips, max_price=max_price,
                    include_suspected=include_suspected))
            except TransportError:
                continue        # a dead domain answers no queries
            if isinstance(reply, P.ErrorReply):
                continue
            self.version = max(self.version, reply.version)
            for w in reply.entries:
                if w.name in self.directory:
                    entries.append(GISEntry.from_wire(
                        dataclasses.asdict(w),
                        self.directory.spec(w.name)))
        return sorted(entries, key=lambda e: e.name)

    def pump(self, t: float) -> int:
        """Ask every live domain to beat its branch's heartbeats —
        liveness is now a real network phenomenon: a crashed domain
        simply goes silent and its resources age into suspicion."""
        n = 0
        for tr in self.transports.values():
            try:
                tr.request(P.GISPump(t=t))
                n += 1
            except TransportError:
                continue
        return n


# ---------------------------------------------------------------------------
# wiring helpers
# ---------------------------------------------------------------------------

def wrap_federation_loopback(fed, codec: bool = True) -> WireFederation:
    """Re-plumb an in-process ``TradeFederation`` through the protocol:
    every server gets a loopback endpoint + proxy, and the federation
    surface is rebuilt over them.  Same objects, same clock, same
    directory — but every trade now crosses the canonical codec.  This
    is the transport the default marketplace runs when asked for
    ``wire="loopback"`` (and must stay byte-identical to direct)."""
    proxies = {}
    for site, server in fed.servers.items():
        proxies[site] = RemoteTradeServer(
            LoopbackTransport(DomainEndpoint(server), codec=codec),
            directory=fed.directory)
    # the wrapped federation already strided its counters: carry its
    # id arithmetic over verbatim instead of striding a second time
    wf = WireFederation(proxies, directory=fed.directory, restride=False)
    wf._rid_floor = fed._rid_floor
    wf.membership_version = fed.membership_version
    return wf


def spawn_domains(configs: List[DomainConfig]
                  ) -> Tuple[Dict[str, DomainProcess], WireFederation,
                             RemoteGIS]:
    """Launch one OS process per administrative domain and return the
    broker-side view: the process handles, a wire federation over them,
    and the merged remote GIS."""
    procs = {cfg.site: DomainProcess(cfg) for cfg in configs}
    directory = ResourceDirectory()
    servers = {site: RemoteTradeServer(proc, directory=directory)
               for site, proc in procs.items()}
    fed = WireFederation(servers, directory=directory)
    gis = RemoteGIS({site: proc for site, proc in procs.items()},
                    directory)
    return procs, fed, gis
