"""Grid telemetry: sim-clock tracing, the metrics registry, and the
Perfetto-exportable run (the observability tentpole).

Three layers under test:

* unit — the registry instruments (Counter/Gauge/MultiGauge/Histogram),
  the tracer's ring bounding and ordering guarantees;
* determinism — tracing is purely observational: a traced market
  reproduces the untraced golden bytes, and two same-seed traced runs
  export byte-identical JSONL;
* integration — a traced market's Chrome export is structurally valid
  (balanced async spans, thread metadata, sim-time timestamps) and its
  metrics snapshot reconciles exactly with the GridBank books.
"""
import json
import math

import pytest

from repro.core import (Counter, Gauge, GridBank, Histogram,
                        MetricsRegistry, MultiGauge, ReconciliationError,
                        Tracer, export_chrome_trace, export_jsonl,
                        load_chrome_trace, mixed_auction_market,
                        stable_dumps, standard_market)

from test_golden_equivalence import GOLDEN, _contention_market, _sha

HOUR = 3600.0


def _traced_market(seed=7, tracer=None, **kw):
    kw.setdefault("n_machines", 8)
    kw.setdefault("n_jobs", 12)
    kw.setdefault("demand_elasticity", 1.0)
    return standard_market(4, seed=seed, tracer=tracer, **kw)


# ---------------------------------------------------------------------------
# registry instruments
# ---------------------------------------------------------------------------

def test_counter_monotone_and_shared_by_name():
    m = MetricsRegistry()
    c = m.counter("hits")
    c.inc()
    c.inc(2.5)
    assert m.counter("hits") is c          # get-or-create shares
    assert c.get() == 3.5


def test_gauge_set_and_derived_fn():
    m = MetricsRegistry()
    g = m.gauge("depth")
    g.set(4.0)
    assert g.get() == 4.0
    live = {"v": 1.0}
    d = m.gauge("live", fn=lambda: live["v"])
    live["v"] = 9.0
    assert d.get() == 9.0                  # evaluated at read time


def test_multi_gauge_sorted_labels():
    m = MetricsRegistry()
    fam = m.multi_gauge("rev", fn=lambda: {"b/kill": 2.0, "a/settle": 1.0})
    assert list(fam.get()) == ["a/settle", "b/kill"]


def test_histogram_buckets_and_summary():
    h = Histogram("lat", bounds=(1.0, 2.0, 5.0))
    for v in (0.5, 1.5, 1.5, 4.0, 99.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 5
    assert s["sum"] == pytest.approx(106.5)
    assert s["min"] == 0.5 and s["max"] == 99.0
    assert s["buckets"] == {"le_1.0": 1, "le_2.0": 2, "le_5.0": 1,
                            "overflow": 1}


def test_registry_type_clash_raises():
    m = MetricsRegistry()
    m.counter("x")
    with pytest.raises(TypeError):
        m.gauge("x")


def test_registry_snapshot_sorted_and_typed():
    m = MetricsRegistry()
    m.counter("b.count").inc(3)
    m.gauge("a.gauge").set(1.5)
    m.histogram("c.h").observe(2.0)
    snap = m.snapshot()
    assert list(snap) == sorted(snap)
    assert snap["b.count"] == 3.0
    assert snap["c.h"]["count"] == 1


# ---------------------------------------------------------------------------
# tracer mechanics
# ---------------------------------------------------------------------------

def test_tracer_orders_events_globally_across_categories():
    tr = Tracer()
    tr.instant(1.0, "t1", "a", "first")
    tr.instant(2.0, "t2", "b", "second")
    tr.instant(3.0, "t1", "a", "third")
    evs = tr.events()
    assert [e.name for e in evs] == ["first", "second", "third"]
    assert [e.seq for e in evs] == [0, 1, 2]


def test_ring_bounds_per_category_and_counts_drops():
    tr = Tracer(ring=4)
    for i in range(10):
        tr.instant(float(i), "t", "flood", "ev", i=i)
    tr.instant(99.0, "t", "calm", "ok")
    assert tr.n_events() == 5              # 4 retained + 1 other cat
    assert tr.dropped == {"flood": 6}
    assert [e.args["i"] for e in tr.events() if e.cat == "flood"] == \
        [6, 7, 8, 9]                       # oldest evicted first
    chrome = tr.to_chrome("bounded")
    assert chrome["otherData"]["dropped"] == {"flood": 6}


def test_ring_capacity_must_be_positive():
    with pytest.raises(ValueError):
        Tracer(ring=0)


def test_event_json_is_key_sorted():
    tr = Tracer()
    tr.span_begin(1.5, "trk", "job", "attempt", "e/j1/a1",
                  zeta=1, alpha=2)
    ev = tr.events()[0]
    d = ev.to_json()
    assert list(d["args"]) == ["alpha", "zeta"]
    assert d["span"] == "e/j1/a1" and d["ph"] == "b"
    # stable_dumps of the dict is what jsonl_lines emits
    assert next(iter(tr.jsonl_lines())) == stable_dumps(d)


# ---------------------------------------------------------------------------
# determinism: tracing is purely observational
# ---------------------------------------------------------------------------

def test_traced_run_reproduces_untraced_golden_bytes():
    """The golden contention hash was captured with telemetry OFF; a
    traced run of the same seed must produce the same report bytes —
    instrumentation draws no RNG and reorders nothing."""
    market = _contention_market()
    market.tracer = None                   # untraced baseline path
    tr = Tracer()
    traced = standard_market(4, n_machines=8, seed=7, n_jobs=12,
                             demand_elasticity=1.0, tracer=tr)
    rep = traced.run(failures=True)
    assert _sha(rep.stable_repr()) == GOLDEN["contention"]
    assert tr.n_events() > 0


def test_same_seed_traced_runs_export_identical_jsonl():
    streams = []
    for _ in range(2):
        tr = Tracer()
        _traced_market(tracer=tr).run()
        streams.append("\n".join(tr.jsonl_lines()))
    assert streams[0] == streams[1]
    assert streams[0]                      # and not trivially empty


def test_jsonl_contains_no_wall_clock_values():
    """The wall-derived gauge (events_per_sec) registers only AFTER the
    final snapshot — nothing nondeterministic may reach the event
    stream."""
    tr = Tracer()
    _traced_market(tracer=tr).run()
    for line in tr.jsonl_lines():
        assert "events_per_sec" not in line
    # ... but they do land in the registry for the Chrome otherData
    assert tr.metrics.get("market.events_per_sec").get() > 0


# ---------------------------------------------------------------------------
# the traced market, structurally
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_run():
    tr = Tracer()
    market = _traced_market(tracer=tr)
    report = market.run()
    return tr, market, report


def test_every_job_gets_a_balanced_lifecycle_span(traced_run):
    tr, market, report = traced_run
    opens = {}
    for e in tr.events():
        if e.ph == "b":
            opens[e.span] = opens.get(e.span, 0) + 1
        elif e.ph == "e":
            opens[e.span] = opens.get(e.span, 0) - 1
            assert opens[e.span] >= 0, f"end before begin: {e.span}"
    unbalanced = {k: v for k, v in opens.items() if v != 0}
    assert not unbalanced
    job_spans = {e.span for e in tr.events()
                 if e.cat == "job" and e.name == "job" and e.ph == "b"}
    assert len(job_spans) == report.total_jobs


def test_every_subsystem_emits_typed_events(traced_run):
    tr, market, report = traced_run
    cats = {e.cat for e in tr.events()}
    assert {"job", "gis", "market", "metric"} <= cats
    names = {(e.cat, e.name) for e in tr.events()}
    assert ("gis", "register") in names            # t=0 registrations
    assert ("gis", "heartbeat_pump") in names
    assert ("market", "broker_finish") in names
    finishes = [e for e in tr.events() if e.name == "broker_finish"]
    assert len(finishes) == len(market.users)


def test_chrome_export_is_perfetto_shaped(traced_run, tmp_path):
    tr, market, report = traced_run
    doc = tr.to_chrome("unit-test-run")
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    threads = {e["args"]["name"] for e in meta
               if e["name"] == "thread_name"}
    assert any(t.startswith("broker:") for t in threads)
    assert "gis" in threads
    tids = {e["tid"] for e in meta if e["name"] == "thread_name"}
    for e in evs:
        if e["ph"] == "M":
            continue
        assert e["pid"] == 1 and e["tid"] in tids
        if e["ph"] in ("b", "e"):
            assert e["id"]                 # async spans carry their id
        if e["ph"] == "i":
            assert e["s"] == "t"
    # ts is sim-time microseconds: a 12-job day-scale market spans hours
    span_us = max(e["ts"] for e in evs if e["ph"] != "M")
    assert span_us > 1 * HOUR * 1e6
    # and the file round-trips through the exporters
    p = tmp_path / "trace.json"
    export_chrome_trace(tr, str(p), run_name="unit-test-run")
    loaded = load_chrome_trace(str(p))
    assert loaded["otherData"]["run"] == "unit-test-run"
    assert len(loaded["traceEvents"]) == len(evs)
    jl = tmp_path / "trace.jsonl"
    export_jsonl(tr, str(jl))
    assert jl.read_text().count("\n") == tr.n_events()


def test_metrics_snapshot_reconciles_with_gridbank(traced_run):
    tr, market, report = traced_run
    snap = tr.metrics.snapshot()
    bank = market.bank
    assert snap["bank.total_spend_gd"] == pytest.approx(
        bank.total_spend(), abs=1e-9)
    assert snap["bank.total_revenue_gd"] == pytest.approx(
        bank.total_revenue(), abs=1e-9)
    # the two-sided audit passes against the live broker ledgers
    total = bank.reconcile(
        {u.name: e.ledger for u, e in zip(market.users, market.engines)})
    assert total == pytest.approx(snap["bank.total_spend_gd"])
    # per-owner revenue-by-kind family sums back to the grand total
    by_kind = snap["bank.revenue_by_kind_gd"]
    assert math.fsum(by_kind.values()) == pytest.approx(total)
    # completion metrics populated
    assert snap["broker.attempts_per_job"]["count"] == report.total_done
    assert snap["market.sim_events"] > 0


def test_auction_market_emits_auction_events():
    tr = Tracer()
    rep = mixed_auction_market(4, n_machines=8, seed=3, n_jobs=8,
                               tracer=tr).run()
    assert rep.contracts_struck > 0
    names = {(e.cat, e.name) for e in tr.events()}
    assert any(cat == "auction" for cat, _ in names)
    assert tr.metrics.get("auction.contracts").get() > 0


# ---------------------------------------------------------------------------
# reconciliation error diagnostics (satellite: per-kind breakdown)
# ---------------------------------------------------------------------------

def test_reconciliation_error_carries_per_kind_breakdown():
    bank = GridBank()
    bank.record(t=1.0, user="u0", owner="ANL", resource="m0", amount=5.0)
    bank.record(t=2.0, user="u0", owner="ANL", resource="m0", amount=2.0,
                kind="kill")
    bank._spend["u0"] += 1.0               # corrupt one side of the books
    with pytest.raises(ReconciliationError) as err:
        bank.reconcile()
    msg = str(err.value)
    assert "per-kind totals" in msg
    assert "settle" in msg and "kill" in msg
    assert "delta" in msg


def test_ledger_mismatch_breakdown_names_the_user():
    bank = GridBank()
    bank.record(t=1.0, user="u1", owner="SDSC", resource="m1", amount=3.0)

    class FakeLedger:
        settled = 4.0

    with pytest.raises(ReconciliationError) as err:
        bank.reconcile({"u1": FakeLedger()})
    msg = str(err.value)
    assert "'u1'" in msg and "per-kind totals" in msg
