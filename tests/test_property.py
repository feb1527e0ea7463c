"""Hypothesis property tests on system invariants."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import MarketUser, Marketplace, available_strategies
from repro.core.economy import BudgetLedger, PriceSchedule
from repro.core.plan import parse_plan
from repro.core.resources import ResourceSpec
from repro.core.scheduler import (ResourceView, ScheduleAdvisor,
                                  SchedulerConfig, cost_per_job)
from repro.core.economy import UserRequirements
from repro.kernels import ops, ref
from repro.roofline.hlo_cost import _parse_rhs, _type_bytes

HOUR = 3600.0
COMMON = dict(deadline=None, max_examples=25)


# ---------------------------------------------------------------------------
# scheduler invariants
# ---------------------------------------------------------------------------

@st.composite
def grids(draw):
    n = draw(st.integers(2, 12))
    views, prices = {}, {}
    for i in range(n):
        name = f"r{i}"
        spec = ResourceSpec(
            name=name, site="s",
            chips=draw(st.integers(1, 8)),
            perf_factor=draw(st.floats(0.25, 4.0)),
            base_price=draw(st.floats(0.1, 5.0)),
            slots=draw(st.integers(1, 3)))
        views[name] = ResourceView(
            spec=spec, est_job_seconds=draw(st.floats(60.0, 7200.0)))
        prices[name] = draw(st.floats(0.05, 10.0))
    return views, prices


@given(grids(), st.integers(1, 500), st.floats(0.5, 48.0),
       st.floats(10.0, 1e6),
       st.sampled_from(["cost", "time", "conservative"]))
@settings(**COMMON)
def test_decision_invariants(grid, n_jobs, deadline_h, budget, strategy):
    views, prices = grid
    adv = ScheduleAdvisor(SchedulerConfig(),
                          UserRequirements(deadline=deadline_h * HOUR,
                                           budget=budget, strategy=strategy))
    led = BudgetLedger(budget=budget)
    d = adv.decide(0.0, views, prices, n_jobs, led, set())
    chosen = set(d.allocate)
    # allocations are real resources, no duplicates with releases
    assert chosen <= set(views)
    assert not (chosen & set(d.release))
    assert d.projected_rate >= 0
    # cost strategy: chosen set is a prefix of the cheapest-per-job ranking
    if strategy in ("cost", "conservative") and chosen:
        ranked = sorted(views, key=lambda n: (cost_per_job(views[n],
                                                           prices[n]), n))
        k = len(chosen)
        assert chosen == set(ranked[:k])
    # time strategy never projects spend over budget — except the
    # min_resources floor (the engine never idles entirely; the ledger's
    # per-dispatch commit guard is the hard budget wall, tested below)
    if strategy == "time" and len(chosen) > SchedulerConfig().min_resources \
            and math.isfinite(d.projected_cost_per_job):
        assert d.projected_cost_per_job * n_jobs <= budget * 1.001 + 1e-6


@given(grids(), st.integers(1, 300), st.floats(1.0, 24.0),
       st.floats(100.0, 1e5))
@settings(**COMMON)
def test_tighter_deadline_never_fewer_resources(grid, n_jobs, dl_h, budget):
    views, prices = grid
    led = BudgetLedger(budget=budget)
    def n_chosen(hours):
        adv = ScheduleAdvisor(SchedulerConfig(),
                              UserRequirements(deadline=hours * HOUR,
                                               budget=budget,
                                               strategy="cost"))
        return len(adv.decide(0.0, views, prices, n_jobs, led,
                              set()).allocate)
    assert n_chosen(dl_h) >= n_chosen(dl_h * 2)   # Figure 3, as a law


@given(st.lists(st.tuples(st.floats(0.0, 100.0), st.floats(0.0, 100.0)),
                min_size=1, max_size=40),
       st.floats(1.0, 1e4))
@settings(**COMMON)
def test_ledger_never_negative(ops_list, budget):
    led = BudgetLedger(budget=budget)
    for commit, actual in ops_list:
        if led.can_commit(commit):
            led.commit(commit)
            led.settle(commit, min(actual, commit))
    assert led.settled <= budget + 1e-6
    assert led.committed >= -1e-9
    assert led.remaining >= -1e-6


# ---------------------------------------------------------------------------
# the strategy zoo under market invariants (whole-market runs: keep
# max_examples low — each example is a full simulation)
# ---------------------------------------------------------------------------

MARKET_EXAMPLES = dict(deadline=None, max_examples=5)


def _zoo_market(seed, mix, *, budgets=None, **market_kw):
    market = Marketplace(n_machines=5, seed=seed, **market_kw)
    for i, strat in enumerate(mix):
        market.add_user(MarketUser(
            name=f"u{i}", deadline=(8.0 + 2.0 * (i % 3)) * HOUR,
            budget=(budgets[i] if budgets else 400.0 * (1 + i % 3)),
            strategy=strat, n_jobs=4, est_seconds=1200.0))
    return market


def _ledgers(market):
    return {u.name: e.ledger
            for u, e in zip(market.users, market.engines)}


@given(st.integers(0, 10_000),
       st.lists(st.sampled_from(available_strategies()),
                min_size=2, max_size=4))
@settings(**MARKET_EXAMPLES)
def test_bank_reconciles_for_any_strategy_mix(seed, mix):
    """Double-entry closure is strategy-independent: whatever policies
    share the market, broker spend equals bank-recorded owner income
    exactly (reconcile raises otherwise)."""
    market = _zoo_market(seed, mix)
    market.run()
    total = market.bank.reconcile(_ledgers(market))
    assert total == pytest.approx(
        sum(e.ledger.settled for e in market.engines))


@given(st.integers(0, 10_000),
       st.lists(st.sampled_from(available_strategies()),
                min_size=2, max_size=4),
       st.booleans(), st.booleans())
@settings(**MARKET_EXAMPLES)
def test_spend_bounded_under_churn_and_resale(seed, mix, churn, resale):
    """No broker's settled spend exceeds its budget, whatever the
    interleaving of churn departures, failures, commitment fees,
    rebates and resale fills — the per-dispatch commit guard is the
    hard wall, and fee/refund flows never tunnel through it."""
    market_kw = dict(gis_ttl=900.0, churn_mean_uptime_h=3.0,
                     churn_mean_downtime_h=1.0)
    if resale:
        market_kw.update(release_fee=0.25, resale=True,
                         ask_fraction=0.15, auction_round=1800.0)
    budgets = [30.0 * (1 + i % 4) for i in range(len(mix))]
    market = _zoo_market(seed, mix, budgets=budgets, **market_kw)
    market.run(churn=churn, failures=True)
    market.bank.reconcile(_ledgers(market))
    for user, eng in zip(market.users, market.engines):
        assert eng.ledger.settled <= user.budget + 1e-6, (
            user.strategy, eng.ledger.settled, user.budget)


@given(st.integers(0, 10_000))
@settings(deadline=None, max_examples=3)
def test_same_seed_tournament_byte_identical(seed):
    """A full all-strategies tournament round (auctions + churn +
    failures + resale live) replays byte-for-byte from the seed."""
    zoo = available_strategies()
    market_kw = dict(release_fee=0.25, resale=True, ask_fraction=0.15,
                     auction_round=1800.0, gis_ttl=900.0)

    def play():
        market = _zoo_market(seed, zoo, **market_kw)
        rep = market.run(churn=True, failures=True)
        market.bank.reconcile(_ledgers(market))
        return rep.stable_repr()

    assert play() == play()


# ---------------------------------------------------------------------------
# economy
# ---------------------------------------------------------------------------

@given(st.floats(0.1, 10.0), st.floats(1.0, 4.0), st.integers(1, 256),
       st.floats(0.0, 72.0))
@settings(**COMMON)
def test_price_positive_and_bounded(base, peak, chips, t_hours):
    spec = ResourceSpec(name="r", site="s", chips=chips, base_price=base,
                        peak_multiplier=peak)
    ps = PriceSchedule(spec)
    p = ps.chip_hour_price(t_hours * HOUR)
    assert base - 1e-9 <= p <= base * peak + 1e-9


# ---------------------------------------------------------------------------
# plan language
# ---------------------------------------------------------------------------

@given(st.integers(1, 5), st.integers(1, 5), st.integers(1, 4))
@settings(**COMMON)
def test_cross_product_size(na, nb, nc):
    plan = parse_plan(f"""
parameter a integer range from 1 to {na} step 1
parameter b integer range from 1 to {nb} step 1
parameter c integer range from 0 to {nc - 1} step 1
task main
    execute run --a $a --b $b --c $c
endtask
""")
    pts = plan.points()
    assert len(pts) == na * nb * nc
    assert len({tuple(sorted(p.items())) for p in pts}) == len(pts)


# ---------------------------------------------------------------------------
# kernels: flash attention == oracle over random shape draws
# ---------------------------------------------------------------------------

@given(st.integers(1, 2), st.integers(1, 4), st.integers(16, 80),
       st.integers(8, 32), st.booleans(), st.integers(0, 1),
       st.integers(0, 2**31 - 1))
@settings(deadline=None, max_examples=12)
def test_flash_attention_random(B, G, S, D, causal, win_mode, seed):
    K = 2
    H = K * G
    window = 0 if not win_mode else max(4, S // 3)
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, K, S, D))
    v = jax.random.normal(ks[2], (B, K, S, D))
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=32, block_k=32, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


@given(st.integers(1, 3), st.integers(4, 70), st.integers(4, 40),
       st.integers(0, 2**31 - 1))
@settings(deadline=None, max_examples=12)
def test_rglru_random(B, S, L, seed):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    log_a = -jnp.exp(jax.random.normal(ks[0], (B, S, L)) * 0.5 - 2)
    b = jax.random.normal(ks[1], (B, S, L))
    h0 = jax.random.normal(ks[2], (B, L))
    out = ops.rglru_scan(log_a, b, h0, block_t=16, block_l=16, interpret=True)
    want = ref.rglru_ref(log_a, b, h0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# HLO parser
# ---------------------------------------------------------------------------

@given(st.sampled_from(["f32", "bf16", "s32", "pred"]),
       st.lists(st.integers(1, 64), min_size=0, max_size=4))
@settings(**COMMON)
def test_type_bytes_matches_numpy(dt, dims):
    bytes_per = {"f32": 4, "bf16": 2, "s32": 4, "pred": 1}[dt]
    n = int(np.prod(dims)) if dims else 1
    s = f"{dt}[{','.join(map(str, dims))}]"
    assert _type_bytes(s) == n * bytes_per


def test_parse_rhs_tuple_with_index_comments():
    rhs = ("(s32[], bf16[16,4096,1152]{2,1,0}, /*index=5*/f32[4,256]{1,0}) "
           "while(%tuple.1), condition=%c, body=%b")
    rtype, opcode, rest = _parse_rhs(rhs)
    assert opcode == "while"
    assert "bf16[16,4096,1152]" in rtype
    assert "condition=%c" in rest
