"""``adaptive`` — fit price curves from ``ClearingHistory`` to time
purchases.

Posted quotes are the owner's ask; the ``ClearingHistory`` records what
capacity actually *traded* for (auction rounds, resale fills).  This
strategy fits a least-squares line through each resource's recent
clearings and treats the extrapolated value at ``t`` as the fair price.
Resources currently quoting at or under ``patience`` times fair are
bought first (in the canonical cheap-per-job order); overpriced ones
are deferred — but only as long as the fairly-priced pool covers the
needed rate.  Deadline pressure always wins: once the fair pool runs
out, the deferred resources are bought in rank order, so selection
stays weakly monotone in the needed rate (larger targets only extend
the walk).  With no clearings yet (or outside a marketplace) every
resource is "fair" and the strategy degrades to exactly ``cost``.
"""
from __future__ import annotations

from typing import Optional, Set

from repro.core.persistence import left_sum
from repro.core.strategies.base import Strategy, StrategyContext, register


@register
class AdaptiveStrategy(Strategy):
    name = "adaptive"
    description = "defer buys quoting above the fitted clearing trend"

    #: pay up to this multiple of the fitted clearing price before
    #: calling a quote overpriced
    patience = 1.05
    #: clearings per resource the fit looks back over
    window = 8

    def fair_price(self, ctx: StrategyContext, name: str
                   ) -> Optional[float]:
        """Extrapolated clearing price at ``ctx.t`` (None = no data)."""
        if ctx.history is None:
            return None
        hist = ctx.history.for_resource(name)[-self.window:]
        if not hist:
            return None
        if len(hist) == 1:
            return hist[0].price
        t0 = hist[0].t
        xs = [c.t - t0 for c in hist]
        ys = [c.price for c in hist]
        n = float(len(xs))
        mx, my = left_sum(xs) / n, left_sum(ys) / n
        var = left_sum((x - mx) ** 2 for x in xs)
        if var <= 1e-12:                       # all clearings at one t
            return my
        slope = left_sum((x - mx) * (y - my) for x, y in zip(xs, ys)) / var
        pred = my + slope * ((ctx.t - t0) - mx)
        # bound the extrapolation by the observed band: a two-point
        # trend must not predict free (or absurd) capacity
        lo, hi = min(ys), max(ys)
        return min(max(pred, 0.5 * lo), 2.0 * hi)

    def select(self, ctx: StrategyContext) -> Set[str]:
        fair, deferred = [], []
        for name in ctx.ranked:
            pred = self.fair_price(ctx, name)
            if (pred is None
                    or ctx.prices[name] <= self.patience * pred + 1e-12):
                fair.append(name)
            else:
                deferred.append(name)
        chosen: Set[str] = set()
        acc = 0.0
        rates = ctx.rates
        for name in fair + deferred:           # patience yields to need
            if acc >= ctx.needed_rate:
                break
            r = rates[name] if rates is not None else ctx.views[name].rate()
            if r <= 0:
                continue
            chosen.add(name)
            acc += r
        return chosen
