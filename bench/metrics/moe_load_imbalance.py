"""moe_load_imbalance (ratio): the largest held expert's load over the
mean load of the held experts, before the capacity rule, from the
program's routing tally.  Each MoE layer of each step contributes its
largest load and its routed pairs; their sums give the ratio, the mean
of the per-layer ratios weighted by the pairs routed (1 is even)."""


def read(record):
    m = record.get("moe")
    if not m or not m["moe_routed"]:
        return None
    return m["moe_max_load"] * m["held"] / m["moe_routed"]
