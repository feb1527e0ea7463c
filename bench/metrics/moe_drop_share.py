"""moe_drop_share (%): (token, expert) pairs dropped by the capacity rule
over the pairs routed to the experts this chip holds, summed over every
MoE layer of every step the process ran (the program's routing tally:
the window's jobs and set-up's one-step runs)."""


def read(record):
    m = record.get("moe")
    if not m or not m["moe_routed"]:
        return None
    return 100.0 * m["moe_dropped"] / m["moe_routed"]
