"""Share of the (token, expert) pairs routed over the whole router in the
window that landed on the experts this chip holds (scheduler counters
``moe_pairs_held`` / ``moe_pairs_routed``, as differences)."""


def read(run):
    routed = run.counters.get("moe_pairs_routed", 0)
    return 100.0 * run.counters.get("moe_pairs_held", 0) / routed \
        if routed else None
