"""Share of the prompt tokens looked up in the prefix index over the window
that it served from cached blocks (scheduler counters, as differences)."""


def read(run):
    q = run.counters.get("prefix_query_tokens", 0)
    return 100.0 * run.counters.get("prefix_hit_tokens", 0) / q if q else None
