"""Live KV blocks the paged decode kernel scores in one of its steps over
the window (scheduler counters ``decode_kv_blocks_live`` /
``decode_kv_steps_live``, as differences): P where every lane's live
blocks fill whole groups of P, 1 for a kernel that walks one block a
step."""


def read(run):
    steps = run.counters.get("decode_kv_steps_live", 0)
    return run.counters.get("decode_kv_blocks_live", 0) / steps \
        if steps else None
