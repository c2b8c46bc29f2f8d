"""Share of the block-table entries handed to the paged decode kernel over
the window that hold KV the step attends to (scheduler counters
``decode_kv_blocks_live`` / ``decode_kv_blocks_passed``, as differences)."""


def read(run):
    p = run.counters.get("decode_kv_blocks_passed", 0)
    return 100.0 * run.counters.get("decode_kv_blocks_live", 0) / p \
        if p else None
