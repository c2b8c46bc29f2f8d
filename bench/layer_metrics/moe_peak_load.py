"""The busiest held expert's rows over the mean held expert's rows, per
MoE layer call, averaged over the window (scheduler counters
``moe_rows_peak``, ``moe_pairs_held`` and ``moe_rows_held_steps``, as
differences; 1 is perfectly even)."""


def read(run):
    held = run.counters.get("moe_pairs_held", 0)
    if not held or not run.counters.get("moe_rows_held_steps", 0):
        return None
    experts = run.cell.ref.dims(run.cell.config["model"])["e"]
    return run.counters["moe_rows_peak"] * experts / held
