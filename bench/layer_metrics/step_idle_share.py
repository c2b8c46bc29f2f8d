"""Share of the engine's step calls (the benchmark's ``bench.step`` spans:
schedule, dispatch, wait and sampling) in which the device ran nothing."""


def read(run):
    if not run.steps or run.plane is None:
        return None
    total = sum(b - a for a, b in run.steps)
    busy = sum(run.trace.busy(run.plane, a, b) for a, b in run.steps)
    return 100.0 * (1.0 - busy / total) if total > 0 else None
