"""Mean time per step the host spends blocked until the step's results
reach it: the engine's ``device_wait`` spans in the window over its steps
(``consume`` spans, one a step)."""


def read(run):
    d = [s.dur for s in run.spans if s.kind == "device_wait"]
    n = sum(1 for s in run.spans if s.kind == "consume")
    return 1e3 * sum(d) / n if d and n else None
