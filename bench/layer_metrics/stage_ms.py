"""Mean host time per step spent building the step's arrays and putting
them on the device: the engine's ``stage`` spans in the window over its
steps (``schedule`` spans, one a step)."""


def read(run):
    d = [s.dur for s in run.spans if s.kind == "stage"]
    n = sum(1 for s in run.spans if s.kind == "schedule")
    return 1e3 * sum(d) / n if d and n else None
