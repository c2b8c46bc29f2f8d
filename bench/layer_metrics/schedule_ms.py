"""Mean host time the scheduler spends admitting and scheduling before it
dispatches a step: the engine's ``schedule`` spans in the window."""


def read(run):
    d = [s.dur for s in run.spans if s.kind == "schedule"]
    return 1e3 * sum(d) / len(d) if d else None
