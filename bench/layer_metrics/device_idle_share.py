"""Share of the traced window (the trace's own extent, from its first
event to its last, host or device) in which no op ran on the device: it
sees the host's time between steps too, and between bursts of arrivals."""


def read(run):
    w = run.window_s()
    return 100.0 * (1.0 - run.busy_s() / w) if w > 0 and run.plane else None
