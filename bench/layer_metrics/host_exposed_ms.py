"""Mean device-idle time per traced step inside the host's own phases:
the engine's ``schedule``, ``device_step``, ``publish`` and ``retire``
spans, mapped onto the device trace's clock by ``lib/align.py``.  Idle
time inside ``device_wait`` (the host blocked on a result) is left out."""
from bench.lib import align

HOST = ("schedule", "device_step", "publish", "retire")


def read(run):
    if not any(s.kind == "retire" for s in run.spans):
        return None
    got = align.idle_by_kind(run, HOST)
    if got is None:
        return None
    idle, steps = got
    return sum(idle.values()) / 1e6 / steps
