"""Mean host time per step that ``consume`` spends on work other than
waiting for the device: sampling dispatch, emitting tokens, stop checks,
retiring requests and publishing prefix blocks (``consume`` less its
``device_wait`` spans, over the window's steps)."""


def read(run):
    wait = [s.dur for s in run.spans if s.kind == "device_wait"]
    con = [s.dur for s in run.spans if s.kind == "consume"]
    if not wait or not con:
        return None
    return 1e3 * (sum(con) - sum(wait)) / len(con)
