"""Share of the decode batch's slots that decoded, over the window's decode
steps: the mean ``batch`` of the engine's ``decode_step`` spans over
``max_batch``."""


def read(run):
    b = [s.args["batch"] for s in run.spans if s.kind == "decode_step"]
    return 100.0 * sum(b) / (len(b) * run.scfg.max_batch) if b else None
