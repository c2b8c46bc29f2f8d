"""Executables the engine's steps built or loaded inside the window (the
scheduler's ``compiles`` counter, as a difference): 0 where every program
was warmed up before it."""


def read(run):
    return run.counters.get("compiles")
