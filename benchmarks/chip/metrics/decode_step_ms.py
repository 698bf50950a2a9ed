"""Median device duration of the window's decode steps (the
``jit_decode_step`` executions on the device's ``XLA Modules`` line),
in ms."""


def read(rec):
    tr = rec["trace"]
    return None if tr is None else tr["decode_step_ms"]
