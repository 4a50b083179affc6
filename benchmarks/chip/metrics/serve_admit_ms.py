"""Host time of one admission into the serving engine, in ms: the mean
duration of the program's ``repro.serve.admit`` spans in the traced
window."""

from benchlib import spans


def read(r):
    s = spans.read(r)
    return None if s is None else s.mean_ms("repro.serve.admit")
