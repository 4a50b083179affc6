"""Host time of one serving sweep outside its uniform draws, in ms: the
mean self time of the program's ``repro.serve.step`` spans in the traced
window (the eager dispatch of the sweep)."""

from benchlib import spans


def read(r):
    s = spans.read(r)
    return None if s is None else s.mean_self_ms("repro.serve.step")
