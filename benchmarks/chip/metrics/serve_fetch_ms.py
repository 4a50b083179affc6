"""Time the serving engine's host waits on the device for a sweep's
results, per sweep, in ms: the summed duration of the program's
``repro.serve.fetch`` spans over the number of ``repro.serve.step`` spans
in the traced window."""

from benchlib import spans


def read(r):
    s = spans.read(r)
    return None if s is None else s.per_ms("repro.serve.fetch",
                                           "repro.serve.step")
