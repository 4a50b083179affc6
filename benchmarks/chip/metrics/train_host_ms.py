"""Host time of one training round's ``Trainer.step`` (faults, pull
schedule, alias refresh, dispatch), in ms: the mean duration of the
program's ``repro.train.step`` spans in the traced window."""

from benchlib import spans


def read(r):
    s = spans.read(r)
    return None if s is None else s.mean_ms("repro.train.step")
