"""Time a served request waits in the server's admission queue, in ms:
the mean ``queue_wait_us`` (enqueue to admission) of the program's
``repro.serve.admit`` spans in the traced window."""

from benchlib import spans


def read(r):
    s = spans.read(r)
    us = None if s is None else s.mean_stat("repro.serve.admit",
                                            "queue_wait_us")
    return None if us is None else us / 1000.0
