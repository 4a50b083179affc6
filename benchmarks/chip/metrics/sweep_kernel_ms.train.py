"""Device time of the fused sweep kernel per training round, in ms: the
summed durations of its events (names in the config's ``kernels.sweep``)
over the window's rounds."""

from benchlib.readings import per_round_s


def read(r):
    s = per_round_s(r, "sweep")
    return None if s is None else 1000.0 * s
