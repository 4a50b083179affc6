"""Device time of the Pallas alias row-rebuild kernels per training round,
in ms (names in the config's ``kernels.alias_rows``)."""

from benchlib.readings import per_round_s


def read(r):
    s = per_round_s(r, "alias_rows")
    return None if s is None else 1000.0 * s
