"""The fused sweep kernel's share of its roofline, in %: the least time
the chip needs for a round's sweep work (``work/``, against
``peaks.json``) over the kernel's device time per round."""

from benchlib.readings import per_round_s, sweep_least_s


def read(r):
    s = per_round_s(r, "sweep")
    if not s:
        return None
    least, bound = sweep_least_s(r)
    r.counters["sweep_roofline_bound"] = bound
    return 100.0 * least / s
