"""The training round's share of the chip's peak, in %: the least time
for a round's required work (the sweep's, from ``work/``) over the traced
window's time per round.  None where the trace holds no device."""

from benchlib.readings import sweep_least_s


def read(r):
    rounds = r.counters.get("rounds")
    if r.reduced is None or not r.reduced.devices or not rounds:
        return None
    least, bound = sweep_least_s(r)
    r.counters["train_mfu_bound"] = bound
    return 100.0 * least / (r.reduced.window_s / rounds)
