"""Wall time per fold-in sweep of the serving engine, in ms: the untraced
stretch of the window over the sweeps the engine ran in it (its
``sweeps_run`` counter).  The profiler slows the engine's host path, so
the traced stretch is left out."""


def read(r):
    n = r.counters.get("untraced_sweeps")
    if not n:
        return None
    return 1000.0 * r.counters["untraced_s"] / n
