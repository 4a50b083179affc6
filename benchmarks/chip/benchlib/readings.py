"""Helpers the per-layer readers share."""

from __future__ import annotations


def sweep_least_s(r) -> tuple[float, str]:
    """Least device time for one round's sweep work, and its binding bound:
    the sum over the round's position chunks of ``work/<sweep kernel>``."""
    model = r.config["model"]
    ops = nbytes = 0.0
    for ch in r.counters["round_chunks"]:
        o, b = r.work(r.config["work"]["sweep"], tokens=ch["tokens"],
                      rows=ch["rows"], n_topics=model["n_topics"],
                      mh_steps=model["mh_steps"])
        ops, nbytes = ops + o, nbytes + b
    return r.least_s(ops, nbytes)


def per_round_s(r, patterns_key: str) -> float | None:
    """Device seconds per window round of the config's kernels
    ``kernels[patterns_key]``; None where the trace shows none."""
    rounds = r.counters.get("rounds")
    if r.reduced is None or not rounds:
        return None
    s, n = r.reduced.kernel_s(r.config["kernels"][patterns_key])
    return s / rounds if n else None


def idle_share(r) -> float | None:
    """Percent of the traced window in which the chip ran no operation:
    100 * (1 - busy / window); None where the trace holds no device."""
    t = r.reduced
    if t is None or not t.devices or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
