"""Open-loop serving traffic: fold-in requests to ``InferenceServer`` over
INFER frames on loopback, at a fixed offered rate.

Set-up draws a corpus from the seed with each token's generating topic,
freezes the statistics of the training documents under those topics
(``serve.snapshot.freeze``: the program builds its alias tables), starts
the server in this process and serves one warm-up batch, which compiles
every program the engine runs (its shapes are fixed by the slot grid).

The window offers ``round(rate x seconds)`` requests.  Their inter-arrival
gaps are the exponential quantiles of the rate, and their documents'
lengths a fixed spread over [``min_len``, ``doc_len``], both in an order
drawn once from the traffic's ``schedule_seed``: every run offers the same
arrivals and lengths in the same order (the order moves the latency tail
far more than the seed's words do), and ``--seed`` draws the documents'
words and the requests' chain seeds.  A dispatcher thread starts each
request at its due time on a connection of its own; request threads never
call JAX.  A request's latency runs from its due time to its
INFER_RESULT; a shed or failed request counts as failed and misses every
limit.  Requests due in the window are waited for up to a minute past its
close.

With ``--trace 1`` the profiler runs over the window's last
``TRACED_SHARE`` only: the sweeps of the untraced stretch before it give
``serve_sweep_ms``, which the profiler would slow, and the traced stretch
gives the device's busy and idle time.

The reference (``refs/<config reference>.py``) then reads a sample drawn
from the seed of the served documents, the longest among them:
``foldin_logp_gap`` (the served assignments' log joint against an exact
Gibbs fold-in's), ``theta_gap`` (served proportions against those of the
served assignments) and ``foldin_ppl_ratio`` (the served proportions'
perplexity against the exact fold-in's; the configuration's quality
gate).
"""

from __future__ import annotations

import gc
import threading
import time

import numpy as np

DRAIN_S = 60.0
TRACED_SHARE = 1.0 / 3.0    # of the window, at its end, when traced


def corpus(r, n_docs: int, lengths=None):
    from benchlib.corpus import make_corpus
    model, c = r.config["model"], r.traffic["corpus"]
    return make_corpus(n_topics=model["n_topics"],
                       vocab_size=model["vocab_size"], n_docs=n_docs,
                       doc_len=c["doc_len"], theta_conc=c["theta_conc"],
                       zipf_a=c["zipf_a"], min_len=c["min_len"],
                       seed=r.seed, lengths=lengths)


def schedule(r, rate: float, seconds: float):
    """(due times, document lengths) of the window's requests."""
    c = r.traffic["corpus"]
    n = max(1, round(rate * seconds))
    rng = np.random.default_rng(r.traffic["schedule_seed"])
    gaps = -np.log1p(-(np.arange(n + 1) + 0.5) / (n + 1)) / rate
    gaps = rng.permutation(gaps)
    due = seconds * np.cumsum(gaps)[:n] / gaps.sum()
    lengths = np.linspace(c["min_len"], c["doc_len"], n).round().astype(int)
    return due, rng.permutation(lengths)


class GcPauses:
    """Python's garbage-collector pauses while entered: a host stall that
    the generator's lateness alone cannot name."""

    def __init__(self) -> None:
        self.pauses: list[tuple[int, float]] = []
        self._start = 0.0

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._start = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._start))

    def __enter__(self):
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._on_gc)

    def summary(self) -> dict:
        return {"count": len(self.pauses),
                "total_s": sum(s for _, s in self.pauses),
                "max_s": max((s for _, s in self.pauses), default=0.0),
                "gen2": sum(g == 2 for g, _ in self.pauses)}


class Outcome:
    __slots__ = ("due", "sent", "done", "result", "error")

    def __init__(self, due: float):
        self.due, self.sent, self.done = due, None, None
        self.result, self.error = None, None


def send(addr: str, uid: int, tokens, seed: int, out: Outcome,
         t0: float) -> None:
    """One request on a connection of its own (never touches JAX)."""
    from repro.serve.client import InferenceClient
    out.sent = time.perf_counter() - t0
    try:
        with InferenceClient(addr, timeout=300.0, retries=0) as cl:
            out.result = cl.infer(uid, tokens, seed)
    except Exception as e:          # a failed request is counted, not raised
        out.error = repr(e)
    out.done = time.perf_counter() - t0


def offer(addr: str, docs: list, seeds: list, outs: list[Outcome],
          t0: float, threads: list[threading.Thread]) -> None:
    """Start each request at its due time (open loop), adding its thread
    to ``threads``."""
    for i, out in enumerate(outs):
        sleep_until(t0 + out.due)
        th = threading.Thread(target=send, daemon=True, args=(
            addr, i, docs[i], seeds[i], out, t0))
        th.start()
        threads.append(th)


def sleep_until(t: float) -> None:
    wait = t - time.perf_counter()
    if wait > 0:
        time.sleep(wait)


def trace_engine(r, engine) -> None:
    """Put each engine call the batcher makes in a harness span, so that
    the trace can name what the host did in the device's idle gaps."""
    for name in ("admit", "_chunk_uniforms", "step", "harvest"):
        call = getattr(engine, name)

        def spanned(*args, _call=call, _name=name, **kw):
            with r.span("serve." + _name.lstrip("_")):
                return _call(*args, **kw)
        setattr(engine, name, spanned)


def start_server(r):
    """Freeze the seed's true-topic statistics and start the server."""
    import jax.numpy as jnp

    from repro.core import family as family_mod
    from repro.serve import snapshot as snapshot_mod
    from repro.serve.engine import ServeConfig
    from repro.serve.server import InferenceServer

    model, c = r.config["model"], r.traffic["corpus"]
    n_train, n_pool = c["n_docs"], r.traffic["pool_docs"]
    tokens, mask, topics = corpus(r, n_train + n_pool)
    ref = r.module("refs", r.config["reference"])
    stats = ref.frozen_stats(model, tokens[:n_train], mask[:n_train],
                             topics[:n_train])
    fam = family_mod.get(r.config["family"])
    cfg = fam.config_cls(**model)
    snap = snapshot_mod.freeze(cfg, fam.shared_from_dict(
        {n: jnp.asarray(v) for n, v in stats.items()}))
    srv = InferenceServer(snap, ServeConfig(**r.traffic["serve"]),
                          max_queue=r.traffic["max_queue"]).start()
    return srv, stats, tokens[n_train:], mask[n_train:]


def run(r, devs) -> dict:
    srv, stats, pool_tok, pool_mask = start_server(r)
    addr = f"{srv.address[0]}:{srv.address[1]}"
    slots = r.traffic["serve"]["max_slots"]
    rate = r.traffic["rate_per_s"]

    # Warm-up: one full batch of pool documents compiles every program.
    lens = pool_mask.sum(1)
    warm = [pool_tok[i, :lens[i]] for i in range(slots)]
    t_w = time.perf_counter()
    outs, ths = [Outcome(0.0) for _ in warm], []
    offer(addr, warm, list(range(slots)), outs, t_w, ths)
    for th in ths:
        th.join()
    if any(o.error for o in outs):
        raise RuntimeError(f"warm-up failed: {[o.error for o in outs]}")
    r.log(f"set-up: warm-up batch of {slots} docs in "
          f"{time.perf_counter() - t_w:.2f}s")

    # The window's documents: fresh draws with the schedule's lengths.
    due, lengths = schedule(r, rate, r.seconds)
    tok, mask, _ = corpus(r, len(due), lengths=lengths)
    docs = [tok[i, :lengths[i]] for i in range(len(due))]
    seeds = [int(s) for s in np.random.default_rng([r.seed, 2]).integers(
        0, 2**31, size=len(due))]

    if r.trace:
        trace_engine(r, srv.engine)
    engine = srv.engine
    outs, ths = [Outcome(d) for d in due], []
    pauses = GcPauses()
    sweeps0 = engine.sweeps_run
    t0 = time.perf_counter()
    dispatcher = threading.Thread(target=offer, daemon=True, args=(
        addr, docs, seeds, outs, t0, ths))
    with pauses:
        dispatcher.start()
        t_split = t0 + (1.0 - TRACED_SHARE) * r.seconds if r.trace else t0
        sleep_until(t_split)
        t_split, sweeps_split = time.perf_counter(), engine.sweeps_run
        with r.window():
            sleep_until(t0 + r.seconds)
            t_close = time.perf_counter()
            sweeps_close = engine.sweeps_run
        dispatcher.join()
    for th in ths:
        th.join(timeout=max(0.0, t_close + DRAIN_S - time.perf_counter()))
    t_drained = time.perf_counter()
    memory_peak = int(devs[0].memory_stats()["peak_bytes_in_use"])
    served = srv.stats()
    srv.close()

    lat = [o.done - o.due if o.result is not None else float("inf")
           for o in outs]
    failed = sum(o.result is None for o in outs)
    late = [o.sent - o.due for o in outs if o.sent is not None]
    p95 = float(np.quantile(lat, 0.95, method="inverted_cdf"))
    if not r.trace:              # the whole window is untraced
        t_split, sweeps_split = t_close, sweeps_close
    r.counters.update(
        requests=len(outs), failed=failed, rate_per_s=rate,
        window_s=t_close - t0, window_sweeps=sweeps_close - sweeps0,
        untraced_s=t_split - t0, untraced_sweeps=sweeps_split - sweeps0,
        traced_s=t_close - t_split, traced_sweeps=sweeps_close - sweeps_split,
        latency_p50_s=float(np.median(lat)),
        latency_p95_s=p95, latency_max_s=float(max(lat)),
        generator_late_max_s=max(late), generator_late_mean_s=float(
            np.mean(late)),
        compile_events_in_window=r.compiles_between(t0, t_drained),
        server_shed=served["shed"], memory_peak_bytes=memory_peak,
        gc_pauses=pauses.summary(),
        requests_due_sent_done_s=[[o.due, o.sent, o.done] for o in outs])
    r.log(f"window: {len(outs)} requests at {rate}/s, p95 {p95:.3f}s, "
          f"{failed} failed, {r.counters['compile_events_in_window']} compile "
          f"events, generator late by <= {max(late) * 1e3:.1f}ms; "
          f"{r.counters['untraced_sweeps']} sweeps in the untraced "
          f"{r.counters['untraced_s']:.2f}s, {r.counters['traced_sweeps']} "
          f"in the traced {r.counters['traced_s']:.2f}s")

    # --- the reference, once the server and its snapshot are freed ------
    del srv, engine
    gc.collect()
    t_ref = time.perf_counter()
    checks = reference_checks(r, stats, docs, outs)
    r.counters["reference_s"] = time.perf_counter() - t_ref
    return {"window_start": t0, "attempted": len(outs), "failed": failed,
            "e2e": {"serve_latency_p95_s": p95}, "checks": checks,
            "memory_peak": memory_peak}


def sample(r, docs, outs) -> list[int]:
    """A sample drawn from the seed of the served requests, the one with
    the longest document first."""
    done = [i for i, o in enumerate(outs) if o.result is not None]
    if not done:
        return []
    n = min(len(done), r.traffic["checked_docs"])
    longest = max(done, key=lambda i: len(docs[i]))
    rest = [i for i in done if i != longest]
    return [longest] + [int(i) for i in np.random.default_rng(
        [r.seed, 3]).choice(rest, size=n - 1, replace=False)]


def reference_checks(r, stats, docs, outs) -> dict:
    """Compare a seeded sample of served documents, the longest among
    them, with the reference: (value, limit) by name."""
    ref = r.module("refs", r.config["reference"])
    pick = sample(r, docs, outs)
    served = [(docs[i], outs[i].result.assignments, outs[i].result.theta)
              for i in pick]
    rd = ref.serve_readings(r.config["model"], stats, served,
                            seed=r.seed, n_sweeps=r.traffic["serve"]["n_sweeps"])
    return {name: (value, r.limits[name]) for name, value in rd.items()}
