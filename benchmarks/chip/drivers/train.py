"""Training traffic: ``Trainer`` rounds over a seeded Zipf corpus.

Set-up makes the corpus from the seed, builds the ``Trainer`` the traffic
file describes and runs round 0, which compiles the round program and makes
the full alias build.  The window then runs rounds 1, 2, ... through
``Trainer.step``, each ended by ``block_until_ready``, and stops before a
round that would end past ``--seconds``.

``train_tokens_per_s`` is the tokens the window's rounds sampled (unmasked
tokens x sweeps per round) over the time from the window's start to the end
of its last round.

The reference (``refs/<config reference>.py``) then reads, from the
assignments copied after each of the window's first ``checked_rounds``
rounds and the statistics the program keeps at the end:
``count_gap`` (every push landed once), and per checked round
``gibbs_gap`` and ``stuck_docs`` (the round moved the assignments as an
exact Gibbs round would).
"""

from __future__ import annotations

import gc
import time

import numpy as np


def corpus(r, n_docs: int):
    from benchlib.corpus import make_corpus
    model, c = r.config["model"], r.traffic["corpus"]
    tokens, mask, _ = make_corpus(
        n_topics=model["n_topics"], vocab_size=model["vocab_size"],
        n_docs=n_docs, doc_len=c["doc_len"], theta_conc=c["theta_conc"],
        zipf_a=c["zipf_a"], min_len=c["min_len"], seed=r.seed)
    return tokens, mask


def build_trainer(r, tokens, mask):
    import jax

    from repro.core import family as family_mod
    from repro.engine import Trainer, TrainerConfig

    fam = family_mod.get(r.config["family"])
    cfg = fam.config_cls(**r.config["model"])
    key = jax.random.PRNGKey(np.random.default_rng(r.seed).integers(2**31))
    trainer = Trainer(cfg, tokens, mask,
                      config=TrainerConfig(**r.traffic["trainer"]), key=key)
    return fam, cfg, trainer


def run(r, devs) -> dict:
    import jax
    import jax.numpy as jnp

    ref = r.module("refs", r.config["reference"])
    tokens, mask = corpus(r, r.traffic["corpus"]["n_docs"])
    fam, cfg, trainer = build_trainer(r, tokens, mask)
    tcfg = trainer.tcfg
    tokens_per_round = trainer.n_tokens * tcfg.tau
    n_check = r.traffic["checked_rounds"]

    def assignments():
        """Copies of the state the reference reads a round from."""
        local = fam.local_dict(trainer.locals_[0])
        state = {n: jnp.copy(local[n]) for n in ref.LOCAL_STATE}
        if ref.SHARED_STATE:
            shared = fam.stats_dict(trainer.shared)
            state.update({n: jnp.copy(shared[n]) for n in ref.SHARED_STATE})
        return state

    with r.span("train.step"):
        trainer.step()                    # round 0: compiles, full alias
    jax.block_until_ready(trainer.locals_)
    states = [assignments()]
    jax.block_until_ready(states)
    r.log(f"set-up: {tokens.shape[0]} docs, {trainer.n_tokens} tokens, "
          f"round program traces {trainer.round_traces}")

    traces0 = trainer.round_traces
    rounds, last_dt = 0, 0.0
    t0 = time.perf_counter()
    with r.window():
        while True:
            t_r = time.perf_counter()
            with r.span("train.step"):
                trainer.step()
            if len(states) <= n_check:
                states.append(assignments())
            with r.span("train.sync"):
                jax.block_until_ready(trainer.locals_)
            rounds += 1
            last_dt = time.perf_counter() - t_r
            if time.perf_counter() - t0 + last_dt > r.seconds:
                break
        t1 = time.perf_counter()
    rate = rounds * tokens_per_round / (t1 - t0)
    retraces = trainer.round_traces - traces0
    memory_peak = int(devs[0].memory_stats()["peak_bytes_in_use"])
    ma = trainer.lower_round().compile().memory_analysis()
    if ma is not None:
        r.counters["round_memory_analysis"] = {
            k: int(getattr(ma, k)) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes")}
    r.counters.update(rounds=rounds, window_s=t1 - t0,
                      compile_events_in_window=r.compiles_between(t0, t1),
                      tokens_per_round=tokens_per_round,
                      last_round_s=last_dt, retraces_in_window=retraces,
                      round_chunks=chunk_shapes(cfg.sorted_chunks, tokens, mask),
                      memory_peak_bytes=memory_peak)
    r.log(f"window: {rounds} rounds in {t1 - t0:.3f}s, {rate:.1f} tokens/s, "
          f"{retraces} retraces, {r.counters['compile_events_in_window']} "
          f"compile events, peak {memory_peak} B")

    # --- the reference, once the program's state is freed ----------------
    kept = {n: np.asarray(v) for n, v in fam.stats_dict(trainer.shared).items()}
    kept.update({n: np.asarray(v) for n, v in
                 fam.local_dict(trainer.locals_[0]).items()})
    states = [{n: np.asarray(v) for n, v in s.items()} for s in states]
    del trainer
    gc.collect()
    t_ref = time.perf_counter()
    checks = reference_checks(ref, r, tokens, mask, states, kept)
    r.counters["reference_s"] = time.perf_counter() - t_ref
    return {"window_start": t0, "attempted": rounds,
            "failed": 0, "e2e": {"train_tokens_per_s": rate},
            "checks": checks, "memory_peak": memory_peak}


def reference_checks(ref, r, tokens, mask, states, kept) -> dict:
    """Every compared number with its limit: (value, limit) by name."""
    lim, model = r.limits, r.config["model"]
    out = {"count_gap": (ref.count_gap(model, tokens, mask, kept),
                         lim["count_gap"])}
    for i in range(1, len(states)):
        rd = ref.round_readings(model, tokens, mask, states[i - 1], states[i])
        for name, value in rd.items():
            out[f"{name}.round{i}"] = (value, lim[name])
    return out


def chunk_shapes(n_chunks: int, tokens, mask) -> list[dict]:
    """Per position chunk of one sweep (the configuration's
    ``sorted_chunks`` equal spans of positions): its tokens and distinct
    token types, what ``work/`` counts a sweep call from."""
    l = tokens.shape[1]
    n = max(1, min(n_chunks, l))
    bounds = [round(i * l / n) for i in range(n + 1)]
    out = []
    for s, e in zip(bounds[:-1], bounds[1:]):
        m = mask[:, s:e]
        out.append({"tokens": int(m.sum()),
                    "rows": int(np.unique(tokens[:, s:e][m]).size)})
    return out
