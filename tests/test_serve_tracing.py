"""The program's host spans (``repro.*``) in a JAX profiler trace.

A tiny ``InferenceServer`` serves a few concurrent requests and a tiny
``Trainer`` runs two rounds under ``jax.profiler``; the trace, read back
with ``ProfileData``, must hold every span with its metadata, nested and
counted as the serving engine's and the trainer's own counters say.
"""

from __future__ import annotations

import glob
import threading
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import family as fam_mod
from repro.data.synthetic import CorpusConfig, make_topic_corpus
from repro.engine import Trainer, TrainerConfig
from repro.serve import ServeConfig, freeze
from repro.serve.client import InferenceClient
from repro.serve.server import InferenceServer
from tests.conftest import make_family_cfg, make_synthetic_corpus

V, K, LEN = 16, 4, 8
N_REQUESTS = 5
ROUNDS = 2
SERVE_SPANS = ("repro.serve.wait", "repro.serve.admit", "repro.serve.step",
               "repro.serve.uniforms", "repro.serve.harvest",
               "repro.serve.fetch")


def _spans(trace_dir) -> list[dict]:
    """Every ``repro.*`` event of the host planes, with its thread."""
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb",
                            recursive=True))[-1]
    with open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append({"name": ev.name, "start": ev.start_ns,
                                "end": ev.start_ns + ev.duration_ns,
                                "thread": (plane.name, li),
                                "stats": dict(ev.stats)})
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Serve N_REQUESTS concurrent documents into 2 slots, then run
    ROUNDS trainer rounds, all under one profiler trace; returns the
    spans and what the program counted meanwhile."""
    fam = fam_mod.get("lda")
    cfg = fam.config_cls(n_topics=K, vocab_size=V)
    tokens, mask, _ = make_topic_corpus(CorpusConfig(
        n_topics=K, vocab_size=V, n_docs=8, doc_len=LEN, seed=0))
    _, shared = fam.init_state(cfg, tokens, mask, jax.random.PRNGKey(0))
    srv = InferenceServer(freeze(cfg, shared),
                          ServeConfig(max_slots=2, max_len=LEN, n_sweeps=2)
                          ).start()
    ttok, tmask, _ = make_synthetic_corpus(n_topics=4, vocab=32, n_docs=8,
                                           doc_len=8, seed=3)
    trainer = Trainer(make_family_cfg("lda", n_topics=4, vocab_size=32),
                      ttok, tmask, config=TrainerConfig(layout="sorted"))
    eng = srv.engine
    sweeps0, admitted0 = eng.sweeps_run, eng.docs_admitted
    round0 = trainer.round_idx
    trace_dir = tmp_path_factory.mktemp("trace")
    addr = "%s:%d" % srv.address
    errors: list[str] = []

    def request(uid: int) -> None:
        try:
            with InferenceClient(addr, timeout=120.0) as cl:
                cl.infer(uid, np.arange(3 + uid) % V, seed=uid)
        except Exception as e:          # reported by the fixture's assert
            errors.append(repr(e))

    jax.profiler.start_trace(str(trace_dir))
    try:
        time.sleep(0.3)                 # the idle batcher waits meanwhile
        threads = [threading.Thread(target=request, args=(u,))
                   for u in range(N_REQUESTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for _ in range(ROUNDS):
            trainer.step()
        jax.block_until_ready(trainer.locals_)
    finally:
        jax.profiler.stop_trace()
        srv.close()
    assert not errors, errors
    return {"spans": _spans(trace_dir),
            "sweeps": eng.sweeps_run - sweeps0,
            "admitted": eng.docs_admitted - admitted0,
            "rounds": list(range(round0, trainer.round_idx))}


def _named(traced, name):
    return [s for s in traced["spans"] if s["name"] == name]


def test_every_span_is_present(traced):
    names = {s["name"] for s in traced["spans"]}
    assert names >= set(SERVE_SPANS) | {"repro.train.step"}


def test_uniforms_nest_in_step_on_the_batcher_thread(traced):
    steps = _named(traced, "repro.serve.step")
    uniforms = _named(traced, "repro.serve.uniforms")
    assert uniforms
    for u in uniforms:
        assert any(s["thread"] == u["thread"] and s["start"] <= u["start"]
                   and u["end"] <= s["end"] for s in steps), u
    # One batcher thread opens every serving span.
    assert len({s["thread"] for s in traced["spans"]
                if s["name"] in SERVE_SPANS}) == 1
    assert {u["stats"]["chunk"] for u in uniforms} <= set(range(LEN))
    assert all(s["stats"]["live"] >= 1 for s in steps)


def test_step_spans_count_the_sweeps(traced):
    assert traced["sweeps"] > 0
    assert len(_named(traced, "repro.serve.step")) == traced["sweeps"]
    harvests = _named(traced, "repro.serve.harvest")
    assert sum(h["stats"]["done"] for h in harvests) == N_REQUESTS
    assert len(_named(traced, "repro.serve.fetch")) == len(harvests)


def test_admit_spans_count_the_admissions(traced):
    admits = _named(traced, "repro.serve.admit")
    assert traced["admitted"] == N_REQUESTS
    assert len(admits) == traced["admitted"]
    assert sorted(a["stats"]["uid"] for a in admits) == list(
        range(N_REQUESTS))
    # Stamped at enqueue: no wait is negative or longer than the test.
    assert all(0 <= a["stats"]["queue_wait_us"] < 120e6 for a in admits)


def test_train_step_spans_count_the_rounds(traced):
    steps = sorted(_named(traced, "repro.train.step"),
                   key=lambda s: s["start"])
    assert len(steps) == ROUNDS
    assert [s["stats"]["round"] for s in steps] == traced["rounds"]
