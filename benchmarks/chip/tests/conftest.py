"""Fixtures for the chip benchmark's own tests (they run on the CPU)."""

from __future__ import annotations

import json
import shutil

import pytest

from chiptest import CHIP, REPO, TINY_CORPUS, TINY_MODEL


@pytest.fixture
def bench_copy(tmp_path):
    """A checkout holding a copy of the benchmark, the real program, and a
    BENCHMARK.json whose one cell ``tiny_train`` names files dropped into
    the copy alone."""
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns(
        "out", "__pycache__", "tests"))
    (tmp_path / "src").symlink_to(REPO / "src")
    cfg = json.loads((CHIP / "configs" / "lda_k1024_v131072.json").read_text())
    cfg["model"] = TINY_MODEL
    (chip / "configs" / "tiny_lda.json").write_text(json.dumps(cfg))
    traffic = json.loads((CHIP / "traffic" / "train_bsp_zipf.json").read_text())
    traffic["corpus"] = TINY_CORPUS
    (chip / "traffic" / "tiny_train.json").write_text(json.dumps(traffic))
    limits = json.loads(
        (CHIP / "limits" / "lda_k1024_train_bsp.json").read_text())
    (chip / "limits" / "tiny_train.json").write_text(json.dumps(limits))
    serve = json.loads(
        (CHIP / "traffic" / "serve_open_poisson.json").read_text())
    serve.update(corpus=TINY_CORPUS, pool_docs=4, rate_per_s=30.0,
                 checked_docs=100,
                 serve={"max_slots": 4, "max_len": 32, "n_sweeps": 3})
    (chip / "traffic" / "tiny_serve.json").write_text(json.dumps(serve))
    # The tiny cell's 3 sweeps over a few hundred tokens read a log-joint
    # gap of up to ~0.2 nats per token when sound (8 seeds), a conditional
    # without the document term 0.55-0.65.
    limits = json.loads(
        (CHIP / "limits" / "lda_k1024_serve_open.json").read_text())
    limits["foldin_logp_gap"] = 0.4
    (chip / "limits" / "tiny_serve.json").write_text(json.dumps(limits))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="tiny_lda"))
    bench["workloads"] = [
        {"name": "tiny_train", "config": "tiny_lda", "traffic": "tiny_train",
         "chips": 1, "why": "tiny"},
        {"name": "tiny_serve", "config": "tiny_lda", "traffic": "tiny_serve",
         "chips": 1, "why": "tiny"}]
    rename = {"lda_k1024_train_bsp": "tiny_train",
              "lda_k1024_serve_open": "tiny_serve"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [rename[w] for w in m["workloads"]
                              if w in rename]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path
