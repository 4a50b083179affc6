"""Quickstart: the unified ModelFamily + Trainer API on a synthetic
power-law corpus — the paper's MHW sampler for any registered family.

    PYTHONPATH=src python examples/quickstart.py
    PYTHONPATH=src python examples/quickstart.py --model pdp
    PYTHONPATH=src python examples/quickstart.py --model hdp --layout sorted

Walks the public API end to end: corpus → model config → ``engine.Trainer``
(pull → sample → filter → push → project rounds) → perplexity +
topics/word.  The Trainer owns the alias-table staleness cadence
(`alias_refresh_every`, the l/n refresh rule of paper §3.3) and the layout
selection: ``--layout sorted`` runs the token-sorted tile-skipping fused
kernels, ``--layout scan`` the sequential oracle.
"""

from __future__ import annotations

import argparse
import statistics

import jax
import jax.numpy as jnp

from repro.core import hdp, lda, pdp
from repro.data.synthetic import CorpusConfig, make_topic_corpus
from repro.engine import Trainer, TrainerConfig


def model_config(model: str, topics: int, vocab: int):
    """K is taken exactly as given (for HDP it is the truncation level —
    pass a value above the expected topic count, e.g. 2× the corpus's)."""
    if model == "lda":
        return lda.LDAConfig(n_topics=topics, vocab_size=vocab, alpha=0.1,
                             beta=0.01, mh_steps=2)
    if model == "pdp":
        return pdp.PDPConfig(n_topics=topics, vocab_size=vocab, alpha=0.1,
                             discount=0.1, concentration=5.0, mh_steps=4,
                             stirling_n_max=256)
    return hdp.HDPConfig(n_topics=topics, vocab_size=vocab, b0=1.0,
                         b1=2.0, mh_steps=4)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=["lda", "pdp", "hdp"], default="lda")
    ap.add_argument("--layout", choices=["scan", "sorted"], default="scan")
    ap.add_argument("--method", choices=["mhw", "exact"], default="mhw")
    ap.add_argument("--topics", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=500)
    ap.add_argument("--docs", type=int, default=256)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--clients", type=int, default=1)
    ap.add_argument("--alias-refresh-every", type=int, default=2,
                    help="rounds between alias-table rebuilds (staleness)")
    args = ap.parse_args()

    tokens, mask, _ = make_topic_corpus(CorpusConfig(
        n_topics=args.topics, vocab_size=args.vocab, n_docs=args.docs,
        doc_len=64, seed=0))
    tokens, mask = jnp.asarray(tokens), jnp.asarray(mask)
    n_tokens = int(mask.sum())
    cfg = model_config(args.model, args.topics, args.vocab)
    print(f"corpus: {args.docs} docs, {n_tokens} tokens, V={args.vocab}, "
          f"K={cfg.n_topics}, model={args.model}, layout={args.layout}")
    trainer = Trainer(cfg, tokens, mask, config=TrainerConfig(
        layout=args.layout, method=args.method, n_clients=args.clients,
        alias_refresh_every=args.alias_refresh_every),
        key=jax.random.PRNGKey(0))

    eval_every = max(1, args.iters // 4)
    res = trainer.run(args.iters, eval_every=eval_every, eval_docs=32)
    for i, ppl in enumerate(res.perplexities):
        tpw = res.topics_per_word[i]
        print(f"eval {i}: perplexity={ppl:8.2f}  topics/word={tpw:5.2f}")
    print(f"round time: {statistics.median(res.iter_times) * 1e3:8.1f} ms "
          f"(median of {len(res.iter_times)} rounds)")

    err = trainer.consistency_error()
    print("done — sufficient-statistics consistency:",
          "OK" if err == 0.0 else f"VIOLATED (max err {err})")


if __name__ == "__main__":
    main()
