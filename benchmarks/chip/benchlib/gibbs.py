"""What the plain Gibbs references share: exact counts, document blocks, and
the readings of one round against an exact collapsed Gibbs round.

A reference supplies ``log_conditional(block) -> (Db, L, E)``: each token's
exact conditional over its E outcomes at the round's start, with its own
count removed.  With ``Q(e) = Σ_i log p_i(e_i)``, an exact round draws each
token from ``p_i`` and so reaches ``Q* = −Σ_i H(p_i)`` in expectation:

* ``gibbs_gap = |1 − (Q(e_new) − Q(e_old)) / (Q* − Q(e_old))|``;
* ``stuck_docs``: the share of documents none of whose tokens changed
  outcome, less the share an exact round leaves unchanged,
  ``mean_d Π_i p_i(e_old_i)``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

DOC_BLOCK = 64    # documents per block of the (docs, positions, E) tensors


def counts(tokens: np.ndarray, mask: np.ndarray, z: np.ndarray, v: int,
           k: int, weight: np.ndarray | None = None
           ) -> tuple[np.ndarray, np.ndarray]:
    """Exact (per word-topic (V, K), per doc-topic (D, K)) int64 counts of
    assignments ``z``, each token counted ``weight`` times (default 1)."""
    m = mask.ravel()
    w, t = tokens.ravel()[m].astype(np.int64), z.ravel()[m].astype(np.int64)
    d = np.repeat(np.arange(tokens.shape[0]), tokens.shape[1])[m]
    wt = None if weight is None else weight.ravel()[m].astype(np.int64)
    n_wk = np.bincount(w * k + t, weights=wt, minlength=v * k).reshape(v, k)
    n_dk = np.bincount(d * k + t, weights=wt,
                       minlength=tokens.shape[0] * k).reshape(-1, k)
    return n_wk.astype(np.int64), n_dk.astype(np.int64)


def blocks(*arrays):
    """Blocks of ``DOC_BLOCK`` documents of each (D, ...) array, the last
    padded with zeros (documents with no tokens)."""
    d = arrays[0].shape[0]
    pad = (-d) % DOC_BLOCK
    padded = [np.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
              for a in arrays]
    for s in range(0, d + pad, DOC_BLOCK):
        yield [p[s:s + DOC_BLOCK] for p in padded]


@jax.jit
def _block_readings(logp, mask, e_old, e_new):
    m = mask.astype(jnp.float32)
    at_old = jnp.take_along_axis(logp, e_old[..., None], -1)[..., 0] * m
    at_new = jnp.take_along_axis(logp, e_new[..., None], -1)[..., 0] * m
    neg_h = jnp.where(logp > -jnp.inf, jnp.exp(logp) * logp, 0.0).sum(-1) * m
    keep_all = jnp.exp(at_old.sum(-1))
    has = mask.any(-1)
    unchanged = jnp.all((e_old == e_new) | ~mask, axis=-1) & has
    return (at_old.sum(), at_new.sum(), neg_h.sum(),
            jnp.where(has, keep_all, 0.0).sum(), unchanged.sum())


def readings(log_conditional, tokens, mask, e_old, e_new, *extra) -> dict:
    """``gibbs_gap`` and ``stuck_docs`` of one round ``e_old → e_new``;
    ``log_conditional(tokens, mask, e_old, *extra)`` gets one block of
    each array."""
    q_old = q_new = q_star = keep = same = 0.0
    for tok, msk, eo, en, *ex in blocks(tokens, mask, e_old, e_new, *extra):
        logp = log_conditional(tok, msk, eo, *ex)
        r = _block_readings(logp, jnp.asarray(msk), jnp.asarray(eo),
                            jnp.asarray(en))
        a, b, c, kp, un = (float(x) for x in r)
        q_old, q_new, q_star = q_old + a, q_new + b, q_star + c
        keep, same = keep + kp, same + un
    n_docs = int(mask.any(1).sum())
    progress = (q_new - q_old) / (q_star - q_old)
    return {"gibbs_gap": abs(1.0 - progress),
            "stuck_docs": (same - keep) / n_docs}


def draw(log_conditional, tokens, mask, e_old, seed: int, *extra
         ) -> np.ndarray:
    """One exact Jacobi round: every token drawn from its conditional."""
    key = jax.random.PRNGKey(seed % 2**31)
    out = []
    for i, (tok, msk, eo, *ex) in enumerate(blocks(tokens, mask, e_old,
                                                   *extra)):
        logp = log_conditional(tok, msk, eo, *ex)
        e = jax.random.categorical(jax.random.fold_in(key, i),
                                   logp.astype(jnp.float32))
        out.append(np.where(msk, np.asarray(e), eo))
    return np.concatenate(out)[:tokens.shape[0]].astype(np.int32)


@partial(jax.jit, static_argnames=("dtype",))
def scatter_counts(base, rows, cols_new, cols_old, *, dtype):
    """``base`` plus the round's deltas, accumulated in ``dtype``."""
    one = jnp.ones(rows.shape, dtype)
    return base.astype(dtype).at[rows, cols_new].add(one).at[
        rows, cols_old].add(-one)
